"""Reduce a profiler trace (``.xplane.pb``) to the numbers the benchmark
reports: device busy and idle time, device time per op, and the longest
idle gaps, each named by the host span it fell in.

Device ops are the events of the ``device_line`` line of every plane that
matches ``device_plane`` (on a TPU: ``/device:TPU:<n>``, line ``XLA Ops``).
Host spans are the ``TraceAnnotation`` events of the host plane; the
benchmark hands the program a tracer that writes each of its spans there
too.  The window is the first host event named ``window`` (the benchmark's
measured window), else the extent of the device ops.

    python3 benchmarks/chip/trace_reduce.py <file.xplane.pb>
"""

from __future__ import annotations

import json
import re
import sys
from collections import defaultdict

TPU_PLANE = r"^/device:TPU:\d+$"
OPS_LINE = r"^XLA Ops$"
HOST_PLANE = r"^/host:CPU$"
WINDOW = "bench_window"
NO_SPAN = "(no host span)"


def _union(intervals):
    """Merged, sorted list of (start, end) intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def load(path: str):
    """The planes of an ``.xplane.pb`` file (``jax.profiler.ProfileData``)."""
    from jax.profiler import ProfileData
    return ProfileData.from_file(path)


def reduce(profile, *, device_plane: str = TPU_PLANE,
           device_line: str = OPS_LINE, host_plane: str = HOST_PLANE,
           window: str = WINDOW, span_names=None, top: int = 10) -> dict:
    """Summarize ``profile`` (a ``ProfileData`` or a path).

    Returns ``busy_s`` (union of device op time in the window, averaged
    over devices), ``window_s``, ``n_devices``, ``ops`` ({name: device
    seconds summed over devices}), ``op_counts``, ``device_ops`` (the
    ``top`` ops by time, [[name, s], ...]) and ``idle_gaps`` (the ``top``
    longest gaps on device 0, [[host span, s], ...]).  Times in seconds.
    ``span_names`` limits which host events may name a gap."""
    if isinstance(profile, str):
        profile = load(profile)
    dev_re, line_re = re.compile(device_plane), re.compile(device_line)
    host_re = re.compile(host_plane)
    devices, host = [], []
    for plane in profile.planes:
        if dev_re.search(plane.name):
            evs = []
            for line in plane.lines:
                if line_re.search(line.name):
                    evs += [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                            for e in line.events]
            devices.append(evs)
        if host_re.search(plane.name):
            for line in plane.lines:
                host += [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                         for e in line.events if e.duration_ns > 0]
    devices = [d for d in devices if d]
    win = [(s, e) for n, s, e in host if n == window]
    if win:
        lo, hi = win[0]
    elif devices:
        lo = min(s for d in devices for _, s, _ in d)
        hi = max(e for d in devices for _, _, e in d)
    else:
        raise ValueError("trace holds no device op and no window event")
    ops, counts = defaultdict(float), defaultdict(int)
    busy = []
    for d in devices:
        inside = [(n, s, e) for n, s, e in d if e > lo and s < hi]
        for n, s, e in inside:
            ops[n] += (min(e, hi) - max(s, lo)) * 1e-9
            counts[n] += 1
        busy.append(_union(_clip([(s, e) for _, s, e in inside], lo, hi)))
    busy_s = (sum(e - s for b in busy for s, e in b) / len(busy) * 1e-9
              if busy else 0.0)
    gaps = []
    if busy:
        edges = [lo] + [x for iv in busy[0] for x in iv] + [hi]
        gaps = [(s, e) for s, e in zip(edges[::2], edges[1::2]) if e > s]
    gaps = sorted(gaps, key=lambda g: g[0] - g[1])[:top]
    spans = [(n, s, e) for n, s, e in host
             if n != window and (span_names is None or n in span_names)]
    named = [(_host_span(spans, s, e), (e - s) * 1e-9) for s, e in gaps]
    return {"busy_s": busy_s, "window_s": (hi - lo) * 1e-9,
            "n_devices": len(busy), "ops": dict(ops),
            "op_counts": dict(counts),
            "device_ops": [[short_name(n), t] for n, t in
                           sorted(ops.items(), key=lambda kv: -kv[1])[:top]],
            "idle_gaps": [[n, t] for n, t in named]}


_OPCODE = re.compile(r"(?:^|[\s)])([a-z][a-z0-9-]*)\(")
_SHAPE = re.compile(r"\(?(\w+\[[^\]]*\])")


def short_name(op: str) -> str:
    """An HLO op's trace name cut to its instruction, opcode (or custom
    call target) and first result shape: the full name holds every
    operand and layout."""
    if " = " not in op:
        return op
    inst, rest = op.split(" = ", 1)
    target = re.search(r'custom_call_target="([^"]+)"', rest)
    code = _OPCODE.search(rest)
    shape = _SHAPE.match(rest)
    return " ".join(x for x in (inst, target.group(1) if target else
                                code.group(1) if code else "",
                                shape.group(1) if shape else "") if x)


def _host_span(spans, s, e):
    """The innermost host span that holds the middle of [s, e)."""
    mid = (s + e) / 2
    best = None
    for n, a, b in spans:
        if a <= mid < b and (best is None or b - a < best[1]):
            best = (n, b - a)
    return best[0] if best else NO_SPAN


def op_time(summary: dict, pattern) -> tuple[float, int]:
    """(device seconds, events) of the ops whose name matches ``pattern``."""
    rx = re.compile(pattern) if isinstance(pattern, str) else pattern
    t = sum(v for n, v in summary["ops"].items() if rx.search(n))
    c = sum(v for n, v in summary["op_counts"].items() if rx.search(n))
    return t, c


if __name__ == "__main__":
    out = reduce(sys.argv[1])
    out.pop("ops")
    out.pop("op_counts")
    print(json.dumps(out, indent=1))
