"""Chrome-trace / Perfetto span tracer for host-side scheduler decisions.

Records the serving plane's *time-structured* story — what the metrics
registry aggregates away: when each dispatch ran and how long (keyed by
compiled-program shape), which bind evicted which victim and what the
parked blob cost, pack/unpack transfers, park/resume, prefill chunks,
speculative verify rounds.  Events are ring-buffered (bounded memory, the
newest ``capacity`` events win) and exported as Trace Event Format JSON —
open the file at https://ui.perfetto.dev or chrome://tracing and the slot
grid's schedule is a flame chart.

Usage:

    from repro.obs import trace
    with trace.span("dispatch", cat="grid", shape="T160", lanes=12):
        ...                       # complete event "X" with measured dur
    trace.instant("evict", sid=3, cost_bytes=1892)
    trace.counter("parking", parked=7, bound=16)
    trace.complete("plane_op", t_enqueue_ns, t_done_ns, cat="plane", id=9)
    trace.export("trace.json")

A DISABLED tracer (the default) costs one attribute load and a truthiness
check per call — ``span()`` returns a shared no-op context manager, so the
hot path pays nothing measurable.  Activation:

  * ``REPRO_TRACE=/path/trace.json`` — enables the process-global tracer
    at import time and registers an atexit export to that path (how the CI
    bench job captures its trace artifact);
  * ``Tracer(enabled=True)`` / ``tracer.enable()`` — programmatic.

``Tracer(annotate=True)`` also enters a ``jax.profiler.TraceAnnotation``
for every ``span()``, so an XProf profile of a live server shows the
plane's and the services' spans on the device trace's clock and names
device idle gaps by them.  ``complete()`` events are never annotated:
they are recorded after the fact and overlap each other.

Services accept a ``tracer=`` argument and default to the global one, so
a test can hand a private enabled tracer to one service without touching
the environment.
"""

from __future__ import annotations

import atexit
import json
import os
import threading
import time
from collections import deque

ENV_VAR = "REPRO_TRACE"


class _NullSpan:
    """Shared no-op context manager — the disabled tracer's span()."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


_NULL_SPAN = _NullSpan()


def _annotation(name: str):
    from jax.profiler import TraceAnnotation
    return TraceAnnotation(name)


class _Span:
    __slots__ = ("_tracer", "name", "cat", "args", "_t0", "_ann")

    def __init__(self, tracer, name, cat, args):
        self._tracer = tracer
        self.name = name
        self.cat = cat
        self.args = args
        self._ann = _annotation(name) if tracer.annotate else None

    def __enter__(self):
        if self._ann is not None:
            self._ann.__enter__()
        self._t0 = time.perf_counter_ns()
        return self

    def __exit__(self, *exc):
        self._tracer.complete(self.name, self._t0, time.perf_counter_ns(),
                              self.cat, **self.args)
        if self._ann is not None:
            self._ann.__exit__(*exc)
        return False


class Tracer:
    """Ring-buffered Trace Event Format recorder.

    ``ts``/``dur`` are microseconds on the ``time.perf_counter_ns`` clock
    (monotonic; one clock for every event, so spans nest correctly in the
    viewer).  Thread identity comes from ``threading.get_native_id`` so a
    future multi-worker front-end traces onto separate rows for free.
    ``annotate=True`` mirrors every ``span()`` into the JAX profiler
    (see the module docstring)."""

    def __init__(self, *, enabled: bool = False, capacity: int = 65536,
                 pid: int | None = None, annotate: bool = False):
        self.enabled = enabled
        self.annotate = annotate
        self.pid = os.getpid() if pid is None else pid
        self._events: deque = deque(maxlen=capacity)
        self.dropped = 0  # events that fell off the ring

    def enable(self) -> None:
        self.enabled = True

    def disable(self) -> None:
        self.enabled = False

    def _tid(self) -> int:
        return threading.get_native_id()

    def _push(self, ev: dict) -> None:
        if len(self._events) == self._events.maxlen:
            self.dropped += 1
        self._events.append(ev)

    # -- recording ----------------------------------------------------------
    def span(self, name: str, cat: str = "", **args):
        """Context manager for a complete ("X") event: duration measured
        between __enter__ and __exit__."""
        if not self.enabled:
            return _NULL_SPAN
        return _Span(self, name, cat, args)

    def complete(self, name: str, t0_ns: int, t1_ns: int, cat: str = "",
                 **args) -> None:
        """Record a complete ("X") event after the fact, from ``t0_ns`` to
        ``t1_ns`` on the ``time.perf_counter_ns`` clock — for an extent
        whose ends are stamped in different places (an op's life from
        enqueue to the end of the cycle that served it)."""
        if not self.enabled:
            return
        self._push({"name": name, "ph": "X", "cat": cat or "repro",
                    "ts": t0_ns / 1e3, "dur": (t1_ns - t0_ns) / 1e3,
                    "pid": self.pid, "tid": self._tid(), "args": args})

    def instant(self, name: str, cat: str = "", **args) -> None:
        """Zero-duration marker ("i" event) — scheduler decisions (evict,
        retire, admit) that have a moment, not an extent."""
        if not self.enabled:
            return
        self._push({"name": name, "ph": "i", "s": "t",
                    "cat": cat or "repro", "ts": time.perf_counter_ns() / 1e3,
                    "pid": self.pid, "tid": self._tid(), "args": args})

    def counter(self, name: str, **values) -> None:
        """Chrome "C" counter event — renders as a stacked area track
        (e.g. bound vs parked session counts over the run)."""
        if not self.enabled:
            return
        self._push({"name": name, "ph": "C",
                    "ts": time.perf_counter_ns() / 1e3,
                    "pid": self.pid, "tid": self._tid(), "args": values})

    # -- export -------------------------------------------------------------
    def events(self) -> list[dict]:
        return list(self._events)

    def clear(self) -> None:
        self._events.clear()
        self.dropped = 0

    def export(self, path: str) -> str:
        """Write Trace Event Format JSON ({"traceEvents": [...]}) —
        loadable by Perfetto and chrome://tracing as-is."""
        doc = {"traceEvents": self.events(),
               "displayTimeUnit": "ms",
               "otherData": {"producer": "repro.obs.trace",
                             "dropped_events": self.dropped}}
        with open(path, "w") as f:
            json.dump(doc, f)
        return path


# -- the process-global tracer ----------------------------------------------
# REPRO_TRACE=path enables it at import time and exports on process exit;
# services default to this tracer, so the env var alone instruments a whole
# run with zero code changes (the bench jobs use exactly this).

TRACE_PATH = os.environ.get(ENV_VAR, "").strip()
trace = Tracer(enabled=bool(TRACE_PATH))

if TRACE_PATH:  # pragma: no cover - exercised via subprocess in tests
    atexit.register(lambda: trace.export(TRACE_PATH))


def get_tracer() -> Tracer:
    return trace
