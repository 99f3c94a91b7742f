"""What the algorithm needs, counted from shapes: FLOPs and bytes of the
fused TCN block kernel and of one served sample, and the chip's peaks.

These counts are the yardstick of ``tcn_block_roofline.*`` and
``step_mfu.*``: they count the work the algorithm requires, not what a
given kernel happens to read, so a kernel that reads only its tap windows
cannot read above 100% of the roofline.
"""

from __future__ import annotations

import json
import re
from pathlib import Path

PEAKS_FILE = Path(__file__).resolve().parent / "peaks.json"

# Device-trace op names of the fused block kernel (kernels/tcn_block.py).
# Its ``pallas_call`` has no ``name=``: in the trace each call is a Mosaic
# custom call named after the jitted chunk step that holds it
# (``%_scan_fused.<n> = ... custom_call_target="tpu_custom_call"``, one per
# block), and that step holds no other Pallas kernel.
TCN_BLOCK_OPS = re.compile(
    r'^%_scan_fused(\.\d+)? = .*custom_call_target="tpu_custom_call"')


def peaks(device_kind: str) -> dict:
    """The published peaks of one chip of ``device_kind``.  A kind that is
    not in ``peaks.json`` is an error, never a default."""
    table = json.loads(PEAKS_FILE.read_text())
    if device_kind not in table:
        raise ValueError(f"no peaks for device kind {device_kind!r} in "
                         f"{PEAKS_FILE.name}; known: {sorted(table)}")
    return table[device_kind]


def blocks(model: dict):
    """(c_in, c_out, dilation) of each residual block."""
    c_in = model["tcn_in_channels"]
    for i, c in enumerate(model["tcn_channels"]):
        yield c_in, c, 2 ** i
        c_in = c


def block_flops(T: int, c_in: int, c: int, k: int, lanes: int = 1) -> int:
    """FLOPs of one fused block over ``lanes`` slots of T samples: both
    k-tap convs, and the 1x1 downsample where the width changes."""
    macs = k * c_in * c + k * c * c + (c_in * c if c_in != c else 0)
    return 2 * lanes * T * macs


def block_bytes(T: int, c_in: int, c: int, k: int, d: int, lanes: int = 1,
                itemsize: int = 4) -> int:
    """Bytes one fused block call must move: for each conv the distinct
    input rows its k taps touch, min(k*T, (k-1)*d + T), per lane; the
    weights and biases once; both outputs (block output and conv1
    activation) per lane."""
    rows = min(k * T, (k - 1) * d + T)
    inputs = lanes * rows * (c_in + c)
    weights = k * c_in * c + c + k * c * c + c
    if c_in != c:
        weights += c_in * c + c
    outputs = 2 * lanes * T * c
    return itemsize * (inputs + weights + outputs)


def chunk_kernel_counts(model: dict, T: int, lanes: int) -> list[tuple[int, int]]:
    """(flops, bytes) of every block kernel call of one chunk step."""
    k = model["tcn_kernel"]
    return [(block_flops(T, ci, c, k, lanes), block_bytes(T, ci, c, k, d, lanes))
            for ci, c, d in blocks(model)]


def kernel_calls(model: dict, dispatches: list, chips: int) -> int:
    """Block kernel events a trace holds for these ``dispatch`` spans: one
    per block, dispatch and chip."""
    return len(model["tcn_channels"]) * len(dispatches) * chips


def least_time(counts, peak: dict) -> tuple[float, str]:
    """Least seconds the chip needs for kernel calls of (flops, bytes),
    each bound by the larger of compute and memory time, and which of the
    two bounds most of that time."""
    t = {"compute": 0.0, "memory": 0.0}
    for f, b in counts:
        tc = f / peak["bf16_flops_per_s"]
        tm = b / peak["hbm_bytes_per_s"]
        t["compute" if tc >= tm else "memory"] += max(tc, tm)
    return t["compute"] + t["memory"], max(t, key=t.get)


def sample_flops(model: dict, n_ways: int) -> int:
    """Model FLOPs of one served sample: every block, the embedding head,
    the class head and the tenant's banked head of ``n_ways`` ways."""
    k = model["tcn_kernel"]
    macs = sum(block_flops(1, ci, c, k) // 2 for ci, c, _ in blocks(model))
    v = model["embed_dim"]
    macs += model["tcn_channels"][-1] * v + v * model["n_classes"] + v * n_ways
    return 2 * macs
