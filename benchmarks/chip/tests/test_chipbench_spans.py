"""The readers of the request's host time: the plane's queue wait and ack
delay (``plane_op`` events) and the service's phases of dispatch and
enroll (``prepare``, ``launch``, ``fetch``, ``assemble``, ``embed``
spans).  Both cells run traced at the tiny CPU size; the readers are
also held to exact quantiles and to silence on a program without the
events they read."""

from types import SimpleNamespace as NS

import chip_tiny
import numpy as np
import pytest
from chip_tiny import BENCH, ROOT

from benchmarks.chip import harness

NEW = {"audio-stream": ("queue_ms.stream", "prepare_ms.stream",
                        "launch_ms.stream", "fetch_ms.stream",
                        "assemble_ms.stream"),
       "cl-250way": ("enroll_queue_ms.cl", "enroll_held_ms.cl",
                     "embed_ms.cl")}
# the accepted metrics a CPU run gives (no peaks, no TPU kernel events)
OLD_ON_CPU = {"audio-stream": ("batch_lanes.stream", "host_ms.stream",
                               "dispatch_ms.stream", "idle_share.stream"),
              "cl-250way": ("enroll_ms.cl", "dispatch_ms.cl",
                            "idle_share.cl")}

# the spans that split ``dispatch``, ``enroll`` and ``bind``
CHILDREN = {"prepare", "launch", "device_wait", "fetch", "assemble", "embed",
            "bank_update", "reset", "bank_park", "bank_unpark"}


def _reader(name):
    return harness.load_module(ROOT / BENCH / "layer_metrics" / f"{name}.py")


@pytest.mark.parametrize("workload", sorted(NEW))
def test_span_metrics_reported_in_a_traced_run(tmp_path, workload):
    root = chip_tiny.make(tmp_path)
    rc, res = chip_tiny.run(root, workload, trace=1)
    assert rc == 0 and res["correct"], res
    m = {k: v["value"] for k, v in res["metrics"].items()}
    for name in NEW[workload] + OLD_ON_CPU[workload]:
        assert name in m and m[name] > 0, (name, m)
    for name in NEW[workload]:
        assert res["metrics"][name]["unit"] == "ms"
    if workload == "audio-stream":
        assert m["launch_ms.stream"] + m["fetch_ms.stream"] \
            <= m["dispatch_ms.stream"]
    # the new spans name the device's idle gaps they hold
    names = {n for n, _ in res["breakdown"]["idle_gaps"]}
    assert names & CHILDREN, names


def _ctx(spans):
    return NS(spans=spans, spans_named=lambda n: [
        e for e in spans if e.get("name") == n and e.get("ph") == "X"])


def _op(kind, queue_us, held_us, ok=True):
    return {"name": "plane_op", "ph": "X", "ts": 0.0, "dur": 1e5,
            "args": {"kind": kind, "queue_us": queue_us, "held_us": held_us,
                     "ok": ok}}


@pytest.mark.parametrize("name", sorted(n for ns in NEW.values()
                                        for n in ns))
def test_readers_silent_without_their_events(name):
    """A program without the plane's op events or the service's phase
    spans (as before they existed) gives no value, and no error."""
    spans = [{"name": "dispatch", "ph": "X", "ts": 0.0, "dur": 10.0,
              "args": {"shape": "T160", "lanes": 4, "fused": True}},
             {"name": "enroll", "ph": "X", "ts": 20.0, "dur": 5.0,
              "args": {}}]
    assert _reader(name).read(_ctx(spans)) is None


def test_plane_op_readers_take_exact_quantiles_of_their_kind():
    rng = np.random.default_rng(3)
    q, h = rng.exponential(2e3, 101), rng.exponential(5e2, 101)
    spans = [_op("enroll", a, b) for a, b in zip(q, h)]
    spans += [_op("push", 1e9, 1e9, ok=False),       # failed: not read
              _op("enroll", 1e9, 1e9, ok=False),
              _op("open", 1e9, 1e9)]
    spans += [_op("push", a / 4, b) for a, b in zip(q, h)]
    ctx = _ctx(spans)
    assert _reader("enroll_queue_ms.cl").read(ctx) == pytest.approx(
        np.percentile(q, 95) / 1e3, rel=1e-12)
    assert _reader("enroll_held_ms.cl").read(ctx) == pytest.approx(
        np.percentile(h, 95) / 1e3, rel=1e-12)
    assert _reader("queue_ms.stream").read(ctx) == pytest.approx(
        np.percentile(q / 4, 95) / 1e3, rel=1e-12)
