"""trace_reduce: device busy and idle time, per-op time and idle gaps named
by host spans, on a synthetic profile and on a trace recorded on the CPU
(``data/cpu_window.xplane.pb``: three jitted matmuls in ``dispatch`` spans,
each followed by a 20 ms sleep in a ``plane_batch`` span, all inside the
``bench_window`` span)."""

from types import SimpleNamespace as NS

import pytest
from chip_tiny import ROOT

from benchmarks.chip import harness, trace_reduce

DATA = ROOT / "benchmarks" / "chip" / "tests" / "data" / "cpu_window.xplane.pb"


def ev(name, start, dur):
    return NS(name=name, start_ns=start, duration_ns=dur)


def profile():
    dev = lambda i, evs: NS(name=f"/device:TPU:{i}",
                            lines=[NS(name="XLA Ops", events=evs),
                                   NS(name="XLA Modules",
                                      events=[ev("jit_step", 0, 10_000)])])
    host = NS(name="/host:CPU", lines=[NS(name="main", events=[
        ev("bench_window", 100, 1000),
        ev("plane_batch", 100, 1000),
        ev("dispatch", 450, 200),
        ev("other", 0, 5000),
    ])])
    return NS(planes=[
        dev(0, [ev("a", 50, 150), ev("b", 180, 120), ev("a", 700, 100)]),
        dev(1, [ev("a", 200, 400)]),
        host])


def test_reduce_synthetic():
    r = trace_reduce.reduce(profile(), span_names={"plane_batch", "dispatch"})
    # device 0 busy in the window [100, 1100): [100, 300) and [700, 800)
    # device 1: [200, 600); mean 350 ns
    assert r["window_s"] == pytest.approx(1000e-9)
    assert r["busy_s"] == pytest.approx(350e-9)
    assert r["n_devices"] == 2
    assert r["ops"] == pytest.approx({"a": 100e-9 + 100e-9 + 400e-9,
                                      "b": 120e-9})
    assert r["op_counts"] == {"a": 3, "b": 1}
    assert r["device_ops"][0] == ["a", pytest.approx(600e-9)]
    # gaps on device 0: [300, 700) holds the dispatch span's middle
    # (innermost), [800, 1100) only plane_batch
    assert r["idle_gaps"] == [["dispatch", pytest.approx(400e-9)],
                              ["plane_batch", pytest.approx(300e-9)]]
    t, n = trace_reduce.op_time(r, "^a$")
    assert (t, n) == (pytest.approx(600e-9), 3)


def test_reduce_without_window_uses_device_extent():
    p = profile()
    p.planes[2].lines[0].events.pop(0)
    r = trace_reduce.reduce(p, top=1)
    assert r["window_s"] == pytest.approx(750e-9)          # [50, 800)
    assert len(r["idle_gaps"]) == 1


def test_reduce_recorded_cpu_trace():
    r = trace_reduce.reduce(str(DATA), top=1000,
                            span_names={"dispatch", "plane_batch"},
                            **harness.CPU_TRACE)
    assert r["n_devices"] == 1
    assert 0 < r["busy_s"] < r["window_s"]
    gaps = sum(t for _, t in r["idle_gaps"])
    assert r["busy_s"] + gaps == pytest.approx(r["window_s"], rel=1e-9)
    assert any(n.startswith("dot_general") for n, _ in r["device_ops"])
    longest = r["idle_gaps"][:3]
    assert [n for n, _ in longest] == ["plane_batch"] * 3
    assert all(t >= 0.019 for _, t in longest)


def test_short_name_of_tpu_ops():
    kernel = ('%_scan_fused.23 = (f32[64,160,24]{2,1,0:T(8,128)}, '
              'f32[64,160,24]{2,1,0:T(8,128)}) custom-call(f32[64,12448,24]'
              '{2,1,0:T(8,128)} %pad_maximum_fusion), '
              'custom_call_target="tpu_custom_call", frontend_attributes={}')
    gather = ('%fusion.108 = f32[786432,24]{0,1:T(8,128)} fusion(f32[64,'
              '12288,24]{1,2,0:T(8,128)} %ring1, s32[786432]{0:T(1024)S(1)} '
              '%bitcast.275), kind=kCustom, calls=%fused_computation.22')
    assert trace_reduce.short_name(kernel) == \
        "%_scan_fused.23 tpu_custom_call f32[64,160,24]"
    assert trace_reduce.short_name(gather) == \
        "%fusion.108 fusion f32[786432,24]"
    assert trace_reduce.short_name("dot_general.1") == "dot_general.1"
