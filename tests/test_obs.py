"""Telemetry plane (repro.obs): metrics registry semantics, trace spans,
device-side counters, and — the load-bearing contract — bit-identity of
instrumented services vs uninstrumented ones on every state leaf and
every emitted output."""

import asyncio
import functools
import json
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import build_bundle
from repro.models.tcn import tcn_empty_state
from repro.obs import (
    MetricsRegistry,
    Tracer,
    acceptance_stats,
    decode_occupancy,
    occupancy_stats,
    valid_stats,
)
from repro.obs.metrics import Histogram
from repro.serving import Rejected, ServingPlane
from repro.sessions import (
    LMSessionService,
    SpeculativeDecoder,
    StreamSessionService,
    ngram_drafter,
)


# ---------------------------------------------------------------------------
# metrics registry
# ---------------------------------------------------------------------------

def test_counter_gauge_basics():
    reg = MetricsRegistry()
    c = reg.counter("reqs_total", service="tcn")
    c.inc()
    c.inc(4)
    assert c.value == 5
    assert reg.counter("reqs_total", service="tcn") is c  # get-or-create
    assert reg.counter("reqs_total", service="lm") is not c  # labels split
    with pytest.raises(ValueError):
        c.inc(-1)  # counters are monotonic
    g = reg.gauge("bound")
    g.set(3)
    g.inc()
    g.dec(2)
    assert g.value == 2


def test_metric_kind_conflict_raises():
    reg = MetricsRegistry()
    reg.counter("x", a=1)
    with pytest.raises(TypeError):
        reg.gauge("x", a=1)


def test_histogram_log2_buckets_and_quantiles():
    h = Histogram()
    for v in (1, 2, 3, 1000):
        h.record(v)
    # 1 -> bucket 0, 2 -> bucket 1, 3 -> bucket 2, 1000 -> bucket 10
    assert h.to_dict()["buckets"] == {"0": 1, "1": 1, "2": 1, "10": 1}
    assert h.count == 4 and h.sum == 1006
    assert h.min == 1 and h.max == 1000
    assert h.mean == pytest.approx(251.5)
    # quantiles are bucket-approximate but clamped to observed extremes
    assert h.percentile(0) == 1
    assert h.percentile(100) == 1000
    assert 1 <= h.percentile(50) <= 3
    with pytest.raises(ValueError):
        h.record(-1)
    h.reset()
    assert h.count == 0 and h.percentile(99) == 0.0


def test_histogram_percentile_within_bucket_error_bound():
    """Quantile error is bounded by the log2 bucket width (factor of 2)."""
    rng = np.random.default_rng(0)
    vals = rng.uniform(10, 10000, size=2000)
    h = Histogram()
    for v in vals:
        h.record(v)
    for q in (50, 90, 99):
        exact = np.percentile(vals, q)
        approx = h.percentile(q)
        assert exact / 2 <= approx <= exact * 2


def test_snapshot_and_prometheus_exposition():
    reg = MetricsRegistry()
    reg.counter("evictions_total", service="tcn").inc(2)
    reg.histogram("lat_us", service="tcn", shape="T16").record(100)
    snap = reg.snapshot()
    assert snap["evictions_total"] == [
        {"labels": {"service": "tcn"}, "type": "counter", "value": 2}]
    [h] = snap["lat_us"]
    assert h["labels"] == {"service": "tcn", "shape": "T16"}
    assert h["count"] == 1
    json.dumps(snap)  # pure-JSON contract
    text = reg.prometheus()
    assert "# TYPE evictions_total counter" in text
    assert 'evictions_total{service="tcn"} 2' in text
    # cumulative le buckets + _sum/_count for histograms
    assert 'lat_us_bucket{service="tcn",shape="T16",le="128.0"} 1' in text
    assert 'lat_us_bucket{service="tcn",shape="T16",le="+Inf"} 1' in text
    assert 'lat_us_count{service="tcn",shape="T16"} 1' in text
    reg.reset()
    assert reg.counter("evictions_total", service="tcn").value == 0


# ---------------------------------------------------------------------------
# trace spans
# ---------------------------------------------------------------------------

def test_disabled_tracer_records_nothing():
    t = Tracer()
    with t.span("dispatch", cat="tcn", shape="T16"):
        pass
    t.instant("evict", sid=1)
    t.counter("sessions", bound=2)
    assert t.events() == []


def test_span_and_instant_events(tmp_path):
    t = Tracer(enabled=True)
    with t.span("dispatch", cat="tcn", shape="T16", lanes=3):
        pass
    t.instant("evict", cat="tcn", victim=7)
    t.counter("sessions", bound=2, parked=1)
    evs = t.events()
    assert [e["ph"] for e in evs] == ["X", "i", "C"]
    x = evs[0]
    assert x["name"] == "dispatch" and x["cat"] == "tcn"
    assert x["dur"] >= 0 and x["args"] == {"shape": "T16", "lanes": 3}
    assert evs[1]["args"]["victim"] == 7
    # export is a Perfetto/chrome://tracing-loadable JSON document
    path = t.export(str(tmp_path / "trace.json"))
    with open(path) as f:
        doc = json.load(f)
    assert doc["traceEvents"] == evs
    assert doc["otherData"]["dropped_events"] == 0


def test_trace_ring_buffer_drops_oldest():
    t = Tracer(enabled=True, capacity=4)
    for i in range(10):
        t.instant(f"e{i}")
    evs = t.events()
    assert len(evs) == 4
    assert [e["name"] for e in evs] == ["e6", "e7", "e8", "e9"]
    assert t.dropped == 6
    t.clear()
    assert t.events() == [] and t.dropped == 0


def test_complete_records_given_extent_only_when_enabled():
    t = Tracer()
    t.complete("plane_op", 1_000, 5_000, cat="plane", id=1)
    assert t.events() == []
    t.enable()
    t.complete("plane_op", 1_000, 5_000, cat="plane", id=1, ok=True)
    (e,) = t.events()
    assert (e["name"], e["ph"], e["cat"]) == ("plane_op", "X", "plane")
    assert (e["ts"], e["dur"]) == (1.0, 4.0)
    assert e["args"] == {"id": 1, "ok": True}


def test_annotate_mirrors_spans_into_the_profiler(monkeypatch):
    """``annotate=True`` enters one profiler annotation per span, around
    it; the default and ``complete()`` events enter none."""
    trace_mod = sys.modules[Tracer.__module__]
    seen = []

    class Ann:
        def __init__(self, name):
            self.name = name

        def __enter__(self):
            seen.append(("enter", self.name))

        def __exit__(self, *exc):
            seen.append(("exit", self.name))

    monkeypatch.setattr(trace_mod, "_annotation", Ann)
    t = Tracer(enabled=True, annotate=True)
    with t.span("dispatch"):
        with t.span("launch"):
            pass
    t.complete("plane_op", 0, 10)
    assert seen == [("enter", "dispatch"), ("enter", "launch"),
                    ("exit", "launch"), ("exit", "dispatch")]
    assert [e["name"] for e in t.events()] == ["launch", "dispatch",
                                                "plane_op"]
    seen.clear()
    with Tracer(enabled=True).span("dispatch"):
        pass
    with Tracer(annotate=True).span("dispatch"):   # disabled: no-op
        pass
    assert seen == []


# ---------------------------------------------------------------------------
# device-side counters (pure functions)
# ---------------------------------------------------------------------------

def test_occupancy_stats_vector():
    lengths = jnp.asarray([5, 0, 3, 8])
    stats = np.asarray(occupancy_stats(lengths, 8))
    assert stats.tolist() == [16, 32, 3, 4]
    occ = decode_occupancy(stats)
    assert occ["live_step_ratio"] == pytest.approx(0.5)
    assert occ["lane_occupancy"] == pytest.approx(0.75)
    # waste within live lanes: 3 live lanes x 8 padded = 24 extent, 16 live
    assert occ["pad_waste"] == pytest.approx(1 - 16 / 24)


def test_valid_stats_matches_lengths():
    lengths = np.asarray([2, 0, 4])
    valid = np.arange(4)[None, :] < lengths[:, None]
    np.testing.assert_array_equal(np.asarray(valid_stats(valid)),
                                  np.asarray(occupancy_stats(lengths, 4)))


def test_acceptance_stats_matching_prefix():
    ys = jnp.asarray([[1, 2, 3, 9],    # full match (3 drafts)
                      [1, 9, 3, 9],    # mismatch at draft 1
                      [5, 6, 7, 9],    # n_draft=0: nothing to accept
                      [1, 2, 9, 9]])   # match 2 then mismatch
    draft = jnp.asarray([[1, 2, 3],
                         [1, 2, 3],
                         [5, 6, 7],
                         [1, 2, 3]])
    n_draft = jnp.asarray([3, 3, 0, 3])
    acc = np.asarray(acceptance_stats(ys, draft, n_draft))
    assert acc.tolist() == [3, 1, 0, 2]


# ---------------------------------------------------------------------------
# instrumented services: bit-identity + wiring
# ---------------------------------------------------------------------------

def _tcn_setup():
    cfg = get_config("chameleon-tcn").replace(
        tcn_channels=(8, 8), tcn_kernel=3, tcn_in_channels=2,
        embed_dim=12, n_classes=4)
    bundle = build_bundle(cfg)
    params = bundle.init(jax.random.key(0))
    return cfg, bundle, params, tcn_empty_state(cfg)


@functools.lru_cache(maxsize=None)
def _lm_setup():
    cfg = get_config("olmo-1b").smoke().replace(
        n_layers=2, d_model=32, d_ff=64, vocab_size=64, head_dim=16)
    bundle = build_bundle(cfg)
    params = bundle.init(jax.random.key(0))
    return cfg, bundle, params


def test_tcn_instrumented_scan_bit_identical():
    """device_counters=True threads extra in-jit outputs through the scan;
    embeddings, logits, AND every state leaf must match the plain service
    bit for bit."""
    cfg, bundle, params, bn = _tcn_setup()
    mk = lambda dev: StreamSessionService(
        bundle, params, bn, n_slots=3, max_tenants=1, t_chunk=8,
        device_counters=dev)
    plain, inst = mk(False), mk(True)
    rng = np.random.default_rng(0)
    x = rng.normal(size=(3, 21, 2)).astype(np.float32)
    for svc in (plain, inst):
        sids = [svc.open_session() for _ in range(3)]
        svc._out = svc.push_audio(
            {sid: x[i] for i, sid in enumerate(sids)})
    for a, b in zip(plain._out.values(), inst._out.values()):
        np.testing.assert_array_equal(a["emb"], b["emb"])
        np.testing.assert_array_equal(a["logits"], b["logits"])
    for la, lb in zip(jax.tree.leaves(plain.states),
                      jax.tree.leaves(inst.states)):
        np.testing.assert_array_equal(np.asarray(la), np.asarray(lb))
    # ...and the instrumented service actually ingested occupancy
    snap = inst.metrics()
    [live] = snap["device_live_steps_total"]
    assert live["value"] == 3 * 21
    assert plain.metrics().get("device_live_steps_total") is None


def test_lm_instrumented_decode_bit_identical():
    cfg, bundle, params = _lm_setup()
    mk = lambda dev: LMSessionService(
        bundle, params, n_slots=2, seq_cap=48, t_chunk=8,
        device_counters=dev)
    plain, inst = mk(False), mk(True)
    prompt = np.array([3, 1, 4, 1, 5], np.int32)
    outs = []
    for svc in (plain, inst):
        a = svc.open_session(prompt)
        b = svc.open_session(prompt[:2])
        outs.append(svc.decode({a: 12, b: 12}))
    assert list(outs[0].values()) == list(outs[1].values())
    for la, lb in zip(jax.tree.leaves(plain.cache),
                      jax.tree.leaves(inst.cache)):
        np.testing.assert_array_equal(np.asarray(la), np.asarray(lb))
    snap = inst.metrics()
    assert snap["device_live_steps_total"][0]["value"] > 0
    # masked + live = n_slots * t_pad per dispatch, always
    total = (snap["device_live_steps_total"][0]["value"]
             + snap["device_masked_steps_total"][0]["value"])
    assert total % plain.n_slots == 0


def test_speculative_device_acceptance_matches_host():
    """The in-jit per-lane acceptance counts equal the host rollback
    arithmetic, and the instrumented verify emits the same stream."""
    cfg, bundle, params = _lm_setup()
    prompts = [np.array([3, 1, 4, 1, 5, 1, 4, 1], np.int32),
               np.array([2, 7, 2, 7, 2], np.int32)]

    def run(dev):
        svc = LMSessionService(bundle, params, n_slots=2, seq_cap=96,
                               t_chunk=8, device_counters=dev)
        sp = SpeculativeDecoder(svc, ngram_drafter(), k=3)
        sids = [svc.open_session(p) for p in prompts]
        out = sp.decode({sid: 24 for sid in sids})
        return svc, sp, [out[sid] for sid in sids]

    _, sp_plain, stream_plain = run(False)
    svc, sp, stream = run(True)
    assert stream == stream_plain
    assert sp._verify_inst is not None
    assert sp.last_device_accepts is not None
    # total device-counted acceptance == total host-counted acceptance
    assert sp.accepted == sp_plain.accepted
    dev_acc = svc.metrics()["spec_device_accepted_total"][0]["value"]
    assert dev_acc == sp.accepted
    # the registry's drafted/accepted counters mirror the plain ints
    snap = svc.metrics()
    assert snap["spec_drafted_total"][0]["value"] == sp.drafted
    assert snap["spec_accepted_total"][0]["value"] == sp.accepted


def test_dispatch_latency_histograms_per_shape():
    """Every jitted dispatch lands one sample in the per-compiled-shape
    log2 histogram; counts equal the dispatch counter."""
    cfg, bundle, params, bn = _tcn_setup()
    svc = StreamSessionService(bundle, params, bn, n_slots=2, max_tenants=1,
                               t_chunk=8)
    sid = svc.open_session()
    svc.push_audio({sid: np.zeros((11, 2), np.float32)})  # T8 + T4 buckets
    svc.push_audio({sid: np.zeros((2,), np.float32)})     # T1 bucket
    snap = svc.metrics()
    hists = {h["labels"]["shape"]: h for h in snap["dispatch_latency_us"]}
    assert set(hists) == {"T8", "T4", "T1"}
    assert sum(h["count"] for h in hists.values()) == svc.dispatches == 3
    for h in hists.values():
        assert h["p50"] <= h["p99"] <= h["max"]


def test_tracer_records_service_lifecycle(tmp_path):
    """A private enabled tracer sees dispatch spans, evict instants with
    the victim sid, and park/resume — the Perfetto story of the grid."""
    cfg, bundle, params, bn = _tcn_setup()
    t = Tracer(enabled=True)
    svc = StreamSessionService(bundle, params, bn, n_slots=1, max_tenants=1,
                               max_sessions=4, tracer=t)
    a = svc.open_session()
    svc.push_audio({a: np.zeros((2,), np.float32)})
    b = svc.open_session()          # grid of 1: evicts a
    svc.push_audio({a: np.zeros((2,), np.float32)})  # resumes a, evicts b
    names = [e["name"] for e in t.events()]
    for expected in ("bind", "dispatch", "pack", "evict", "unpack", "resume"):
        assert expected in names, f"missing {expected!r} in {names}"
    evict = next(e for e in t.events() if e["name"] == "evict")
    assert evict["args"]["victim"] == a
    dispatch = next(e for e in t.events() if e["name"] == "dispatch")
    assert dispatch["args"]["shape"] == "T1" and dispatch["dur"] >= 0
    doc = json.load(open(t.export(str(tmp_path / "t.json"))))
    assert len(doc["traceEvents"]) == len(t.events())


def test_backward_compat_counter_properties():
    """The historical bare-int surface (svc.dispatches / svc.evictions,
    including += writes) routes through the registry and can't disagree
    with metrics()."""
    cfg, bundle, params, bn = _tcn_setup()
    svc = StreamSessionService(bundle, params, bn, n_slots=2, max_tenants=1)
    assert svc.dispatches == 0
    svc.dispatches += 5
    assert svc.metrics()["dispatches_total"][0]["value"] == 5
    svc.evictions = 2
    assert svc.stats()["evictions"] == 2
    assert svc.metrics()["evictions_total"][0]["value"] == 2


def test_park_unknown_sid_raises():
    """park() has _touch_and_bind's contract: unknown sids raise KeyError
    instead of silently no-oping; parking a parked session stays a no-op."""
    cfg, bundle, params, bn = _tcn_setup()
    svc = StreamSessionService(bundle, params, bn, n_slots=2, max_tenants=1)
    sid = svc.open_session()
    with pytest.raises(KeyError):
        svc.park(sid + 999)
    svc.park(sid)
    svc.park(sid)  # already parked: no-op, no raise
    assert svc.poll(sid)["state"] == "parked"

    cfg2, bundle2, params2 = _lm_setup()
    lm = LMSessionService(bundle2, params2, n_slots=2, seq_cap=32)
    with pytest.raises(KeyError):
        lm.park(123)


# ---------------------------------------------------------------------------
# a request traced through the plane and the service
# ---------------------------------------------------------------------------

N_CLIENTS, PUSH_LENS = 4, (5, 8, 11)    # 11 samples: a T8 and a T4 tick


def _plane_run(tracer):
    """Four clients over a traced plane on a paged-bank service: open a
    session of a tenant, enroll raw shots, push three ragged chunks.  Then
    tenant 0's two sessions park (its bank rows leave the device), one of
    them pushes again (they come back), and one more push expires in the
    queue.  Returns (results, service, events)."""
    cfg, bundle, params, bn = _tcn_setup()
    reg = MetricsRegistry()
    svc = StreamSessionService(bundle, params, bn, n_slots=4, max_tenants=2,
                               max_ways=4, t_chunk=8, paged_bank=True,
                               bank_block_ways=2, metrics=reg, tracer=tracer)
    rng = np.random.default_rng(7)
    shots = rng.standard_normal((N_CLIENTS, 2, 6, 2)).astype(np.float32)
    chunks = [[rng.standard_normal((n, 2)).astype(np.float32)
               for n in PUSH_LENS] for _ in range(N_CLIENTS)]

    async def client(plane, c):
        psid = await plane.open_session(tenant=c % 2)
        out = [psid, await plane.enroll(psid, shots[c], label=c)]
        for x in chunks[c]:
            out.append(await plane.push(psid, x))
        return out

    async def main():
        async with ServingPlane(svc, metrics=reg, tracer=tracer) as plane:
            res = await asyncio.gather(*(client(plane, c)
                                         for c in range(N_CLIENTS)))
            await plane.park(res[0][0])
            await plane.park(res[2][0])
            res[0].append(await plane.push(res[0][0], chunks[1][2]))
            with pytest.raises(Rejected):
                await plane.push(res[0][0], chunks[0][0], deadline_s=-1.0)
            return res

    return asyncio.run(main()), svc, tracer.events()


def _inside(child, parent):
    return (parent["ts"] <= child["ts"] and child["ts"] + child["dur"]
            <= parent["ts"] + parent["dur"] + 1e-3)


def test_plane_records_one_op_event_per_request():
    _, _, evs = _plane_run(Tracer(enabled=True))
    ops = [e for e in evs if e["name"] == "plane_op"]
    per_client = 2 + len(PUSH_LENS)               # open, enroll, pushes
    assert len(ops) == N_CLIENTS * per_client + 4  # 2 parks, 2 pushes
    assert sorted(e["args"]["id"] for e in ops) == list(
        range(1, len(ops) + 1))
    kinds = [e["args"]["kind"] for e in ops]
    assert kinds.count("push") == N_CLIENTS * len(PUSH_LENS) + 2
    assert kinds.count("enroll") == kinds.count("open") == N_CLIENTS
    assert kinds.count("park") == 2
    assert [e["args"]["ok"] for e in ops].count(False) == 1
    for e in ops:
        a = e["args"]
        assert e["ph"] == "X" and e["cat"] == "plane"
        assert a["queue_us"] >= 0 and a["held_us"] >= 0
        assert a["queue_us"] + a["held_us"] <= e["dur"] + 1e-6
    batches = {e["args"]["batch"]: e for e in evs
               if e["name"] == "plane_batch"}
    enrolls = [e for e in evs if e["name"] == "enroll"]
    for e in ops:
        a = e["args"]
        # the op's service work lies between its dequeue and its result
        served = {"ts": e["ts"] + a["queue_us"],
                  "dur": e["dur"] - a["queue_us"] - a["held_us"]}
        if a["kind"] == "push" and a["ok"]:
            b = batches[a["batch"]]
            assert _inside(b, e) and _inside(b, served)
            assert any(_inside(d, b) for d in evs if d["name"] == "dispatch")
        if a["kind"] == "enroll":
            assert sum(_inside(x, served) for x in enrolls) == 1


def _children(evs, parent, names):
    """Each ``parent`` span with the ``names`` spans inside it, in order."""
    out = []
    for p in (e for e in evs if e["name"] == parent and e["ph"] == "X"):
        kids = sorted((e for e in evs if e["name"] in names and e is not p
                       and _inside(e, p)), key=lambda e: e["ts"])
        out.append([k["name"] for k in kids])
    return out


def test_service_phases_nest_inside_dispatch_and_enroll():
    _, _, evs = _plane_run(Tracer(enabled=True))
    names = {e["name"] for e in evs}
    assert {"prepare", "assemble", "reset", "bank_park",
            "bank_unpark"} <= names
    dispatches = _children(evs, "dispatch", ("launch", "device_wait", "fetch"))
    assert len(dispatches) >= N_CLIENTS * len(PUSH_LENS) // 4
    assert all(k == ["launch", "device_wait", "fetch"] for k in dispatches)
    enrolls = _children(evs, "enroll", ("embed", "bank_update", "device_wait"))
    assert len(enrolls) == N_CLIENTS
    assert all(k == ["embed", "bank_update", "device_wait"] for k in enrolls)
    # one prepare span per tick, each ending before its tick's dispatch
    n_dispatch = sum(e["name"] == "dispatch" for e in evs)
    assert sum(e["name"] == "prepare" for e in evs) == n_dispatch
    assert sum(e["name"] == "launch" for e in evs) == n_dispatch


def test_tracing_leaves_states_and_results_bit_identical():
    res_on, svc_on, evs = _plane_run(Tracer(enabled=True))
    res_off, svc_off, none = _plane_run(Tracer())
    assert evs and none == []
    for a, b in zip(res_on, res_off):
        assert a[:2] == b[:2]                      # session, enrolled way
        assert len(a) == len(b)
        for ra, rb in zip(a[2:], b[2:]):
            assert ra["pred"] == rb["pred"] and ra["step"] == rb["step"]
            for k in ("emb", "logits", "tenant_logits"):
                np.testing.assert_array_equal(ra[k], rb[k])
    for la, lb in zip(jax.tree.leaves(svc_on.states),
                      jax.tree.leaves(svc_off.states)):
        np.testing.assert_array_equal(np.asarray(la), np.asarray(lb))
    for k in ("s_sums", "counts"):
        np.testing.assert_array_equal(
            np.asarray(getattr(svc_on.bankpool, k)),
            np.asarray(getattr(svc_off.bankpool, k)))
