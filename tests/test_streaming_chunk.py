"""Chunked scan-based streaming: bit-exactness of the lax.scan time-chunk
execution path vs the per-sample step path, across ragged lengths, ring
wraparound, quantization, sharding specs, and random push schedules."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _hyp import given, settings, st
from repro.configs import get_config
from repro.core.streaming import (
    _ordered_history,
    _ring_advance,
    ring_sizes,
    stream_init_single,
    stream_scan_single,
    stream_step_single,
)
from repro.models import build_bundle
from repro.models.tcn import (
    bake_stream_params,
    make_fused_forward,
    tcn_empty_state,
    tcn_forward,
)
from repro.sessions import (
    StreamSessionService,
    grid_init,
    grid_pspecs,
    grid_scan,
    grid_step,
    lengths_to_valid,
    make_grid_fused,
    bank_init,
    bank_pspecs,
)

settings.register_profile("chunk", deadline=None, max_examples=30)
settings.load_profile("chunk")


@functools.lru_cache(maxsize=None)
def _setup(seed=0):
    cfg = get_config("chameleon-tcn").replace(
        tcn_channels=(8, 8), tcn_kernel=3, tcn_in_channels=2,
        embed_dim=12, n_classes=4)
    bundle = build_bundle(cfg)
    params = bundle.init(jax.random.key(seed))
    bn = tcn_empty_state(cfg)
    bn = jax.tree.map(
        lambda a: a + 0.05 * jnp.abs(jax.random.normal(jax.random.key(7), a.shape)),
        bn)
    return cfg, bundle, params, bn


# ---------------------------------------------------------------------------
# core/streaming: stream_scan_single
# ---------------------------------------------------------------------------

def test_stream_scan_single_matches_sequential_steps_and_forward():
    """One scanned chunk == T sequential stream_step_single calls, bit for
    bit (outputs AND end state), and matches the full-sequence conv."""
    cfg, bundle, params, bn = _setup()
    T = 30
    x = np.random.default_rng(0).normal(size=(T, 2)).astype(np.float32)
    st0 = stream_init_single(cfg)
    # params/bn as jit ARGUMENTS: the cross-program exactness discipline
    scan = jax.jit(lambda p, b, s, xc, v: stream_scan_single(p, b, cfg, s, xc, v))
    end, embs, logits = scan(params, bn, st0, jnp.asarray(x), jnp.ones(T, bool))
    st_seq = stream_init_single(cfg)
    step = jax.jit(lambda p, b, s, xt: stream_step_single(p, b, cfg, s, xt))
    for t in range(T):
        st_seq, e, l = step(params, bn, st_seq, jnp.asarray(x[t]))
        np.testing.assert_array_equal(np.asarray(embs[t]), np.asarray(e))
        np.testing.assert_array_equal(np.asarray(logits[t]), np.asarray(l))
    for a, b in zip(jax.tree.leaves(end), jax.tree.leaves(st_seq)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    emb_full, _, _ = tcn_forward(params, bn, cfg, jnp.asarray(x)[None],
                                 train=False)
    np.testing.assert_allclose(np.asarray(embs[-1]), np.asarray(emb_full[0]),
                               rtol=2e-4, atol=2e-5)


def test_stream_scan_single_invalid_tail_bit_frozen():
    """Padding a short chunk: steps past ``valid`` leave state untouched and
    T=1 scan is exactly stream_step_single."""
    cfg, bundle, params, bn = _setup()
    T = 12
    x = np.random.default_rng(1).normal(size=(T, 2)).astype(np.float32)
    scan = jax.jit(lambda p, b, s, xc, v: stream_scan_single(p, b, cfg, s, xc, v))
    step = jax.jit(lambda p, b, s, xt: stream_step_single(p, b, cfg, s, xt))
    st0 = stream_init_single(cfg)
    valid = jnp.arange(T) < 7
    end, _, _ = scan(params, bn, st0, jnp.asarray(x), valid)
    st_seq = stream_init_single(cfg)
    for t in range(7):
        st_seq, _, _ = step(params, bn, st_seq, jnp.asarray(x[t]))
    for a, b in zip(jax.tree.leaves(end), jax.tree.leaves(st_seq)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # T=1 special case degenerates to the single step
    one, e1, l1 = scan(params, bn, st_seq, jnp.asarray(x[7:8]),
                       jnp.ones(1, bool))
    ref, er, lr = step(params, bn, st_seq, jnp.asarray(x[7]))
    np.testing.assert_array_equal(np.asarray(e1[0]), np.asarray(er))
    np.testing.assert_array_equal(np.asarray(l1[0]), np.asarray(lr))
    for a, b in zip(jax.tree.leaves(one), jax.tree.leaves(ref)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ---------------------------------------------------------------------------
# sessions/state: grid_scan over ragged slot grids
# ---------------------------------------------------------------------------

def test_grid_scan_ragged_bit_exact_vs_sequential_grid_step():
    """A ragged (S, T) chunk == T sequential masked grid_step calls, bit for
    bit, including a zero-length slot that must stay fully frozen."""
    cfg, bundle, params, bn = _setup()
    S, T = 4, 21
    x = np.random.default_rng(2).normal(size=(S, T, 2)).astype(np.float32)
    lens = np.array([21, 7, 0, 13])
    ga = grid_init(cfg, S)
    ga, emb_a, log_a = jax.jit(
        lambda p, b, s, xx, v: grid_scan(p, b, cfg, s, xx, v))(
            params, bn, ga, jnp.asarray(x), lengths_to_valid(lens, T))
    gb = grid_init(cfg, S)
    gstep = jax.jit(lambda p, b, s, xx, a: grid_step(p, b, cfg, s, xx, a))
    emb_b = np.zeros((S, T, cfg.embed_dim), np.float32)
    log_b = np.zeros((S, T, cfg.n_classes), np.float32)
    for t in range(T):
        gb, e, l = gstep(params, bn, gb, jnp.asarray(x[:, t]),
                         jnp.asarray(t < lens))
        emb_b[:, t], log_b[:, t] = np.asarray(e), np.asarray(l)
    emb_a, log_a = np.asarray(emb_a), np.asarray(log_a)
    for i in range(S):
        np.testing.assert_array_equal(emb_a[i, :lens[i]], emb_b[i, :lens[i]])
        np.testing.assert_array_equal(log_a[i, :lens[i]], log_b[i, :lens[i]])
    for a, b in zip(jax.tree.leaves(ga), jax.tree.leaves(gb)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert int(np.asarray(ga["t"])[2]) == 0  # zero-length slot never moved


def test_chunk_boundaries_straddle_ring_wraparound():
    """Chunk lengths coprime with every ring depth: each chunk boundary
    lands mid-wraparound in some FIFO, and the stitched stream still matches
    an unchunked scan bit for bit."""
    cfg, bundle, params, bn = _setup()
    depths = {n for b in ring_sizes(cfg).values() for (n, _c) in b.values()}
    chunk = 7
    assert all(chunk % n != 0 for n in depths), (chunk, depths)
    T = 5 * chunk  # several full wraps of every ring
    x = np.random.default_rng(3).normal(size=(T, 2)).astype(np.float32)
    st_chunked = stream_init_single(cfg)
    scan = jax.jit(lambda p, b, s, xc: stream_scan_single(
        p, b, cfg, s, xc, jnp.ones(chunk, bool)))
    embs = []
    for off in range(0, T, chunk):
        st_chunked, e, _ = scan(params, bn, st_chunked,
                                jnp.asarray(x[off:off + chunk]))
        embs.append(np.asarray(e))
    whole, e_all, _ = jax.jit(lambda p, b, s, xc: stream_scan_single(
        p, b, cfg, s, xc, jnp.ones(T, bool)))(
            params, bn, stream_init_single(cfg), jnp.asarray(x))
    np.testing.assert_array_equal(np.concatenate(embs), np.asarray(e_all))
    for a, b in zip(jax.tree.leaves(st_chunked), jax.tree.leaves(whole)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_grid_scan_t160_bit_exact_vs_160_grid_steps():
    """Acceptance: grid_scan with T_chunk=160 == 160 sequential grid_step
    calls, bit for bit (outputs and end state)."""
    cfg, bundle, params, bn = _setup()
    S, T = 2, 160
    x = np.random.default_rng(9).normal(size=(S, T, 2)).astype(np.float32)
    ga = grid_init(cfg, S)
    ga, emb_a, _ = jax.jit(
        lambda p, b, s, xx, v: grid_scan(p, b, cfg, s, xx, v))(
            params, bn, ga, jnp.asarray(x), jnp.ones((S, T), bool))
    gb = grid_init(cfg, S)
    gstep = jax.jit(lambda p, b, s, xx, a: grid_step(p, b, cfg, s, xx, a))
    active = jnp.ones(S, bool)
    emb_b = np.zeros((S, T, cfg.embed_dim), np.float32)
    for t in range(T):
        gb, e, _ = gstep(params, bn, gb, jnp.asarray(x[:, t]), active)
        emb_b[:, t] = np.asarray(e)
    np.testing.assert_array_equal(np.asarray(emb_a), emb_b)
    for a, b in zip(jax.tree.leaves(ga), jax.tree.leaves(gb)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ---------------------------------------------------------------------------
# sessions/service: ragged chunked pushes
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=None)
def _services(quantize=False, t_chunk=4):
    """One chunked + one per-sample control service, reused across tests so
    each compiled bucket shape is paid for once."""
    cfg, bundle, params, bn = _setup()
    svc = StreamSessionService(bundle, params, bn, n_slots=3, max_tenants=1,
                               quantize=quantize, t_chunk=t_chunk)
    ctl = StreamSessionService(bundle, params, bn, n_slots=3, max_tenants=1,
                               quantize=quantize, t_chunk=1)
    return svc, ctl


def _push_schedule(svc, ctl, schedule, x):
    """Run {sid: length} rounds against both services; compare bit-exactly.
    schedule: list of dicts mapping stream index -> chunk length."""
    sids = [svc.open_session() for _ in range(2)]
    cids = [ctl.open_session() for _ in range(2)]
    try:
        pos = [0, 0]
        for round_ in schedule:
            chunk = {sids[i]: x[i, pos[i]:pos[i] + n]
                     for i, n in round_.items()}
            res = svc.push_audio(chunk)
            for i, n in round_.items():
                ref_e, ref_l = [], []
                for t in range(pos[i], pos[i] + n):
                    r = ctl.push_audio({cids[i]: x[i, t]})[cids[i]]
                    ref_e.append(r["emb"])
                    ref_l.append(r["logits"])
                got = res[sids[i]]
                e = got["emb"] if got["emb"].ndim == 2 else got["emb"][None]
                l = (got["logits"] if got["logits"].ndim == 2
                     else got["logits"][None])
                np.testing.assert_array_equal(e, np.stack(ref_e))
                np.testing.assert_array_equal(l, np.stack(ref_l))
                pos[i] += n
    finally:
        for sid in sids:
            svc.close(sid)
        for cid in cids:
            ctl.close(cid)


def test_service_ragged_chunk_push_bit_exact():
    """Two sessions pushing different-length chunks in the same call match
    their per-sample controls bit for bit (incl. straddling t_chunk)."""
    svc, ctl = _services()
    x = np.random.default_rng(4).normal(size=(2, 40, 2)).astype(np.float32)
    _push_schedule(svc, ctl, [{0: 5, 1: 2}, {0: 1}, {1: 9}, {0: 6, 1: 4}], x)


def test_service_quantized_chunked_push_bit_exact():
    svc, ctl = _services(quantize=True)
    x = np.random.default_rng(5).normal(size=(2, 24, 2)).astype(np.float32)
    _push_schedule(svc, ctl, [{0: 7, 1: 3}, {0: 3, 1: 7}, {0: 2, 1: 2}], x)


def test_service_random_push_schedules_bit_exact():
    """Property: ANY interleaving of ragged chunk pushes across sessions is
    bit-exact vs per-sample stepping (hypothesis via the _hyp fallback)."""
    @given(st.integers(0, 2 ** 31 - 1))
    def prop(seed):
        rng = np.random.default_rng(seed)
        svc, ctl = _services()
        x = rng.normal(size=(2, 36, 2)).astype(np.float32)
        schedule = []
        pos = [0, 0]
        for _ in range(4):
            round_ = {}
            for i in range(2):
                if rng.random() < 0.75 and pos[i] < x.shape[1]:
                    n = int(rng.integers(1, min(9, x.shape[1] - pos[i]) + 1))
                    round_[i] = n
                    pos[i] += n
            if round_:
                schedule.append(round_)
        _push_schedule(svc, ctl, schedule, x)
    prop()


def test_service_per_sample_surface_unchanged():
    """A (C_in,) push keeps the historical scalar result surface."""
    svc, _ = _services()
    sid = svc.open_session()
    try:
        r = svc.push_audio({sid: np.zeros(2, np.float32)})[sid]
        assert r["emb"].shape == (12,) and r["logits"].shape == (4,)
        assert r["step"] == 1 and isinstance(r["pred"], int)
    finally:
        svc.close(sid)


def test_service_rejects_malformed_chunks():
    svc, _ = _services()
    sid = svc.open_session()
    try:
        with pytest.raises(ValueError):
            svc.push_audio({sid: np.zeros((0, 2), np.float32)})  # empty
        with pytest.raises(ValueError):
            svc.push_audio({sid: np.zeros((4, 3), np.float32)})  # bad C_in
    finally:
        svc.close(sid)


def test_chunked_push_amortizes_dispatches():
    """The whole point: a 16-sample chunk costs ceil(16/4)=4 dispatches on a
    t_chunk=4 service, not 16."""
    svc, ctl = _services()
    sid = svc.open_session()
    try:
        before = svc.dispatches
        svc.push_audio({sid: np.zeros((16, 2), np.float32)})
        assert svc.dispatches - before == 4
        before = svc.dispatches
        svc.push_audio({sid: np.zeros((3, 2), np.float32)})  # pow2 bucket
        assert svc.dispatches - before == 1
    finally:
        svc.close(sid)


# ---------------------------------------------------------------------------
# Fused kernel fast path: grid executor + service, bit-identical to the
# pre-existing chunked scan (PR 2's cross-program discipline must survive)
# ---------------------------------------------------------------------------

def _rows_ordered_history(ring, t):
    """Row-index reference: row i is ring row (t+i) mod n."""
    n = ring.shape[1]
    idx = (t[:, None].astype(np.int64) + np.arange(n)[None, :]) % n
    return np.take_along_axis(ring, idx[:, :, None], axis=1)


def _rows_ring_advance(strip, t, lengths, n):
    """Row-index reference: window strip[L : L+n], row s of the new ring
    taking window row (s - t - L) mod n."""
    ar = np.arange(n)[None, :]
    window = np.take_along_axis(strip, (lengths[:, None] + ar)[:, :, None],
                                axis=1)
    perm = (ar - (t.astype(np.int64) + lengths)[:, None]) % n
    return np.take_along_axis(window, perm[:, :, None], axis=1)


@pytest.mark.parametrize("T", [160, 784])
@pytest.mark.parametrize("n", [6, 192, 384, 12288])
def test_ring_relay_bit_exact_vs_row_index(n, T):
    """The fused step's ring re-lay (per-slot rotations) is the same
    permutation as indexing every row, bit for bit: time-ordering a ring
    and laying the advanced window back, for step counters at 0, at
    t = n-1 (mod n) and near the int32 limit, and lengths 0 .. T."""
    rng = np.random.default_rng(n * 1000 + T)
    t = np.array([0, n - 1, 5 * n - 1, 2**31 - 1 - T,
                  rng.integers(0, 2**30), rng.integers(0, 10 * n)], np.int32)
    lengths = np.array([0, T, rng.integers(1, T), T, 0,
                        rng.integers(0, T + 1)], np.int32)
    S, c = len(t), 3
    ring = rng.normal(size=(S, n, c)).astype(np.float32)
    hist = np.asarray(jax.jit(_ordered_history)(ring, t))
    np.testing.assert_array_equal(hist, _rows_ordered_history(ring, t))
    strip = np.concatenate(
        [hist, rng.normal(size=(S, T, c)).astype(np.float32)], axis=1)
    new = np.asarray(jax.jit(_ring_advance, static_argnums=3)(
        strip, t, lengths, n))
    np.testing.assert_array_equal(new,
                                  _rows_ring_advance(strip, t, lengths, n))
    frozen = lengths == 0
    np.testing.assert_array_equal(new[frozen], ring[frozen])


@pytest.mark.parametrize("quantize", [False, True])
def test_fused_chunk_bit_identical_to_grid_scan(quantize):
    """make_grid_fused on baked params == grid_scan on the same baked
    params, bit for bit: outputs at valid positions AND end state, over a
    multi-chunk schedule whose boundaries straddle ring wraparound, with
    ragged lengths including a frozen zero-length slot."""
    cfg, bundle, params, bn = _setup()
    scan_p, scan_bn, fused_p = bake_stream_params(params, bn, cfg,
                                                  quantize=quantize)
    S, T = 4, 7  # 7 is coprime with every ring depth of this config
    depths = {n for b in ring_sizes(cfg).values() for (n, _c) in b.values()}
    assert all(T % n != 0 for n in depths), (T, depths)
    scan = jax.jit(lambda p, b, s, xx, v: grid_scan(
        p, b, cfg, s, xx, v, quantize=quantize))
    fused = jax.jit(make_grid_fused(cfg, quantize=quantize))
    ga, gb = grid_init(cfg, S), grid_init(cfg, S)
    rng = np.random.default_rng(11)
    for step in range(5):  # several wraps of every ring
        x = rng.normal(size=(S, T, 2)).astype(np.float32)
        lens = rng.integers(0, T + 1, size=S)
        lens[step % S] = 0  # always one fully frozen slot
        ga, emb_a, log_a = scan(scan_p, scan_bn, ga, jnp.asarray(x),
                                lengths_to_valid(lens, T))
        gb, emb_b, log_b = fused(fused_p, gb, jnp.asarray(x),
                                 jnp.asarray(lens, jnp.int32))
        emb_a, emb_b = np.asarray(emb_a), np.asarray(emb_b)
        log_a, log_b = np.asarray(log_a), np.asarray(log_b)
        for i in range(S):
            np.testing.assert_array_equal(emb_a[i, :lens[i]],
                                          emb_b[i, :lens[i]])
            np.testing.assert_array_equal(log_a[i, :lens[i]],
                                          log_b[i, :lens[i]])
        for a, b in zip(jax.tree.leaves(ga), jax.tree.leaves(gb)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("quantize", [False, True])
def test_fused_service_bit_identical_incl_park_resume(quantize):
    """A fused=True service == an unfused control running the existing
    chunked scan on the same baked params — bit for bit through ragged
    pushes, enrollment, tenant logits, explicit park, LRU eviction, and
    resume in a different slot."""
    cfg, bundle, params, bn = _setup()
    scan_p, scan_bn, _ = bake_stream_params(params, bn, cfg,
                                            quantize=quantize)
    svc = StreamSessionService(bundle, params, bn, n_slots=2, max_tenants=2,
                               t_chunk=4, quantize=quantize, fused=True,
                               max_sessions=4)
    ctl = StreamSessionService(bundle, scan_p, scan_bn, n_slots=2,
                               max_tenants=2, t_chunk=4, quantize=quantize,
                               max_sessions=4)
    assert svc.stats()["fused"] and not ctl.stats()["fused"]
    x = np.random.default_rng(12).normal(size=(3, 40, 2)).astype(np.float32)
    shots = np.random.default_rng(13).normal(size=(3, 12, 2)).astype(np.float32)
    fa, fb = svc.open_session(), svc.open_session(tenant=None)
    ca, cb = ctl.open_session(), ctl.open_session(tenant=None)
    for f_r, c_r in [(svc.push_audio({fa: x[0, :9], fb: x[1, :5]}),
                      ctl.push_audio({ca: x[0, :9], cb: x[1, :5]}))]:
        np.testing.assert_array_equal(f_r[fa]["emb"], c_r[ca]["emb"])
        np.testing.assert_array_equal(f_r[fb]["logits"], c_r[cb]["logits"])
    svc.enroll_shots(fb, shots)
    ctl.enroll_shots(cb, shots)
    svc.park(fa)
    ctl.park(ca)
    f_r = svc.push_audio({fa: x[0, 9:30], fb: x[1, 5:30]})
    c_r = ctl.push_audio({ca: x[0, 9:30], cb: x[1, 5:30]})
    np.testing.assert_array_equal(f_r[fa]["emb"], c_r[ca]["emb"])
    np.testing.assert_array_equal(f_r[fb]["tenant_logits"],
                                  c_r[cb]["tenant_logits"])
    assert f_r[fb]["pred"] == c_r[cb]["pred"]
    # slot pressure: opening a third session LRU-evicts; resume must be
    # bit-identical in whatever slot comes free
    fx, cx = svc.open_session(), ctl.open_session()
    assert svc.poll(fa)["state"] == "parked"
    svc.push_audio({fx: x[2, :4]})
    ctl.push_audio({cx: x[2, :4]})
    f_r = svc.push_audio({fa: x[0, 30:40]})
    c_r = ctl.push_audio({ca: x[0, 30:40]})
    np.testing.assert_array_equal(f_r[fa]["emb"], c_r[ca]["emb"])
    np.testing.assert_array_equal(f_r[fa]["logits"], c_r[ca]["logits"])


def test_fused_service_chunk_size_invariance():
    """The fused service's cross-program discipline: pushing one stream
    through different t_chunk buckets yields bit-identical outputs."""
    cfg, bundle, params, bn = _setup()
    x = np.random.default_rng(14).normal(size=(23, 2)).astype(np.float32)
    outs = []
    for t_chunk in (1, 4, 16):
        svc = StreamSessionService(bundle, params, bn, n_slots=2,
                                   t_chunk=t_chunk, fused=True)
        sid = svc.open_session()
        r = svc.push_audio({sid: x})[sid]
        outs.append((r["emb"], r["logits"]))
    for e, l in outs[1:]:
        np.testing.assert_array_equal(outs[0][0], e)
        np.testing.assert_array_equal(outs[0][1], l)


def test_fused_forward_matches_stream_and_unfused():
    """models/tcn.make_fused_forward: bit-identical to the fused chunk
    executor run from a fresh state (same kernels, zero history == causal
    left-pad), and allclose to raw tcn_forward (BN folding reassociates
    by design — that is the documented fused-service caveat)."""
    cfg, bundle, params, bn = _setup()
    scan_p, scan_bn, fused_p = bake_stream_params(params, bn, cfg)
    B, T = 3, 30
    x = np.random.default_rng(15).normal(size=(B, T, 2)).astype(np.float32)
    fwd = jax.jit(make_fused_forward(cfg))
    emb_f, log_f = fwd(fused_p, jnp.asarray(x))
    fused = jax.jit(make_grid_fused(cfg))
    _, emb_s, log_s = fused(fused_p, grid_init(cfg, B), jnp.asarray(x),
                            jnp.full((B,), T, jnp.int32))
    np.testing.assert_array_equal(np.asarray(emb_f), np.asarray(emb_s)[:, -1])
    np.testing.assert_array_equal(np.asarray(log_f), np.asarray(log_s)[:, -1])
    emb_r, log_r, _ = tcn_forward(params, bn, cfg, jnp.asarray(x),
                                  train=False)
    np.testing.assert_allclose(np.asarray(emb_f), np.asarray(emb_r),
                               rtol=2e-4, atol=2e-5)
    np.testing.assert_allclose(np.asarray(log_f), np.asarray(log_r),
                               rtol=2e-4, atol=2e-5)


# ---------------------------------------------------------------------------
# sharding: grid_pspecs / bank_pspecs placement
# ---------------------------------------------------------------------------

def test_grid_pspecs_places_slots_on_data_and_banks_on_model():
    """Acceptance: every slot-grid leaf's leading axis maps to 'data' and
    every bank leaf's leading axis to 'model' (through sharding/rules)."""
    from repro.launch.mesh import make_mesh
    cfg, bundle, params, bn = _setup()
    mesh = make_mesh((1, 1), ("data", "model"))
    specs = jax.tree.leaves(grid_pspecs(cfg, mesh, n_slots=2))
    assert specs and all(len(p) >= 1 and p[0] == "data" for p in specs)
    bspecs = jax.tree.leaves(bank_pspecs(bank_init(2, 2, 4), mesh))
    assert bspecs and all(len(p) >= 1 and p[0] == "model" for p in bspecs)


def test_service_runs_unchanged_on_one_device_mesh():
    """Acceptance: the sharded service on a 1-device mesh is bit-identical
    to the unsharded service."""
    from repro.launch.mesh import make_mesh
    cfg, bundle, params, bn = _setup()
    mesh = make_mesh((1, 1), ("data", "model"))
    plain = StreamSessionService(bundle, params, bn, n_slots=2, max_tenants=1,
                                 t_chunk=4)
    meshed = StreamSessionService(bundle, params, bn, n_slots=2, max_tenants=1,
                                  t_chunk=4, mesh=mesh)
    x = np.random.default_rng(6).normal(size=(14, 2)).astype(np.float32)
    a = plain.open_session()
    b = meshed.open_session()
    ra = plain.push_audio({a: x})[a]
    rb = meshed.push_audio({b: x})[b]
    np.testing.assert_array_equal(ra["emb"], rb["emb"])
    np.testing.assert_array_equal(ra["logits"], rb["logits"])
