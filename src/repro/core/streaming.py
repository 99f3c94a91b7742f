"""Ring-buffer streaming TCN execution — the paper's §III-B contribution.

Chameleon's "greedy dilation-aware execution with layer-wise FIFO activation
storage" (Fig. 8) keeps, per conv layer, only the last (k-1)·d activations and
overwrites the oldest slot each step.  That is precisely a ring buffer; total
streaming state is O(receptive field), *independent of sequence length* —
two orders of magnitude below a same-length KV cache, which is what makes
16 kHz raw-audio KWS feasible on 2 kB of activation memory.

This module is the JAX equivalent: per-layer ring buffers in a pytree,
indexed with a shared step counter mod buffer length; one jitted ``step``
advances all layers for one timestep.  Output is bit-exact vs. the
full-sequence convolution (tests/test_tcn_stream.py), reproducing the
"identical outputs" claim of Fig. 8(c).

The residual path needs no extra buffer at all (the paper's dual-port
register file, Fig. 9): the block input of the current step is still live
when the residual add happens.

The params-as-jit-ARGUMENTS discipline documented on ``stream_scan_single``
is load-bearing well beyond this module: any chunked scan whose per-step
outputs must be bit-identical across separately compiled chunk sizes needs
it.  sessions/lm.decode_scan applies the same rule to LM serving, where the
KV-cache token chunk is the exact analog of the time chunk here.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.models.config import ArchConfig
from repro.models.tcn import BN_EPS
from repro.quant.log2 import fake_quant_act_u4, fake_quant_log2
from repro.sharding.rules import DEFAULT_RULES, pspec_sized, resolve_rules


def ring_sizes(cfg: ArchConfig) -> dict:
    """Per-layer FIFO depths: (k-1)*d for each of the two convs per block."""
    k = cfg.tcn_kernel
    out = {}
    c_in = cfg.tcn_in_channels
    for i, c in enumerate(cfg.tcn_channels):
        d = 2 ** i
        out[f"b{i}"] = {"ring1": ((k - 1) * d, c_in), "ring2": ((k - 1) * d, c)}
        c_in = c
    return out


def stream_state_bytes(cfg: ArchConfig, bytes_per_act: float = 0.5) -> float:
    """Total streaming activation memory (the paper counts 4-bit = 0.5 B)."""
    return sum(n * c * bytes_per_act
               for b in ring_sizes(cfg).values() for (n, c) in b.values())


def stream_init(cfg: ArchConfig, batch: int, dtype=jnp.float32) -> dict:
    state = {"t": jnp.zeros((), jnp.int32), "blocks": {}}
    for name, rs in ring_sizes(cfg).items():
        (n1, c1), (n2, c2) = rs["ring1"], rs["ring2"]
        state["blocks"][name] = {
            "ring1": jnp.zeros((batch, n1, c1), dtype),
            "ring2": jnp.zeros((batch, n2, c2), dtype),
        }
    return state


def stream_init_single(cfg: ArchConfig, dtype=jnp.float32) -> dict:
    """Single-session streaming state with NO batch axis: rings (n, c), t ().

    This is the vmappable pytree unit the sessions subsystem stacks into a
    structure-of-arrays slot grid — one leaf set per session, so a session's
    entire stream position is capturable/restorable as one small pytree."""
    state = {"t": jnp.zeros((), jnp.int32), "blocks": {}}
    for name, rs in ring_sizes(cfg).items():
        (n1, c1), (n2, c2) = rs["ring1"], rs["ring2"]
        state["blocks"][name] = {"ring1": jnp.zeros((n1, c1), dtype),
                                 "ring2": jnp.zeros((n2, c2), dtype)}
    return state


def stream_step_single(params, bn_state, cfg: ArchConfig, state: dict,
                       x_t: jax.Array, *, quantize: bool = False):
    """``stream_step`` for one session: x_t (C_in,), rings (n, c).

    Designed to sit under ``jax.vmap`` (sessions/state.py): vmapping this
    over a stacked state recovers exactly the batched math of
    ``stream_step``, but with an *independent* step counter per session —
    streams admitted at different times stay phase-correct."""
    st = {"t": state["t"],
          "blocks": jax.tree.map(lambda a: a[None], state["blocks"])}
    new, emb, logits = stream_step(params, bn_state, cfg, st, x_t[None],
                                   quantize=quantize)
    return ({"t": new["t"],
             "blocks": jax.tree.map(lambda a: a[0], new["blocks"])},
            emb[0], logits[0])


def stream_scan_single(params, bn_state, cfg: ArchConfig, state: dict,
                       x_chunk: jax.Array, valid: jax.Array, *,
                       quantize: bool = False):
    """Advance one session over a whole time chunk INSIDE jit.

    x_chunk: (T, C_in); valid: (T,) bool.  Runs ``jax.lax.scan`` over the T
    samples so a chunk costs ONE dispatch instead of T — the host<->device
    round trip per 16 kHz sample is the serving wall, not the compute
    (ReckOn makes the same amortization argument in hardware).

    ``valid`` handles ragged per-session chunk lengths: steps with
    valid=False leave the state bit-frozen (the same ``jnp.where``
    discipline grid_step uses for inactive slots), so padding a short
    chunk to the compiled T never perturbs the stream.  Outputs at invalid
    steps are computed but meaningless — callers mask them.

    Returns (new_state, embs (T, V), logits (T, n_classes)); step t of the
    outputs is bit-exact vs a ``stream_step_single`` call at that step
    (T=1 is exactly that special case; tests/test_streaming_chunk.py).

    Bit-exactness across SEPARATELY JITTED programs (e.g. a T=160 scan vs
    160 single steps) additionally requires params/bn_state to enter jit
    as arguments, not closure constants — XLA constant-folds a captured
    BN chain differently per program, reassociating the multiplies by one
    ULP.  Runtime data is never reassociated.
    """
    def body(st, inp):
        x_t, v = inp
        stepped, emb, logits = stream_step_single(
            params, bn_state, cfg, st, x_t, quantize=quantize)
        st2 = jax.tree.map(lambda n, o: jnp.where(v, n, o), stepped, st)
        return st2, (emb, logits)

    new_state, (embs, logits) = jax.lax.scan(body, state, (x_chunk, valid))
    return new_state, embs, logits


# ---------------------------------------------------------------------------
# Fused kernel fast path: whole-chunk block evaluation over ring-buffer taps
# ---------------------------------------------------------------------------

def _block_rows(n):
    """Rows a re-lay moves per gather index: B = gcd(n, 128).  On a TPU the
    ring axis lies along the 128 lanes, and a block of at most one lane row
    per index stays a single gather, where a longer slice is lowered to a
    loop over slots with several device ops a trip."""
    return math.gcd(n, 128)


def _gather_blocks(x, starts, B):
    """result[s, k*B + j] = x[s, starts[s, k] + j] for j < B, each channel's
    rows moved as one block per index.  x: (S, m, c); starts: (S, nb)."""
    S, _, c = x.shape

    def take(rows, o):
        return jax.lax.dynamic_slice_in_dim(rows, o, B)

    per_slot = jax.vmap(jax.vmap(take, (None, 0)), (1, None), 2)
    return jax.vmap(per_slot)(x, starts).reshape(S, -1, c)


def _rotate(x, r):
    """Per-slot rotation along axis 1: result[s, i] = x[s, (r[s]+i) mod n],
    for 0 <= r < n.  A block that starts within B rows of the end runs on
    into a copy of the first B rows."""
    n = x.shape[1]
    B = _block_rows(n)
    starts = (r[:, None] + jnp.arange(0, n, B)) % n
    return _gather_blocks(jnp.concatenate([x, x[:, :B]], axis=1), starts, B)


def _ordered_history(ring, t):
    """Time-order one ring's circular layout.  ring: (S, n, c); t: (S,)
    step counters.  Row i of the result is the sample at time t-n+i —
    slots not yet written (stream younger than n) read their zero init,
    which is exactly causal left-padding."""
    return _rotate(ring, t % ring.shape[1])


def _ring_advance(strip, t, lengths, n):
    """New circular ring contents after consuming ``lengths`` chunk samples.

    strip: (S, n+T, c) time-ordered [history | chunk-values]; the window
    ``strip[L : L+n]`` holds times t+L-n .. t+L-1, re-laid so slot s holds
    the sample at time ≡ s (mod n).  L=0 reproduces the old ring bit-for-
    bit (the inactive-slot freeze), with no branch.  ``t`` is reduced
    mod n before ``lengths`` is added, so a long stream cannot overflow."""
    B = _block_rows(n)
    window = _gather_blocks(strip, lengths[:, None] + jnp.arange(0, n, B), B)
    return _rotate(window, -(t % n + lengths) % n)


def _shard_slots(block_fn, mesh):
    """Run ``block_fn`` per device on its own slots.  A Pallas kernel
    cannot be partitioned automatically, and the block is independent
    across slots, so each device runs it on its shard of the slot axis
    (the ``slots`` rule's mesh axis, replicated when S does not divide
    it) with the weights replicated — no collectives."""
    rules = resolve_rules(DEFAULT_RULES, mesh)

    def sharded(strip1, hist2, p, **kw):
        spec = pspec_sized(("slots",), rules, strip1.shape[:1], mesh)
        return jax.shard_map(
            functools.partial(block_fn, **kw), mesh=mesh,
            in_specs=(spec, spec, P()), out_specs=(spec, spec),
            check_vma=False)(strip1, hist2, p)

    return sharded


def make_fused_chunk(cfg: ArchConfig, *, quantize: bool = False,
                     backend: str | None = None, mesh=None):
    """Build the fused chunk executor (kernel backend resolved ONCE).

    Returns ``fused_chunk(fused_params, states, x, lengths)`` advancing a
    whole slot grid over a time chunk through kernels/tcn_block.py:
    ``states`` is the SoA grid (rings (S, n, c), t (S,)); x: (S, T, C_in);
    lengths: (S,) valid-prefix lengths (the service's ragged chunks are
    always prefixes of the padded tick).  Returns (new_states, embs
    (S, T, V), logits (S, T, n_classes)); outputs at positions >= lengths
    are computed-but-meaningless (callers slice), state bit-freezes there.

    Vs ``grid_scan`` this pays k tap-shifted batched matmuls per conv for
    the WHOLE chunk instead of a T-step lax.scan of per-sample ops, and
    the conv history is the ring taps themselves — no per-chunk re-pad.
    On baked params (models/tcn.bake_stream_params) it is bit-identical
    to ``grid_scan``; params must enter jit as arguments (same discipline
    as stream_scan_single).  ``backend=None`` defers to
    ``cfg.kernel_backend``.  With a ``mesh`` the block ops run under
    ``shard_map`` over the slot axis (``_shard_slots``)."""
    from repro.kernels.tcn_block import expand_weight, make_block_fn

    block_fn = make_block_fn(backend or cfg.kernel_backend)
    if mesh is not None:
        block_fn = _shard_slots(block_fn, mesh)
    k = cfg.tcn_kernel
    qa = (lambda a: fake_quant_act_u4(a, jnp.float32(cfg.act_scale))) \
        if quantize else (lambda a: a)

    def fused_chunk(fused_params, states, x, lengths):
        t = states["t"]
        lengths = jnp.asarray(lengths, t.dtype)
        new_blocks = {}
        h = x
        for i in range(len(cfg.tcn_channels)):
            name = f"b{i}"
            d = 2 ** i
            rings = states["blocks"][name]
            hist1 = _ordered_history(rings["ring1"], t)
            hist2 = _ordered_history(rings["ring2"], t)
            strip1 = jnp.concatenate([hist1, h], axis=1)
            h, mid = block_fn(strip1, hist2, fused_params["blocks"][name],
                              dilation=d, k=k, act_scale=cfg.act_scale,
                              quantize=quantize)
            strip2 = jnp.concatenate([hist2, mid], axis=1)
            new_blocks[name] = {
                "ring1": _ring_advance(strip1, t, lengths,
                                       rings["ring1"].shape[1]),
                "ring2": _ring_advance(strip2, t, lengths,
                                       rings["ring2"].shape[1]),
            }
        emb = h @ expand_weight(fused_params["head_w"]) + fused_params["head_b"]
        emb = qa(jax.nn.relu(emb))
        logits = emb @ fused_params["fc"]["w"] + fused_params["fc"]["b"]
        return {"t": t + lengths, "blocks": new_blocks}, emb, logits

    return fused_chunk


def _taps(ring, x_t, t, dilation: int, k: int):
    """Collect the k conv taps for the current step: x_{t-(k-1-j)d}, j=0..k-1.

    The newest tap is x_t itself (passed in registers, not the buffer) —
    taps older than the start of the stream read zero-initialized slots,
    matching causal left-padding."""
    n = ring.shape[1]  # (k-1)*d
    taps = []
    for j in range(k - 1):
        off = (k - 1 - j) * dilation  # steps back
        idx = jnp.mod(t - off, n)
        taps.append(jax.lax.dynamic_index_in_dim(ring, idx, axis=1, keepdims=False))
    taps.append(x_t)
    return taps  # list of (B, C), ordered oldest tap (w[0]) .. newest (w[k-1])


def _write(ring, x_t, t):
    n = ring.shape[1]
    return jax.lax.dynamic_update_index_in_dim(ring, x_t, jnp.mod(t, n), axis=1)


def _bn_inf(x, p, st, which):
    inv = jax.lax.rsqrt(st[f"{which}_var"] + BN_EPS)
    return (x - st[f"{which}_mean"]) * inv * p[which]["scale"] + p[which]["bias"]


def stream_step(params, bn_state, cfg: ArchConfig, state: dict, x_t: jax.Array,
                *, quantize: bool = False):
    """Advance the TCN one timestep.  x_t: (B, C_in).

    Returns (new_state, embedding (B, V), logits (B, n_classes)).
    Matches ``tcn_forward(...)[:, t]`` exactly for every t (tested).
    """
    qw = (lambda w: fake_quant_log2(w)) if quantize else (lambda w: w)
    qa = (lambda a: fake_quant_act_u4(a, jnp.float32(cfg.act_scale))) \
        if quantize else (lambda a: a)
    t = state["t"]
    new_blocks = {}
    h = x_t
    for i in range(len(cfg.tcn_channels)):
        name = f"b{i}"
        p = params["blocks"][name]
        st = bn_state[name]
        rings = state["blocks"][name]
        d = 2 ** i
        k = cfg.tcn_kernel
        w1 = qw(p["conv1_w"])  # (k, Cin, Cout)
        taps = _taps(rings["ring1"], h, t, d, k)
        y = sum(tp @ w1[j] for j, tp in enumerate(taps)) + p["conv1_b"]
        y = qa(jax.nn.relu(_bn_inf(y, p, st, "bn1")))
        w2 = qw(p["conv2_w"])
        taps2 = _taps(rings["ring2"], y, t, d, k)
        y2 = sum(tp @ w2[j] for j, tp in enumerate(taps2)) + p["conv2_b"]
        y2 = _bn_inf(y2, p, st, "bn2")
        if "down_w" in p:
            res = h @ qw(p["down_w"])[0] + p["down_b"]
        else:
            res = h
        new_blocks[name] = {"ring1": _write(rings["ring1"], h, t),
                            "ring2": _write(rings["ring2"], y, t)}
        h = qa(jax.nn.relu(y2 + res))
    emb = h @ qw(params["head_w"]) + params["head_b"]
    emb = qa(jax.nn.relu(emb))
    logits = emb @ params["fc"]["w"] + params["fc"]["b"]
    return {"t": t + 1, "blocks": new_blocks}, emb, logits


# ---------------------------------------------------------------------------
# Greedy dilation-aware (cone-sparse) evaluation — Fig. 7(b)/8(a).
#
# For end-of-window classification (KWS on a 1 s window) only the final
# timestep's class is needed, so layer l need only be evaluated at positions
# in the backward dependency cone — the dilation grid {T-1 - j*d_l}.  Deeper
# layers are evaluated exponentially more sparsely (the paper's "zero-valued
# activations introduced by dilation" skip), the steady-state FIFO per conv
# is k-1 entries *independent of dilation*, and the total activation state is
# sum_l (k-1)*C ~ 2 kB for the raw-audio model — the paper's headline.
# Dense per-step streaming (stream_step above) is the other serving mode
# (per-step outputs); both produce outputs identical to the full conv.
# ---------------------------------------------------------------------------

def _cone_positions(cfg: ArchConfig, T: int):
    """Needed positions per block, top down. Returns list[np-like sorted
    arrays], index 0 = input positions."""
    import numpy as np
    k = cfg.tcn_kernel
    nb = len(cfg.tcn_channels)
    need = {nb: np.array([T - 1])}
    for b in range(nb - 1, -1, -1):
        d = 2 ** b
        ps = need[b + 1]
        # two stacked convs with the same dilation: offsets 0..2(k-1)d
        offs = np.arange(0, 2 * (k - 1) * d + 1, d)
        prev = (ps[:, None] - offs[None, :]).reshape(-1)
        need[b] = np.unique(prev[prev >= 0])
    return [need[b] for b in range(nb + 1)]


def cone_eval(params, bn_state, cfg: ArchConfig, x, *, quantize: bool = False):
    """Greedy dilation-aware evaluation of the FINAL timestep's embedding:
    computes only the backward cone (paper Fig. 8a).  x: (B, T, Cin).
    Returns (embedding (B, V), logits, positions_evaluated)."""
    import numpy as np
    from repro.quant.log2 import fake_quant_act_u4, fake_quant_log2

    qw = (lambda w: fake_quant_log2(w)) if quantize else (lambda w: w)
    qa = (lambda a: fake_quant_act_u4(a, jnp.float32(cfg.act_scale))) \
        if quantize else (lambda a: a)
    B, T, _ = x.shape
    k = cfg.tcn_kernel
    need = _cone_positions(cfg, T)
    total_evals = 0
    # h holds block-(b) input values at positions need[b]
    h = x[:, jnp.asarray(need[0]), :]
    for b in range(len(cfg.tcn_channels)):
        d = 2 ** b
        p = params["blocks"][f"b{b}"]
        st = bn_state[f"b{b}"]
        pos_in = need[b]
        pos_out = need[b + 1]
        idx_of = {int(v): i for i, v in enumerate(pos_in)}
        # conv1 at the mid grid: positions needed by conv2 of this block
        mid = np.unique((pos_out[:, None]
                         - np.arange(0, (k - 1) * d + 1, d)[None]).reshape(-1))
        mid = mid[mid >= 0]

        def taps(pos_set, source_pos, source_vals, dd):
            cols = []
            src = {int(v): i for i, v in enumerate(source_pos)}
            for j in range(k):
                idx = [src.get(int(q - (k - 1 - j) * dd), -1) for q in pos_set]
                gathered = source_vals[:, jnp.asarray(np.maximum(idx, 0)), :]
                mask = (np.asarray(idx) >= 0).astype(np.float32)[None, :, None]
                cols.append(gathered * mask)  # causal zero-pad
            return cols

        c1 = taps(mid, pos_in, h, d)
        w1 = qw(p["conv1_w"])
        y1 = sum(c @ w1[j] for j, c in enumerate(c1)) + p["conv1_b"]
        y1 = qa(jax.nn.relu(_bn_inf(y1, p, st, "bn1")))
        total_evals += len(mid)
        c2 = taps(pos_out, mid, y1, d)
        w2 = qw(p["conv2_w"])
        y2 = sum(c @ w2[j] for j, c in enumerate(c2)) + p["conv2_b"]
        y2 = _bn_inf(y2, p, st, "bn2")
        total_evals += len(pos_out)
        # residual: block input at pos_out (subset of pos_in)
        ridx = jnp.asarray([idx_of[int(q)] for q in pos_out])
        res_src = h[:, ridx, :]
        if "down_w" in p:
            res = res_src @ qw(p["down_w"])[0] + p["down_b"]
        else:
            res = res_src
        h = qa(jax.nn.relu(y2 + res))
    feat = h[:, -1, :]
    emb = qa(jax.nn.relu(feat @ qw(params["head_w"]) + params["head_b"]))
    logits = emb @ params["fc"]["w"] + params["fc"]["b"]
    return emb, logits, total_evals


def cone_stats(cfg: ArchConfig, seq_len: int):
    """Steady-state greedy-execution accounting for a length-T window:
    per-conv FIFO depth k-1 (dilation-independent!), per-layer evaluations
    = T / dilation."""
    k = cfg.tcn_kernel
    acts = 0
    macs = 0
    c_in = cfg.tcn_in_channels
    for i, c in enumerate(cfg.tcn_channels):
        d = 2 ** i
        evals = max(seq_len // d, 1)
        macs += evals * k * (c_in * c + c * c)
        acts += (k - 1) * (c_in + c)  # two FIFOs per block
        c_in = c
    return {"act_entries": acts, "macs": macs}


def ws_inference_stats(cfg: ArchConfig, seq_len: int):
    """Weight-stationary baseline accounting for the Fig. 8(c) comparison
    (paper: ~90x memory / ~10x compute at 16k steps): activation memory is a
    full-sequence buffer (WS requires pre-loading the sequence), and compute
    evaluates every layer densely at every timestep (no dilation-aware
    skipping of the unused cone complement)."""
    k = cfg.tcn_kernel
    cmax = max(max(cfg.tcn_channels), cfg.tcn_in_channels)
    acts = seq_len * cmax
    macs = 0
    c_in = cfg.tcn_in_channels
    for c in cfg.tcn_channels:
        macs += seq_len * k * (c_in * c + c * c)
        c_in = c
    return {"act_entries": acts, "macs": macs}


def greedy_inference_stats(cfg: ArchConfig, seq_len: int):
    """Chameleon-style streaming accounting: FIFO state + dilation-aware
    compute (only real taps, no zero-padding work)."""
    k = cfg.tcn_kernel
    acts = sum(n * c for b in ring_sizes(cfg).values() for (n, c) in b.values())
    macs = 0
    c_in = cfg.tcn_in_channels
    for i, c in enumerate(cfg.tcn_channels):
        macs += seq_len * k * (c_in * c + c * c)
        c_in = c
    return {"act_entries": acts, "macs": macs}
