"""The plain reference of the Chameleon TCN, and the benchmark's weights.

Written from the paper (§III-B, Eq. 6/7) in straightforward ``jax.numpy``
and float32, sharing no code with the served path: causal dilated
residual blocks (two k-tap convs with BN, ReLU, a 1x1 downsample where the
width changes), an embedding head, a class head, and prototype logits in
float64 numpy.  The served path folds BN into the convs, runs the blocks
as Pallas kernels over ring buffers and gathers the tenant's prototypes
through a paged bank; none of that is here.

The weights are made here, on the device, from the seed, in the layout the
program's TCN parameters take.  ``precision`` says how the reference's
matmuls run: ``"highest"`` is the float32 reference; the lower-precision
control (``low="float8_e4m3fn"``) rounds every matmul operand to that
type first.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import ml_dtypes
import numpy as np

BN_EPS = 1e-5


def key_from_seed(seed: int) -> jax.Array:
    """A threefry key from any whole number (``jax.random.key`` keeps only
    32 bits without x64)."""
    words = np.random.SeedSequence(int(seed)).generate_state(2, np.uint32)
    return jax.random.wrap_key_data(jnp.asarray(words, jnp.uint32),
                                    impl="threefry2x32")


def _init(model: dict, key):
    k = model["tcn_kernel"]
    keys = iter(jax.random.split(key, 16 * len(model["tcn_channels"]) + 16))

    def normal(shape, std):
        return std * jax.random.normal(next(keys), shape, jnp.float32)

    def uniform(shape, lo, hi):
        return jax.random.uniform(next(keys), shape, jnp.float32, lo, hi)

    params: dict = {"blocks": {}}
    bn: dict = {}
    c_in = model["tcn_in_channels"]
    for i, c in enumerate(model["tcn_channels"]):
        b = {"conv1_w": normal((k, c_in, c), (k * c_in) ** -0.5),
             "conv1_b": normal((c,), 0.05),
             "conv2_w": normal((k, c, c), (k * c) ** -0.5),
             "conv2_b": normal((c,), 0.05),
             "bn1": {"scale": uniform((c,), 0.8, 1.2),
                     "bias": normal((c,), 0.05)},
             "bn2": {"scale": uniform((c,), 0.8, 1.2),
                     "bias": normal((c,), 0.05)}}
        if c_in != c:
            b["down_w"] = normal((1, c_in, c), c_in ** -0.5)
            b["down_b"] = normal((c,), 0.05)
        params["blocks"][f"b{i}"] = b
        bn[f"b{i}"] = {"bn1_mean": normal((c,), 0.05),
                       "bn1_var": uniform((c,), 0.8, 1.2),
                       "bn2_mean": normal((c,), 0.05),
                       "bn2_var": uniform((c,), 0.8, 1.2)}
        c_in = c
    v = model["embed_dim"]
    params["head_w"] = normal((c_in, v), c_in ** -0.5)
    params["head_b"] = normal((v,), 0.05)
    params["fc"] = {"w": normal((v, model["n_classes"]), v ** -0.5),
                    "b": normal((model["n_classes"],), 0.05)}
    return params, bn


def init_params(model: dict, seed: int):
    """(params, bn_state) on the default device, made in one jitted call."""
    return jax.jit(functools.partial(_init, model))(key_from_seed(seed))


def _mm(a, b, precision, low):
    if low is not None:
        a = a.astype(low).astype(jnp.float32)
        b = b.astype(low).astype(jnp.float32)
    return jnp.matmul(a, b, precision=precision)


def _conv(x, w, b, d, precision, low):
    """Causal dilated conv as k tap matmuls over a left-zero-padded input.
    x: (B, T, Cin); w: (k, Cin, C)."""
    k = w.shape[0]
    T = x.shape[1]
    xp = jnp.pad(x, ((0, 0), ((k - 1) * d, 0), (0, 0)))
    y = sum(_mm(xp[:, j * d:j * d + T], w[j], precision, low)
            for j in range(k))
    return y + b


def _bn(y, p, st, which):
    inv = 1.0 / jnp.sqrt(st[f"{which}_var"] + BN_EPS)
    return (y - st[f"{which}_mean"]) * inv * p[which]["scale"] + p[which]["bias"]


def features(params, bn, x, *, precision="highest", low=None):
    """Last block's output at every position.  x: (B, T, Cin)."""
    h = x
    for i in range(len(params["blocks"])):
        p = params["blocks"][f"b{i}"]
        st = bn[f"b{i}"]
        d = 2 ** i
        y = jax.nn.relu(_bn(_conv(h, p["conv1_w"], p["conv1_b"], d,
                                  precision, low), p, st, "bn1"))
        y = _bn(_conv(y, p["conv2_w"], p["conv2_b"], d, precision, low),
                p, st, "bn2")
        res = _conv(h, p["down_w"], p["down_b"], 1, precision, low) \
            if "down_w" in p else h
        h = jax.nn.relu(y + res)
    return h


def heads(params, h, *, precision="highest", low=None):
    """(embedding, class logits) of features h (..., C)."""
    emb = jax.nn.relu(_mm(h, params["head_w"], precision, low)
                      + params["head_b"])
    return emb, _mm(emb, params["fc"]["w"], precision, low) + params["fc"]["b"]


@functools.partial(jax.jit, static_argnames=("precision", "low"))
def outputs_at(params, bn, x, pos, *, precision="highest", low=None):
    """(emb, logits) at positions ``pos`` (B, P) of each row of x."""
    h = features(params, bn, x, precision=precision, low=low)
    h = jnp.take_along_axis(h, pos[:, :, None], axis=1)
    return heads(params, h, precision=precision, low=low)


@functools.partial(jax.jit, static_argnames=("precision", "low"))
def embed_last(params, bn, x, *, precision="highest", low=None):
    """Embedding of each whole sequence (its last position).  x: (B, T, Cin)."""
    h = features(params, bn, x, precision=precision, low=low)[:, -1]
    return heads(params, h, precision=precision, low=low)[0]


def round_to(a: np.ndarray, low: str | None) -> np.ndarray:
    """float64 values of ``a`` rounded through ``low`` (None: unchanged)."""
    a = np.asarray(a, np.float64)
    if low is None:
        return a
    return a.astype(np.float32).astype(getattr(ml_dtypes, low)).astype(np.float64)


def proto_logits(emb, sums, counts, low: str | None = None) -> np.ndarray:
    """Eq. 6 in the per-way mean form, float64: w = sum / count,
    b = -|w|^2 / 2, logits = emb . w + b.  emb: (..., V); sums: (N, V)."""
    w = np.asarray(sums, np.float64) / np.maximum(
        np.asarray(counts, np.float64), 1.0)[:, None]
    b = -0.5 * (w * w).sum(-1)
    return round_to(emb, low) @ round_to(w, low).T + b


def pad_len(n: int, quantum: int = 4096) -> int:
    """A time length >= n from a short ladder (powers of two times the
    quantum), so references over runs of different lengths share
    compiled programs.  Padding at the end leaves a causal output alone."""
    m = quantum
    while m < n:
        m *= 2
    return m
