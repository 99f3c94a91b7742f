"""Glyphs: Omniglot-like classes of seeded strokes, read pixel by pixel.

A copy of the idea of the program's procedural glyphs, so that the
benchmark's data never depends on the code under test: a few jittered
strokes per class, rendered as Gaussian blobs on a 28 x 28 grid and read
as a 784-sample sequence, made on the device in bulk.  Each tenant's bank
of ``ways`` classes is filled in set-up with ``prefill_shots`` shots a
class, embedded by the reference; each client then walks a seeded pool of
enrollments (``shots`` glyphs of a drawn class) and queries (one glyph of
a drawn class).

Mix keys read here: ``clients``, ``tenants``, ``ways``, ``shots``,
``prefill_shots``, ``pool``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.chip import generator, reference

GRID = 28
STROKES, POINTS, STEPS = 3, 4, 12


def glyph_strokes(seed: int, n_classes: int) -> jax.Array:
    """Stroke control points of each class: (n_classes, STROKES, POINTS, 2)."""
    return jax.random.uniform(
        jax.random.fold_in(reference.key_from_seed(seed), 1),
        (n_classes, STROKES, POINTS, 2), jnp.float32, 3.0, GRID - 3.0)


@jax.jit
def _render(pts, key):
    """pts: (N, STROKES, POINTS, 2) -> (N, GRID * GRID) in [0, 1]."""
    p = pts + 0.8 * jax.random.normal(key, pts.shape, jnp.float32)
    t = jnp.linspace(0.0, 1.0, STEPS)[:, None]
    a, b = p[:, :, :-1, None, :], p[:, :, 1:, None, :]
    c = (a * (1 - t) + b * t).reshape(p.shape[0], -1, 2)    # (N, M, 2)
    g = jnp.arange(GRID, dtype=jnp.float32)
    gx = jnp.exp(-jnp.square(g - c[..., 0:1]) / 1.6)       # (N, M, GRID)
    gy = jnp.exp(-jnp.square(g - c[..., 1:2]) / 1.6)
    img = jnp.einsum("nmy,nmx->nyx", gy, gx, precision="highest")
    return (jnp.clip(img, 0.0, 1.5) / 1.5).reshape(p.shape[0], -1)


def glyphs(strokes: jax.Array, classes, seed: int, salt: int,
           batch: int = 4096) -> jax.Array:
    """One jittered glyph of each class in ``classes`` (N,), on the device:
    (N, 784, 1).  The same (seed, salt) gives the same glyphs."""
    classes = jnp.asarray(classes, jnp.int32)
    key = jax.random.fold_in(reference.key_from_seed(seed), 1000 + salt)
    out = []
    for i in range(0, classes.shape[0], batch):
        sel = classes[i:i + batch]
        n = sel.shape[0]
        pad = batch - n if classes.shape[0] > batch else 0
        sel = jnp.pad(sel, (0, pad))
        out.append(_render(strokes[sel], jax.random.fold_in(key, i))[:n])
    return jnp.concatenate(out)[..., None]


class Source:
    """The inputs of one run, all from the seed."""

    def __init__(self, mix: dict, model: dict, seed: int):
        self.mix, self.seed = mix, seed
        n = mix["tenants"] * mix["ways"]
        self.strokes = glyph_strokes(seed, n)
        rng = np.random.default_rng([int(seed), 4])
        shape = (mix["clients"], mix["pool"])
        self.enroll_cls = rng.integers(0, mix["ways"], shape)
        self.query_cls = rng.integers(0, mix["ways"], shape)
        ten = (np.arange(mix["clients"]) % mix["tenants"])[:, None]
        glob = lambda c: (ten * mix["ways"] + c).reshape(-1)
        self.enroll_x = np.asarray(glyphs(
            self.strokes, np.repeat(glob(self.enroll_cls), mix["shots"]),
            seed, salt=1)).reshape(shape + (mix["shots"], -1, 1))
        self.query_x = np.asarray(glyphs(
            self.strokes, glob(self.query_cls), seed,
            salt=2)).reshape(shape + (-1, 1))

    def prefill(self, params, bn, precision: str = "default") -> np.ndarray:
        """(tenants, ways, prefill_shots, V) embeddings of the set-up
        shots, made by the reference on the device: data for the bank."""
        mix = self.mix
        k = mix["prefill_shots"]
        cls = np.repeat(np.arange(mix["tenants"] * mix["ways"]), k)
        out = []
        for i in range(0, len(cls), 4096):
            sel = np.pad(cls[i:i + 4096], (0, max(0, 4096 - len(cls[i:i + 4096]))))
            x = glyphs(self.strokes, sel, self.seed, salt=100 + i)
            emb = reference.embed_last(params, bn, x, precision=precision)
            out.append(np.asarray(emb)[:min(4096, len(cls) - i)])
        emb = np.concatenate(out)
        return emb.reshape(mix["tenants"], mix["ways"], k, -1)

    async def setup(self, clients, params, bn) -> dict:
        return await generator.enroll_embedded(clients,
                                               self.prefill(params, bn))

    def push_input(self, op) -> np.ndarray:
        return self.query_x[op.client, op.loop % self.mix["pool"]]

    def enroll_input(self, op):
        i = op.loop % self.mix["pool"]
        return int(self.enroll_cls[op.client, i]), self.enroll_x[op.client, i]
