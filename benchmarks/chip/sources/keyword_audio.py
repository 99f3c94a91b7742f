"""Keyword audio: streams of seeded 1 s keyword clips laid end to end.

A copy of the idea of the program's GSC-like procedural audio, so that the
benchmark's data never depends on the code under test: class-specific
formant trajectories under a Hann envelope, plus noise, made on the host
in bulk.  Each client streams clips drawn from a seeded pool; each tenant's
bank holds ``ways`` keyword classes of ``shots`` support clips, embedded by
the reference in set-up and enrolled as embeddings.

Mix keys read here: ``clients``, ``tenants``, ``ways``, ``shots``,
``shot_samples``, ``chunk``, ``clip_pool``.
"""

from __future__ import annotations

import numpy as np

from benchmarks.chip import generator, reference

SR = 16000


def keyword_clips(seed: int, n_classes: int, classes, salt: int,
                  n_samples: int = SR, snr_db: float = 6.0) -> np.ndarray:
    """One clip of each class in ``classes``: (len(classes), n_samples)
    float32 in [-1, 1].  The keywords (formant trajectories) come from the
    seed alone; jitter and noise from (seed, salt)."""
    rng = np.random.default_rng([int(seed), 1])
    n_seg = rng.integers(2, 4, n_classes)
    f0 = rng.uniform(100, 300, (n_classes, 3))
    f1 = rng.uniform(400, 2500, (n_classes, 3))
    slope = rng.uniform(-400, 400, (n_classes, 3))
    cls = np.asarray(classes, np.int64)
    n = len(cls)
    rng = np.random.default_rng([int(seed), 2, int(salt)])
    jit = rng.normal(0, 20, (n, 3))
    t = np.arange(n_samples) / SR
    segs = n_seg[cls][:, None]
    seg = np.minimum(np.arange(n_samples)[None, :] * segs // n_samples,
                     segs - 1)                                  # (n, T)
    tt = t[None, :] - seg * (n_samples // segs) / SR
    rows = np.arange(n)[:, None]
    a = f0[cls[:, None], seg] + jit[rows, seg]
    b = f1[cls[:, None], seg] + jit[rows, seg] + slope[cls[:, None], seg] * tt
    x = np.sin(2 * np.pi * a * tt) + 0.6 * np.sin(2 * np.pi * b * tt)
    noise = rng.normal(0, 10 ** (-snr_db / 20), (n, n_samples))
    out = np.clip(x * np.hanning(n_samples)[None] * 0.5 + noise, -1, 1)
    return out.astype(np.float32)


class Source:
    """The inputs of one run, all from the seed."""

    def __init__(self, mix: dict, model: dict, seed: int):
        self.mix, self.seed = mix, seed
        n_cls = model["n_classes"]
        pool = mix["clip_pool"]
        self.pool = keyword_clips(seed, n_cls, np.arange(pool) % n_cls, salt=0)
        rng = np.random.default_rng([int(seed), 3])
        self.order = rng.integers(0, pool, (mix["clients"], 1 << 12))
        self.n_classes = n_cls

    def way_class(self, tenant: int, way: int) -> int:
        return (3 * tenant + way) % self.n_classes

    def support(self) -> np.ndarray:
        """(tenants, ways, shots, shot_samples, 1) support clips."""
        mix = self.mix
        out = []
        for t in range(mix["tenants"]):
            cls = [self.way_class(t, j) for j in range(mix["ways"])]
            out.append(keyword_clips(
                self.seed, self.n_classes, np.repeat(cls, mix["shots"]),
                salt=1 + t, n_samples=mix["shot_samples"]))
        return np.stack(out).reshape(mix["tenants"], mix["ways"],
                                     mix["shots"], -1, 1)

    async def setup(self, clients, params, bn) -> dict:
        """Every tenant's bank: the support clips' embeddings, made by the
        reference on the device (the weights are arguments of its
        programs, so no seed compiles anew), enrolled way by way."""
        x = self.support()
        emb = reference.embed_last(params, bn, x.reshape((-1,) + x.shape[3:]),
                                   precision="default")
        return await generator.enroll_embedded(
            clients, np.asarray(emb).reshape(x.shape[:3] + (-1,)))

    def stream(self, client: int, start: int, n: int) -> np.ndarray:
        """Samples [start, start + n) of the client's stream: (n, 1)."""
        T = self.pool.shape[1]
        out = np.empty(n, np.float32)
        i = 0
        while i < n:
            p = start + i
            clip = self.order[client, (p // T) % self.order.shape[1]]
            take = min(n - i, T - p % T)
            out[i:i + take] = self.pool[clip, p % T:p % T + take]
            i += take
        return out[:, None]

    def push_input(self, op) -> np.ndarray:
        return self.stream(op.client, op.pos, self.mix["chunk"])
