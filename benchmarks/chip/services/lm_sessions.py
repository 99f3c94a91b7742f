"""A language model behind ``LMSessionService``, the LM slot grid.

The configuration names a preset (``repro.configs``) and, under ``model``,
every field it changes; lists become tuples.  Each size of the grid has
one owner: the mix gives ``slots`` and ``seq_cap`` (its traffic is sized
to them), the configuration's ``serving`` block the rest: ``t_chunk``,
``prefill_chunk``, ``block_len``, ``n_blocks`` (absent: the service's
default pool), ``prefix_cache`` and ``paged``.

The weights are the benchmark's, drawn from the seed: only the tree and
the shapes and types of its leaves are taken from the program's layout.
"""

from __future__ import annotations

SERVING_KEYS = ("t_chunk", "prefill_chunk", "block_len", "n_blocks",
                "prefix_cache", "paged")


def arch_config(config: dict):
    from repro.configs import get_config
    model = {k: tuple(v) if isinstance(v, list) else v
             for k, v in config.get("model", {}).items()}
    return get_config(config["preset"]).replace(**model)


def weights(cfg, model: dict, seed: int):
    """(params, None), made on the device in one jitted call from the
    seed, in the tree and types of the program's layout: a leaf of two or
    more axes is normal with deviation 1 / sqrt(its next-to-last axis),
    a leaf of fewer axes is 1 (norm scales).  Nothing besides the weights
    is needed by a check."""
    import jax
    import jax.numpy as jnp
    from repro.models import build_bundle

    from benchmarks.chip import reference
    leaves, tree = jax.tree.flatten(
        jax.eval_shape(build_bundle(cfg).init, jax.random.key(0)))

    def init(key):
        out = []
        for k, a in zip(jax.random.split(key, len(leaves)), leaves):
            x = (jax.random.normal(k, a.shape, jnp.float32)
                 * (a.shape[-2] ** -0.5) if a.ndim >= 2
                 else jnp.ones(a.shape, jnp.float32))
            out.append(x.astype(a.dtype))
        return jax.tree.unflatten(tree, out)

    return jax.jit(init)(reference.key_from_seed(seed)), None


def build(run, cfg, tracer, registry):
    from repro.models import build_bundle
    from repro.sessions.lm import LMSessionService
    mix, serving = run.plan.mix, run.plan.config.get("serving", {})
    grid = {k: serving[k] for k in SERVING_KEYS if k in serving}
    return LMSessionService(
        build_bundle(cfg), run.params, n_slots=mix["slots"],
        seq_cap=mix["seq_cap"], metrics=registry, tracer=tracer, **grid)
