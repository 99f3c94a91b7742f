"""The Chameleon TCN behind ``StreamSessionService``: the service of a
configuration that names none.

The weights are the reference's (``reference.init_params``), made from the
seed in the layout the program's TCN parameters take; the check reads the
BN statistics beside them.
"""

from __future__ import annotations


def arch_config(config: dict):
    from repro.configs import get_config
    model = dict(config["model"])
    model["tcn_channels"] = tuple(model["tcn_channels"])
    return get_config(config["preset"]).replace(**model)


def weights(cfg, model: dict, seed: int):
    """(params, bn_state) from the seed, checked against the program's
    parameter layout."""
    from benchmarks.chip import reference
    params, bn = reference.init_params(model, seed)
    _same_layout(params, cfg)
    return params, bn


def _same_layout(params, cfg) -> None:
    """The benchmark's weights have the tree and shapes of the program's."""
    import jax
    from repro.models import build_bundle
    want = jax.eval_shape(build_bundle(cfg).init, jax.random.key(0))
    got = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), params)
    if jax.tree.structure(want) != jax.tree.structure(got) or any(
            a.shape != b.shape for a, b in zip(jax.tree.leaves(want),
                                                jax.tree.leaves(got))):
        raise ValueError("benchmark weights do not match the program's layout")


def build(run, cfg, tracer, registry):
    """The service under test.  A mix with ``"mesh": [data, model]`` shards
    the slot grid over ``data`` and the tenants' banks over ``model``."""
    from repro.models import build_bundle
    from repro.sessions import StreamSessionService
    mix, serving = run.plan.mix, run.plan.config["serving"]
    mesh = None
    if "mesh" in mix:
        from repro.launch.mesh import make_mesh
        mesh = make_mesh(tuple(mix["mesh"]), ("data", "model"))
    return StreamSessionService(
        build_bundle(cfg), run.params, run.bn, n_slots=mix["slots"],
        max_tenants=mix["tenants"], max_ways=mix["ways"],
        t_chunk=mix["chunk"], quantize=serving["quantize"],
        fused=serving["fused"], paged_bank=serving["paged_bank"],
        bank_block_ways=serving["bank_block_ways"], metrics=registry,
        tracer=tracer, mesh=mesh)
