"""The served kernels compile for a TPU v5e that is described, not attached.

Interpret mode runs a Pallas kernel's math on the CPU but not Mosaic's
lowering, which refuses what the CPU accepts: value slices at unaligned
offsets, casts it has no rule for, blocks whose layout differs from XLA's,
more scoped VMEM than the limit, a kernel under automatic partitioning.
These tests compile the kernels of the main path at published widths (and
the whole fused chunk step, on one chip and on a 2x2 mesh) with the TPU
compiler installed beside jax, and assert that a Mosaic kernel
(``tpu_custom_call``) is in the program.  Nothing runs.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and the suite runs under
several workers.
"""

import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P, SingleDeviceSharding

from repro.configs import get_config
from repro.kernels.proto_extract import proto_extract
from repro.kernels.tcn_block import tcn_block_pallas
from repro.models import build_bundle
from repro.models.tcn import bake_stream_params, tcn_empty_state
from repro.sessions.state import grid_init, grid_pspecs, make_grid_fused

# (preset, chunk length): raw-input presets stream 10 ms (160-sample)
# chunks, the MFCC preset 16 frames
PRESETS = [("chameleon-tcn", 160), ("chameleon-tcn-audio", 160),
           ("chameleon-tcn-kws", 16)]
N_SLOTS = 64


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here, or its library is held
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    # a described device cannot read the persistent compilation cache back,
    # so keep these compiles out of it (the cache memoizes whether it is
    # on, hence the resets)
    from jax.experimental.compilation_cache import compilation_cache

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


def _sds(tree, sharding):
    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding),
        tree)


def _compiled_text(fn, *args, quantize=False):
    """Compile ``fn`` for the described chip; quantized programs trace at
    "highest" matmul precision, as the service runs them."""
    prec = "highest" if quantize else None
    with jax.default_matmul_precision(prec):
        return jax.jit(fn).lower(*args).compile().as_text()


def _block_weights(Cin, C, k, quantize, down, sharding):
    def w(shape):
        if quantize:
            return {"codes": jax.ShapeDtypeStruct(
                        shape[:-1] + (shape[-1] // 2,), jnp.uint8),
                    "scale": jax.ShapeDtypeStruct((), jnp.float32)}
        return jax.ShapeDtypeStruct(shape, jnp.float32)

    b = jax.ShapeDtypeStruct((C,), jnp.float32)
    p = {"conv1_w": w((k, Cin, C)), "conv1_b": b,
         "conv2_w": w((k, C, C)), "conv2_b": b}
    if down:
        p["down_w"], p["down_b"] = w((1, Cin, C)), b
    return _sds(p, sharding)


@pytest.mark.parametrize("quantize", [False, True], ids=["fp32", "u4"])
@pytest.mark.parametrize("which", ["first", "widest"])
@pytest.mark.parametrize("preset,T", PRESETS)
def test_tcn_block_compiles(one_chip, preset, T, which, quantize):
    """The first block (raw or MFCC input, with the 1x1 downsample) and the
    widest-dilation block of every preset — for the audio preset that is
    d=2048, whose ring strips need the raised scoped-VMEM limit."""
    cfg = get_config(preset)
    k, chans = cfg.tcn_kernel, cfg.tcn_channels
    if which == "first":
        Cin, C, d = cfg.tcn_in_channels, chans[0], 1
    else:
        Cin, C, d = chans[-1], chans[-1], 2 ** (len(chans) - 1)
    n = (k - 1) * d
    p = _block_weights(Cin, C, k, quantize, Cin != C, one_chip)
    strip1, hist2 = _sds((jax.ShapeDtypeStruct((N_SLOTS, n + T, Cin),
                                               jnp.float32),
                          jax.ShapeDtypeStruct((N_SLOTS, n, C), jnp.float32)),
                         one_chip)
    fn = functools.partial(tcn_block_pallas, dilation=d, k=k,
                           act_scale=cfg.act_scale, quantize=quantize)
    assert "tpu_custom_call" in _compiled_text(fn, strip1, hist2, p,
                                               quantize=quantize)


@pytest.mark.parametrize("n_ways", [5, 250])
def test_proto_extract_compiles(one_chip, n_ways):
    """5-way enrollment and the 250-way continual-learning store (two
    128-row blocks)."""
    emb = jax.ShapeDtypeStruct((n_ways, 64), jnp.float32, sharding=one_chip)
    onehot = jax.ShapeDtypeStruct((n_ways, n_ways), jnp.float32,
                                  sharding=one_chip)
    text = _compiled_text(lambda e, o: proto_extract(e, o, 1), emb, onehot)
    assert "tpu_custom_call" in text


def _step_args(cfg, T, quantize):
    params = jax.eval_shape(lambda: build_bundle(cfg).init(jax.random.key(0)))
    fused = jax.eval_shape(lambda p: bake_stream_params(
        p, tcn_empty_state(cfg), cfg, quantize=quantize)[2], params)
    states = jax.eval_shape(lambda: grid_init(cfg, N_SLOTS))
    x = jax.ShapeDtypeStruct((N_SLOTS, T, cfg.tcn_in_channels), jnp.float32)
    lengths = jax.ShapeDtypeStruct((N_SLOTS,), jnp.int32)
    return fused, states, x, lengths


def _row_gathers(text, c):
    """Gathers that fetch one row of a ``c``-channel ring per index: a
    slice of 1 along the ring axis and ``c`` along the channels."""
    return [s for s in re.findall(r"slice_sizes=\{([0-9,]+)\}", text)
            if sorted(map(int, s.split(","))) == [1, 1, c]]


@pytest.mark.parametrize("quantize", [False, True], ids=["fp32", "u4"])
def test_fused_chunk_step_compiles(one_chip, quantize):
    """One whole fused chunk step of the raw-audio preset at 64 slots:
    the ring re-lay, 12 block kernels and the head, as the service runs
    it for 10 ms chunks.  The re-lay moves each slot's ring in blocks of
    rows: no gather fetches single ring rows, and no loop over slots runs
    (each trip of one would add device ops to every dispatch).  The only
    Mosaic kernels are the blocks'."""
    cfg = get_config("chameleon-tcn-audio")
    args = _sds(_step_args(cfg, 160, quantize), one_chip)
    step = make_grid_fused(cfg, quantize=quantize, backend="mosaic")
    text = _compiled_text(step, *args, quantize=quantize)
    assert text.count("tpu_custom_call") == len(cfg.tcn_channels)
    assert not _row_gathers(text, cfg.tcn_channels[0])
    assert " while(" not in text


def test_fused_chunk_step_compiles_on_mesh(topo, one_chip):
    """The same step with its slots sharded over a (4, 1) data x model
    mesh: each device runs the kernels on its own 16 slots under
    shard_map, and no collective is needed."""
    cfg = get_config("chameleon-tcn-audio")
    mesh = jax.sharding.Mesh(np.array(topo.devices).reshape(4, 1),
                             ("data", "model"))
    fused, states, x, lengths = _step_args(cfg, 160, False)
    fused = _sds(fused, NamedSharding(mesh, P()))
    states = jax.tree.map(
        lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                          sharding=NamedSharding(mesh, s)),
        states, grid_pspecs(cfg, mesh, N_SLOTS))
    x, lengths = _sds((x, lengths), NamedSharding(mesh, P("data")))
    step = make_grid_fused(cfg, backend="mosaic", mesh=mesh)
    text = _compiled_text(step, fused, states, x, lengths)
    assert text.count("tpu_custom_call") >= len(cfg.tcn_channels)
    assert "all-gather" not in text and "all-reduce" not in text
