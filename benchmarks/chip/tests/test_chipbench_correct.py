"""The check that decides ``correct``, on the CPU at a tiny size with the
committed limits: a sound run passes; the control (the reference at the
precision below the configuration's, put in the program's place) fails;
and with the timed path broken underneath, each fault a cell can have
fails.  The chip's readings of the same numbers are in PERF.md."""

import jax.numpy as jnp
import pytest

import chip_tiny
from benchmarks.chip import harness


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return chip_tiny.make(tmp_path_factory.mktemp("tiny"))


def _wrap_chunk(svc, fn):
    """Replace the service's compiled chunk step by ``fn(step, *args)``."""
    step = svc._scan_fused
    svc._scan_fused = lambda *a: fn(step, *a)
    return svc


def state_unchanged(svc):
    """The chunk step returns its input state: the ring never advances."""
    return _wrap_chunk(svc, lambda step, fp, states, *a:
                       (states, *step(fp, states, *a)[1:]))


def half_batch(svc):
    """The chunk step runs the first half of the slots only."""
    def fn(step, fp, states, x, lengths, *bank):
        half = jnp.arange(lengths.shape[0]) < lengths.shape[0] // 2
        return step(fp, states, x, jnp.where(half, lengths, 0), *bank)
    return _wrap_chunk(svc, fn)


def answer_altered(svc):
    """One live lane's tenant logits come back negated."""
    def fn(step, fp, states, x, lengths, *bank):
        new, emb, logits, tl, *dev = step(fp, states, x, lengths, *bank)
        lane = jnp.argmax(lengths > 0)
        return (new, emb, logits, tl.at[lane].multiply(-1.0), *dev)
    return _wrap_chunk(svc, fn)


def bank_unchanged(svc):
    """An enrollment into an enrolled way leaves the bank as it was."""
    svc.bankpool.update_class = lambda *a, **k: None
    return svc


def half_shots(svc):
    """An enrollment embeds half of its shots; the mean is over those."""
    embed = svc._embed
    svc._embed = lambda x: embed(x[:x.shape[0] // 2])
    return svc


FAULTS = {
    "audio-stream": [state_unchanged, half_batch, answer_altered],
    "cl-250way": [bank_unchanged, half_shots, answer_altered],
}


@pytest.mark.parametrize("workload", sorted(FAULTS))
def test_sound_run_passes_and_control_fails(root, workload):
    rc, res = chip_tiny.run(root, workload)
    assert rc == 0
    assert res["correct"], res["compared"]
    assert res["attempted"] > 0 and res["failed"] == 0

    p = harness.plan(workload, root)
    run = harness.Run(plan=p, seed=4_000_000_011, seconds=1.0, trace=False,
                      t_process=0.0)
    harness.execute(run, require_tpu=False)
    check = harness.check_module(p)
    assert all(v <= p.limits[k] for k, v in check.compare(run).items())
    check.fabricate(run, harness.CONTROL)
    control = check.compare(run)
    assert any(v > p.limits[k] for k, v in control.items()), control


@pytest.mark.parametrize("workload,fault", [
    (w, f) for w, fs in sorted(FAULTS.items()) for f in fs],
    ids=lambda v: getattr(v, "__name__", v))
def test_fault_fails(root, workload, fault):
    rc, res = chip_tiny.run(root, workload, service_hook=fault)
    assert rc == 0
    assert not res["correct"], res["compared"]
