"""The harness is driven by data: a cell added as files and entries runs
with no edit to any file that was there, a new kind of inputs and a new
loop verb included.  Off a TPU, and outside a whole checkout, the command
fails and prints no result."""

import hashlib
import json
import os
import shutil
import subprocess
import sys

import chip_tiny
from chip_tiny import BENCH, ROOT

from benchmarks.chip import harness

EXTRA_METRIC = '''"""Dispatches in the window (a test of the harness's discovery)."""


def read(ctx):
    return float(len(ctx.spans_named("dispatch"))) or None
'''


EXTRA_SOURCE = '''"""Seeded pure tones, one frequency a client, pushed at a pace (a
test of the harness's discovery)."""

import asyncio
import time

import numpy as np

from benchmarks.chip import generator, reference


async def pause(clients, op):
    op.t0 = time.perf_counter()
    await asyncio.sleep(0.001)
    op.t1 = time.perf_counter()


class Source:
    VERBS = {"pause": pause}

    def __init__(self, mix, model, seed):
        self.mix, self.seed = mix, seed
        rng = np.random.default_rng([int(seed), 7])
        self.freq = rng.uniform(200.0, 2000.0, mix["clients"])

    def stream(self, client, start, n):
        t = np.arange(start, start + n) / 16000.0
        x = 0.5 * np.sin(2 * np.pi * self.freq[client] * t)
        return x.astype(np.float32)[:, None]

    def push_input(self, op):
        return self.stream(op.client, op.pos, self.mix["chunk"])

    async def setup(self, clients, params, bn):
        m = self.mix
        n = m["tenants"] * m["ways"] * m["shots"]
        x = np.stack([self.stream(i % m["clients"], 0, m["shot_samples"])
                      for i in range(n)])
        emb = np.asarray(reference.embed_last(params, bn, x))
        return await generator.enroll_embedded(
            clients, emb.reshape(m["tenants"], m["ways"], m["shots"], -1))
'''


def _digests(root):
    return {p.relative_to(root): hashlib.sha256(p.read_bytes()).hexdigest()
            for p in root.rglob("*") if p.is_file()}


def test_new_cell_needs_no_edit(tmp_path):
    root = chip_tiny.make(tmp_path)
    before = _digests(root)
    bench = json.loads((root / "BENCHMARK.json").read_text())
    d = root / BENCH
    cfg = json.loads((d / "configs/chameleon-tcn-audio.json").read_text())
    cfg["name"] = "extra-tcn"
    cfg["model"]["tcn_channels"] = [8, 8]
    (d / "configs/extra-tcn.json").write_text(json.dumps(cfg))
    mix = json.loads((d / "traffic/stream-10ms.json").read_text())
    mix.update(slots=4, clients=4, chunk=8, inputs="extra_tones",
               loop=["push", "pause"])
    (d / "traffic/extra-stream.json").write_text(json.dumps(mix))
    (d / "sources/extra_tones.py").write_text(EXTRA_SOURCE)
    (d / "layer_metrics/extra_dispatches.stream.py").write_text(EXTRA_METRIC)
    shutil.copy(d / "limits/audio-stream.json", d / "limits/extra-cell.json")
    bench["configs"].append({
        "name": "extra-tcn", "source": "https://arxiv.org/abs/2505.24852",
        "file": "benchmarks/chip/configs/extra-tcn.json", "reduced": [],
        "why": "a test"})
    bench["workloads"].append({
        "name": "extra-cell", "config": "extra-tcn",
        "traffic": "extra-stream", "chips": 1, "why": "a test"})
    for m in bench["end_to_end"]:
        if "workloads" in m and "audio-stream" in m["workloads"]:
            m["workloads"].append("extra-cell")
    bench["per_layer"].append({
        "name": "extra_dispatches.stream", "unit": "dispatches",
        "better": "lower", "source": "program_span", "layer": "a test",
        "moves": "rt_streams", "workloads": ["extra-cell"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))

    after = _digests(root)
    changed = {p for p in before if after[p] != before[p]}
    assert changed == {harness.Path("BENCHMARK.json")}

    p = harness.plan("extra-cell", root)
    assert p.config["model"]["tcn_channels"] == [8, 8]
    assert p.mix["chunk"] == 8
    assert harness.source_module(p).Source.VERBS.keys() == {"pause"}
    assert "extra_dispatches.stream" in harness.readers(p)
    assert "extra_dispatches.stream" not in harness.readers(
        harness.plan("audio-stream", root))

    rc, res = chip_tiny.run(root, "extra-cell")
    assert rc == 0 and res["correct"], res
    assert res["failed"] == 0 and res["attempted"] >= 8
    assert {"rt_streams", "result_p95_ms", "setup_s"} <= set(res["metrics"])
    rc, res = chip_tiny.run(root, "extra-cell", trace=1)
    assert rc == 0 and res["correct"], res
    assert res["metrics"]["extra_dispatches.stream"]["value"] >= 1
    assert res["metrics"]["extra_dispatches.stream"]["unit"] == "dispatches"
    assert res["device"]["busy_s"] > 0 and res["device"]["window_s"] > 0
    assert list(res)[-1] == "compared"


MESH_RUN = """
import json, pathlib, sys
import jax
import chip_tiny

def spans(svc):
    devs = {s.device for leaf in jax.tree.leaves(svc.states)
            for s in leaf.addressable_shards}
    assert len(devs) == 4, devs
    return svc

rc, res = chip_tiny.run(pathlib.Path(sys.argv[1]), "extra-mesh",
                        service_hook=spans)
print(json.dumps(res))
sys.exit(rc)
"""


def test_sharded_cell_needs_no_edit(tmp_path):
    """A cell over four chips, its slots sharded over a (4, 1) mesh, is a
    mix and an entry too; four host devices stand in for the chips."""
    root = chip_tiny.make(tmp_path)
    bench = json.loads((root / "BENCHMARK.json").read_text())
    d = root / BENCH
    mix = json.loads((d / "traffic/stream-10ms.json").read_text())
    mix.update(mesh=[4, 1])
    (d / "traffic/extra-sharded.json").write_text(json.dumps(mix))
    shutil.copy(d / "limits/audio-stream.json", d / "limits/extra-mesh.json")
    bench["workloads"].append({
        "name": "extra-mesh", "config": "chameleon-tcn-audio",
        "traffic": "extra-sharded", "chips": 4, "why": "a test"})
    for m in bench["end_to_end"]:
        if "workloads" in m and "audio-stream" in m["workloads"]:
            m["workloads"].append("extra-mesh")
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.pathsep.join(
                   [str(ROOT / "src"), str(ROOT), str(ROOT / BENCH / "tests")]))
    out = subprocess.run([sys.executable, "-c", MESH_RUN, str(root)],
                         env=env, capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    res = json.loads(out.stdout.strip().splitlines()[-1])
    assert res["correct"], res["compared"]
    assert res["device"]["count"] == 4 and res["failed"] == 0


def _command(cwd, env=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH="", **(env or {}))
    return subprocess.run(
        [sys.executable, "benchmarks/chip/run.py", "--workload",
         "audio-stream", "--seed", "3000000001", "--seconds", "1",
         "--trace", "0"], cwd=cwd, env=env, capture_output=True, text=True,
        timeout=300)


def _no_result(out):
    return not any(line.lstrip().startswith("{") for line in
                   out.stdout.splitlines())


def test_command_fails_off_a_tpu():
    out = _command(ROOT)
    assert out.returncode != 0
    assert _no_result(out)
    assert "no TPU" in out.stderr


def test_command_fails_with_only_the_benchmark_files(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / BENCH, tmp_path / BENCH,
                    ignore=shutil.ignore_patterns("out", "__pycache__"))
    out = _command(tmp_path)
    assert out.returncode != 0
    assert _no_result(out)
