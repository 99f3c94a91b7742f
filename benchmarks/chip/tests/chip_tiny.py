"""A tiny copy of the benchmark for the CPU: the committed ``BENCHMARK.json``
and harness files, with the configurations and traffic mixes shrunk so
that one run of a cell takes seconds.  The limits stay as committed."""

from __future__ import annotations

import io
import json
import shutil
import sys
from contextlib import redirect_stdout
from pathlib import Path

ROOT = Path(__file__).resolve().parents[3]
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)

BENCH = Path("benchmarks") / "chip"

TINY_MODEL = {
    "chameleon-tcn-audio": {"tcn_channels": [8, 8, 8, 8], "embed_dim": 16,
                            "n_layers": 8},
    "chameleon-tcn": {"tcn_channels": [8, 8, 8], "embed_dim": 16,
                      "n_layers": 6},
}
TINY_MIX = {
    "keyword_audio": {"slots": 8, "clients": 8, "tenants": 2, "ways": 3,
                      "shot_samples": 480, "chunk": 16, "full_every": 4,
                      "ref_block": 4},
    "glyphs": {"slots": 4, "clients": 2, "tenants": 2, "ways": 20,
               "shots": 3, "prefill_shots": 2, "pool": 8, "full_every": 2,
               "ref_block": 16},
}


def _update(path: Path, **kw) -> None:
    data = json.loads(path.read_text())
    (data["model"] if "model" in data else data).update(kw)
    path.write_text(json.dumps(data, indent=1))


def make(dst: Path) -> Path:
    """Build the tiny copy under ``dst``; returns ``dst``."""
    shutil.copytree(ROOT / BENCH, dst / BENCH, ignore=shutil.ignore_patterns(
        "out", "tests", "__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", dst)
    bench = json.loads((dst / "BENCHMARK.json").read_text())
    for c in bench["configs"]:
        _update(dst / c["file"], **TINY_MODEL[c["name"]])
    for w in bench["workloads"]:
        mix = dst / BENCH / "traffic" / f"{w['traffic']}.json"
        _update(mix, **TINY_MIX[json.loads(mix.read_text())["inputs"]])
    return dst


def run(root: Path, workload: str, *, seed: int = 4_000_000_007,
        seconds: float = 1.0, trace: int = 0, service_hook=None):
    """One run of ``workload`` in the tiny copy on the CPU: (exit code,
    the result line as a dict or None)."""
    from benchmarks.chip import harness
    buf = io.StringIO()
    with redirect_stdout(buf):
        rc = harness.main(["--workload", workload, "--seed", str(seed),
                           "--seconds", str(seconds), "--trace", str(trace)],
                          root=root, require_tpu=False,
                          service_hook=service_hook)
    lines = buf.getvalue().strip().splitlines()
    return rc, (json.loads(lines[-1]) if lines else None)
