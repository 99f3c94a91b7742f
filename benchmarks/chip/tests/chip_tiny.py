"""A tiny copy of the benchmark for the CPU: the committed ``BENCHMARK.json``
and harness files, with the configurations and traffic mixes shrunk so
that one run of a cell takes seconds.  The limits stay as committed.

The CPU sizes are files found by name: ``tiny/config/<config>.json`` for
each configuration (merged into its ``model``) and
``tiny/inputs/<inputs>.json`` for each mix (merged into the mix, by the
kind of inputs it names).  A new cell brings its own."""

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
TINY = Path(__file__).resolve().parent / "tiny"


def tiny(kind: str, name: str) -> dict:
    """The CPU sizes of configuration or inputs ``name``."""
    return json.loads((TINY / kind / f"{name}.json").read_text())


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
        _update(dst / c["file"], **tiny("config", c["name"]))
    for w in bench["workloads"]:
        mix = dst / BENCH / "traffic" / f"{w['traffic']}.json"
        _update(mix, **tiny("inputs", json.loads(mix.read_text())["inputs"]))
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
