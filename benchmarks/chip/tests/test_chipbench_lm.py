"""A language-model cell is added as new files alone: a configuration
naming the ``lm_sessions`` service, a mix of the ``prompts`` source, a
check, its limits and a per-layer reader.  Here at the CPU size of the
DeepSeek-V2-Lite preset (``.smoke()``: latent attention and routed and
shared experts on the path), with a check that compares only failures."""

import asyncio
import dataclasses
import hashlib
import json
import time

import chip_tiny
import numpy as np
import pytest
from chip_tiny import BENCH

from benchmarks.chip import harness

CELL, CONFIG, MIX = "lm-tiny", "dsv2-lite-smoke", "lm-tiny-chat"

CHECK = '''"""Failed requests only (a test's stand-in for a comparison with a
reference)."""


def compare(run):
    return {"failed": float(sum(not op.ok for ops in run.ops for op in ops))}
'''

READER = '''"""Tokens the window's pushes returned, and the samples it read (a
test of what the harness gives an LM cell's readers)."""


def read(ctx):
    if not ctx.tokens:
        return None
    return float(ctx.tokens), {"samples": ctx.samples, "ways": ctx.n_ways}
'''

# The entries the first LM cell brings into BENCHMARK.json with its name
# (a metric that names no cell is refused), at the probe's bounds.
LM_METRICS = [
    {"name": "ttft_p95_ms", "unit": "ms", "better": "lower", "bound": 0.25,
     "source": "host_clock"},
    {"name": "tokens_per_s", "unit": "tokens/s", "better": "higher",
     "bound": 0.08, "source": "host_clock"},
]

TINY_MIX = {"inputs": "prompts", "check": "lm_failures", "loop": ["chat"],
            **chip_tiny.tiny("inputs", "prompts")}


def _digests(root):
    return {p.relative_to(root): hashlib.sha256(p.read_bytes()).hexdigest()
            for p in root.rglob("*") if p.is_file()}


def smoke_model() -> dict:
    from repro.configs import get_config
    cfg = get_config("deepseek-v2-lite-16b").smoke()
    return {k: list(v) if isinstance(v, tuple) else v
            for k, v in dataclasses.asdict(cfg).items()}


def add_lm_cell(root, cell=CELL, mix=MIX, extra=None):
    """Write the cell's files and entries into the tiny copy at ``root``
    (a second cell adds its mix, with ``extra`` keys, and its entries)."""
    d = root / BENCH
    bench = json.loads((root / "BENCHMARK.json").read_text())
    if cell != CELL:
        (d / "traffic" / f"{mix}.json").write_text(
            json.dumps({**TINY_MIX, **(extra or {})}))
        (d / "limits" / f"{cell}.json").write_text(json.dumps({"failed": 0}))
        bench["workloads"].append({"name": cell, "config": CONFIG,
                                   "traffic": mix, "chips": 1, "why": "a test"})
        for m in bench["end_to_end"] + bench["per_layer"]:
            if CELL in m.get("workloads", ()):
                m["workloads"].append(cell)
        (root / "BENCHMARK.json").write_text(json.dumps(bench))
        return
    (d / "configs" / f"{CONFIG}.json").write_text(json.dumps({
        "name": CONFIG, "preset": "deepseek-v2-lite-16b",
        "service": "lm_sessions", "model": smoke_model(),
        "serving": {"paged": True, "block_len": 4, "prefill_chunk": 8,
                    "t_chunk": 4, "prefix_cache": True}}))
    (d / "traffic" / f"{MIX}.json").write_text(json.dumps(TINY_MIX))
    (d / "checks" / "lm_failures.py").write_text(CHECK)
    (d / "limits" / f"{CELL}.json").write_text(json.dumps({"failed": 0}))
    (d / "layer_metrics" / "tokens.lm.py").write_text(READER)
    bench["configs"].append({
        "name": CONFIG, "source": "https://arxiv.org/abs/2405.04434",
        "file": f"benchmarks/chip/configs/{CONFIG}.json", "reduced": [],
        "why": "a test"})
    bench["workloads"].append({"name": CELL, "config": CONFIG,
                               "traffic": MIX, "chips": 1, "why": "a test"})
    bench["end_to_end"] += [dict(m, workloads=[CELL]) for m in LM_METRICS]
    bench["per_layer"].append({
        "name": "tokens.lm", "unit": "tokens", "better": "higher",
        "source": "program_counter", "layer": "a test",
        "moves": "tokens_per_s", "workloads": [CELL]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return chip_tiny.make(tmp_path_factory.mktemp("tiny"))


def test_lm_cell_changes_no_committed_file(tmp_path):
    root = chip_tiny.make(tmp_path)
    before = _digests(root)
    add_lm_cell(root)
    after = _digests(root)
    assert {p for p in before if after[p] != before[p]} \
        == {harness.Path("BENCHMARK.json")}
    new = {str(p) for p in after if p not in before}
    assert new == {f"benchmarks/chip/{f}" for f in (
        f"configs/{CONFIG}.json", f"traffic/{MIX}.json",
        "checks/lm_failures.py", f"limits/{CELL}.json",
        "layer_metrics/tokens.lm.py")}
    p = harness.plan(CELL, root)
    assert harness.service_name(p) == "lm_sessions"
    assert {m["name"] for m in p.end_to_end} == {
        "result_p95_ms", "setup_s", "ttft_p95_ms", "tokens_per_s"}


def test_committed_cells_resolve_to_the_tcn_service():
    for w in ("audio-stream", "cl-250way"):
        p = harness.plan(w)
        assert "service" not in p.config
        assert harness.service_name(p) == "tcn_stream"
        assert harness.service_module(p).build.__module__.endswith(
            "tcn_stream")
        names = {m["name"] for m in p.end_to_end}
        assert not names & {"ttft_p95_ms", "tokens_per_s"}


OPEN_CELL, OPEN_MIX = "lm-tiny-open", "lm-tiny-chat-open"


@pytest.mark.parametrize("trace", [0, 1])
def test_lm_cell_runs_through_the_harness(root, trace):
    _run_lm_cell(root, CELL, trace)


def test_lm_open_loop_cell_runs_through_the_harness(root):
    _run_lm_cell(root, OPEN_CELL, 0)


def _run_lm_cell(root, cell, trace):
    if not (root / BENCH / "configs" / f"{CONFIG}.json").exists():
        add_lm_cell(root)
        add_lm_cell(root, OPEN_CELL, OPEN_MIX, {"rate": 40.0})
    rc, res = chip_tiny.run(root, cell, trace=trace)
    assert rc == 0 and res["correct"], res
    assert res["failed"] == 0 and res["attempted"] > 0
    m = res["metrics"]
    if not trace:
        assert set(m) == {"ttft_p95_ms", "tokens_per_s", "result_p95_ms",
                          "setup_s"}, m
        assert m["ttft_p95_ms"]["unit"] == "ms"
        assert m["tokens_per_s"]["unit"] == "tokens/s"
        assert all(v["value"] > 0 for v in m.values())
    else:
        # the readers see the window's tokens, and no samples or ways
        t = m["tokens.lm"]
        assert t["value"] >= TINY_MIX["answer_min"] and t["value"] % 1 == 0
        assert t["samples"] is None and t["ways"] is None


def _source(seed, **kw):
    from benchmarks.chip.sources import prompts
    mix = {**TINY_MIX, "length_pool": 8, "turn_max": 18, "answer_max": 4,
           **kw}
    return prompts.Source(mix, {"vocab_size": 97}, seed)


def test_prompts_same_seed_same_requests():
    a, b, c = _source(2**33 + 5), _source(2**33 + 5), _source(2**33 + 6)
    for k in (0, 1, 7, 500):
        pa = a.prompt(k)
        assert np.array_equal(pa, b.prompt(k))
        assert pa.dtype == np.int32 and pa.min() >= 0 and pa.max() < 97
        assert np.array_equal(pa[:10], a.prefix[k % 2])
        assert a.tenant(k) == k % 2
        assert a.answer_len(k) == b.answer_len(k)
    assert not np.array_equal(a.prompt(1), c.prompt(1))
    # each cycle of 8 requests deals the whole pool, in an order the seed
    # draws: the seed changes the order, not the work
    for kind in ("turn", "answer"):
        deal = lambda s, cycle: [getattr(s, f"{kind}_len")(cycle * 8 + i)
                                 for i in range(8)]
        pool = sorted(a.pool[kind].tolist())
        for cycle in (0, 1, 9):
            assert sorted(deal(a, cycle)) == sorted(deal(c, cycle)) == pool
        assert any(deal(a, cy) != deal(c, cy) for cy in range(4))
    assert a.pool["turn"].min() >= 2 and a.pool["turn"].max() <= 18
    assert a.pool["answer"].min() >= 2 and a.pool["answer"].max() <= 4


def test_prompts_open_loop_arrivals():
    """Arrivals start at 0, rise, and each cycle spans the same time on
    every seed: the seed orders the gaps."""
    a, c = _source(2**35 + 1, rate=20.0), _source(2**35 + 2, rate=20.0)
    t = [a.arrival(k) for k in range(40)]
    assert t[0] == 0.0 and all(y > x for x, y in zip(t, t[1:]))
    span = a.pool["gap"].sum()
    assert span == pytest.approx(8 / 20.0, rel=0.05)
    for cycle in (1, 4):
        assert a.arrival(8 * cycle) == pytest.approx(cycle * span)
        assert c.arrival(8 * cycle) == pytest.approx(cycle * span)
    assert t[1:8] != [c.arrival(k) for k in range(1, 8)]
    assert "gap" not in _source(3).pool


def test_prompts_refuse_a_mix_over_seq_cap():
    from benchmarks.chip.sources import prompts
    with pytest.raises(ValueError, match="seq_cap"):
        prompts.Source({**TINY_MIX, "turn_max": 17}, {"vocab_size": 97}, 1)


class _Plane:
    """Records what a client sends; every push returns one token."""

    def __init__(self):
        self.sent, self.n = [], 0

    async def open_session(self, prompt, tenant=None):
        self.n += 1
        self.sent.append(("open", self.n, np.asarray(prompt), tenant,
                          time.perf_counter()))
        return self.n

    async def push(self, sid, budget):
        self.sent.append(("push", sid, budget))
        return [7]

    async def close(self, sid):
        self.sent.append(("close", sid))


def _drive(src, body):
    from benchmarks.chip import generator
    plane = _Plane()
    clients = generator.Clients(plane, src, src.mix)

    async def go():
        await clients.open_all()
        await src.setup(clients, None, None)
        await clients.warm_up()
        await body(clients)

    asyncio.run(go())
    return clients, plane


def test_chat_sends_one_request_an_op():
    """A closed loop: after set-up, a client's ops are the pushes of its
    set-up request's answer and its close, then for each request it takes
    next (requests 4, 5, ... here, one client driven) its open (with the
    request's prompt, and its first token), its pushes and its close."""
    src = _source(2**40 + 3)

    async def body(clients):
        for _ in range(40):
            await clients.one_loop(1, window=True)

    clients, plane = _drive(src, body)
    ops = [op for op in clients.log[1] if op.in_window]
    assert all(op.ok for op in ops) and len(ops) == 40
    want = ["push"] * (src.answer_len(1) - 2) + ["close"]
    for k in range(4, 40):
        want += ["open_prompt"] + ["push"] * (src.answer_len(k) - 1) \
            + ["close"]
    assert [op.kind for op in ops] == want[:40]
    opens = [op for op in ops if op.kind == "open_prompt"]
    assert [op.result["request"] for op in opens] \
        == list(range(4, 4 + len(opens)))
    assert all(op.result["tokens"] == [7] and op.result["ttft_s"] > 0
               for op in opens)
    sent = [(p, t) for kind, _, p, t, _ in
            (e for e in plane.sent if e[0] == "open")]
    assert np.array_equal(sent[1][0], src.prompt(1))
    for op, (p, t) in zip(opens, sent[-len(opens):]):
        k = op.result["request"]
        assert np.array_equal(p, src.prompt(k)) and t == src.tenant(k)


def test_open_loop_sends_on_schedule():
    """An open loop: nothing is sent before the window; request ``k`` is
    opened no earlier than it is due, in order, its time to first token
    counted from then; none due after the deadline is sent."""
    src = _source(2**41 + 9, rate=50.0)

    async def body(clients):
        clients.warm_sent = len(clients.plane.sent)
        clients.window = await clients.run(0.5)

    clients, plane = _drive(src, body)
    start, _ = clients.window
    # set-up opened and closed its own sessions; the warm-up sent nothing
    setup = plane.sent[:clients.warm_sent]
    assert sum(e[0] == "open" for e in setup) == 4 + 2
    assert sum(e[0] == "close" for e in setup) == 4 + 2
    assert all(op.kind == "wait" for ops in clients.log for op in ops
               if not op.in_window)
    opens = sorted((op for op in clients.window_ops("open_prompt")),
                   key=lambda op: op.result["request"])
    ks = [op.result["request"] for op in opens]
    assert ks == list(range(len(ks))) and len(ks) >= 4
    for op in opens:
        due = src.t0 + src.arrival(op.result["request"])
        assert op.t0 >= due - 1e-3
        assert op.result["ttft_s"] == pytest.approx(op.t1 - due, abs=1e-9)
        assert due < clients.deadline
    # the recording plane answers at once, so every request due in the
    # window was taken
    assert src.t0 + src.arrival(len(ks)) >= clients.deadline
    assert src.t0 - start < 0.05
