"""One run of one cell: set-up, the measured window, the trace, the check.

Everything that belongs to one configuration, traffic mix, per-layer
metric or check is a file found by its name in ``BENCHMARK.json``:

  configs/<config>.json        sizes, serving path and precision (the
                               configuration's ``file`` key)
  services/<service>.py        the system under test, named by the
                               configuration's ``"service"`` (absent:
                               ``tcn_stream``): ``arch_config(config)``,
                               ``weights(cfg, model, seed)`` -> (params,
                               what else the check reads), ``build(run,
                               cfg, tracer, registry)`` -> the service
  traffic/<traffic>.json       the mix, read by ``generator.py``
  sources/<inputs>.py          the kind of inputs a mix names: ``Source``
                               makes them from the seed, ``setup`` fills
                               the banks or caches the cell starts with
  layer_metrics/<metric>.py    ``read(ctx) -> float | None`` (or a value
                               and extra keys for the metric, such as
                               the roofline's ``bound``)
  checks/<check>.py            the comparison with ``reference.py``
  limits/<workload>.json       the limit of each number compared

The end-to-end metrics are measured with tracing off; ``--trace 1`` runs
the same window under the profiler and reports the per-layer metrics.
"""

from __future__ import annotations

import asyncio
import gc
import importlib.util
import json
import shutil
import sys
import time
from dataclasses import dataclass, field
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
BENCH = Path("benchmarks") / "chip"
OUT = BENCH / "out"
WINDOW = "bench_window"
CONTROL = "float8_e4m3fn"     # the precision below bfloat16 (see checks)

# JAX's events of building a program: a trace of a new jaxpr, a program
# loaded from the persistent compile cache, a compile.  Programs loaded or
# compiled inside the window are a number compared, with the limit 0: the
# warm-up has to build every program the window runs.
JAX_EVENTS = {"/jax/core/compile/jaxpr_trace_duration": "traces",
              "/jax/compilation_cache/cache_retrieval_time_sec": "cache_loads",
              "/jax/core/compile/backend_compile_duration": "compiles"}
_EVENTS: list = []      # (perf_counter, kind) of each such event
_LISTENING = False


def _listen_for_builds() -> None:
    """Time-stamp JAX's program-building events from here on (one listener
    for the process, however many runs it makes)."""
    global _LISTENING
    if not _LISTENING:
        import jax
        jax.monitoring.register_event_duration_secs_listener(
            lambda name, _s, **_kw: _EVENTS.append(
                (time.perf_counter(), JAX_EVENTS[name]))
            if name in JAX_EVENTS else None)
        _LISTENING = True


class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


# ---------------------------------------------------------------------------
# Finding a cell's files by name
# ---------------------------------------------------------------------------

@dataclass
class Plan:
    root: Path
    workload: dict
    config: dict
    mix: dict
    limits: dict
    end_to_end: list
    per_layer: list


def _json(path: Path) -> dict:
    return json.loads(path.read_text())


def load_module(path: Path):
    """Import a file by path (metric names hold dots)."""
    spec = importlib.util.spec_from_file_location(
        "chipbench_" + path.stem.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def applies(metric: dict, workload: str) -> bool:
    return "workloads" not in metric or workload in metric["workloads"]


def plan(workload: str, root: Path = ROOT) -> Plan:
    """Everything a run of ``workload`` needs, read from ``root``."""
    bench = _json(root / "BENCHMARK.json")
    wl = {w["name"]: w for w in bench["workloads"]}
    if workload not in wl:
        raise KeyError(f"no workload {workload!r}; have {sorted(wl)}")
    w = wl[workload]
    cfg = {c["name"]: c for c in bench["configs"]}[w["config"]]
    config = _json(root / cfg["file"])
    return Plan(root=root, workload=w, config=config,
                mix=_json(root / BENCH / "traffic" / f"{w['traffic']}.json"),
                limits=_json(root / BENCH / "limits" / f"{workload}.json"),
                end_to_end=[m for m in bench["end_to_end"]
                            if applies(m, workload)],
                per_layer=[m for m in bench["per_layer"]
                           if applies(m, workload)])


def readers(p: Plan) -> dict:
    return {m["name"]: load_module(
        p.root / BENCH / "layer_metrics" / f"{m['name']}.py")
        for m in p.per_layer}


def check_module(p: Plan):
    return load_module(p.root / BENCH / "checks" / f"{p.mix['check']}.py")


def source_module(p: Plan):
    return load_module(p.root / BENCH / "sources" / f"{p.mix['inputs']}.py")


DEFAULT_SERVICE = "tcn_stream"


def service_name(p: Plan) -> str:
    return p.config.get("service", DEFAULT_SERVICE)


def service_module(p: Plan):
    return load_module(p.root / BENCH / "services" / f"{service_name(p)}.py")


# ---------------------------------------------------------------------------
# Spans: the program's tracer, mirrored into the profiler's trace
# ---------------------------------------------------------------------------

def bench_tracer(enabled: bool):
    """A tracer for the program that records its spans as usual and also
    writes each into the profiler's trace (``TraceAnnotation``), so that
    device idle gaps can be named by what the host was doing."""
    import jax
    from repro.obs.trace import Tracer

    class _Both:
        __slots__ = ("span", "ann")

        def __init__(self, span, name):
            self.span = span
            self.ann = jax.profiler.TraceAnnotation(name)

        def __enter__(self):
            self.ann.__enter__()
            self.span.__enter__()
            return self

        def __exit__(self, *exc):
            self.span.__exit__(*exc)
            self.ann.__exit__(*exc)
            return False

    class BenchTracer(Tracer):
        def span(self, name, cat="", **args):
            inner = super().span(name, cat, **args)
            return _Both(inner, name) if self.enabled else inner

    return BenchTracer(enabled=enabled, capacity=1 << 20)


# ---------------------------------------------------------------------------
# The run
# ---------------------------------------------------------------------------

@dataclass
class Run:
    plan: Plan
    seed: int
    seconds: float
    trace: bool
    t_process: float
    devices: list = field(default_factory=list)
    model: dict = field(default_factory=dict)
    params: object = None
    bn: object = None
    source: object = None
    bank: dict = field(default_factory=dict)
    clients: object = None
    setup_s: float = 0.0
    start: float = 0.0
    end: float = 0.0
    counters: tuple = ({}, {})
    spans: list = field(default_factory=list)
    trace_summary: dict | None = None
    memory_peak_bytes: int = 0
    window_events: dict = field(default_factory=dict)
    phases: dict = field(default_factory=dict)

    def phase(self, name: str) -> None:
        """Mark the end of a set-up phase (seconds since process start)."""
        self.phases[name] = round(time.perf_counter() - self.t_process, 3)

    @property
    def ops(self):
        return self.clients.log


def check_devices(chips: int, require_tpu: bool = True) -> list:
    import jax
    devices = jax.devices()
    if require_tpu and devices[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX found {devices[0].platform}")
    if len(devices) < chips:
        raise NoChip(f"the cell asks for {chips} chips, JAX found "
                     f"{len(devices)}")
    return devices


def execute(run: Run, *, require_tpu: bool = True, service_hook=None) -> Run:
    """Set up, measure the window, free the program's state.  Fills
    ``run`` with the ops log, counters, spans and (traced) trace summary.
    ``service_hook(svc)`` may wrap the service (the harness's tests use it
    to plant faults)."""
    import jax
    from repro.launch.cache import enable_compile_cache
    from repro.obs.metrics import MetricsRegistry
    from repro.serving import ServingPlane

    from benchmarks.chip import generator, trace_reduce

    p = run.plan
    run.devices = check_devices(p.workload["chips"], require_tpu)
    run.phase("devices")
    if require_tpu:
        enable_compile_cache()
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    run.model = p.config["model"]
    service = service_module(p)
    cfg = service.arch_config(p.config)
    run.params, run.bn = service.weights(cfg, run.model, run.seed)
    run.source = source_module(p).Source(p.mix, run.model, run.seed)
    run.phase("weights_and_inputs")
    registry = MetricsRegistry()
    tracer = bench_tracer(run.trace)
    svc = service.build(run, cfg, tracer, registry)
    if service_hook is not None:
        svc = service_hook(svc)
    run.phase("service")
    trace_dir = run.plan.root / OUT / "trace"

    _listen_for_builds()

    async def session():
        async with ServingPlane(svc, metrics=registry, tracer=tracer) as plane:
            clients = generator.Clients(plane, run.source, p.mix)
            run.clients = clients
            await clients.open_all()
            run.bank = await run.source.setup(clients, run.params, run.bn)
            run.phase("enroll")
            await clients.warm_up()
            # set-up leaves a heap of young objects behind; collected here,
            # they would take a full collection of ~0.1 s inside the window
            gc.collect()
            run.phase("warm_up")
            c0 = registry.snapshot()
            tracer.clear()
            run.setup_s = time.perf_counter() - run.t_process
            if run.trace:
                shutil.rmtree(trace_dir, ignore_errors=True)
                opts = jax.profiler.ProfileOptions()
                opts.python_tracer_level = 0
                jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
                with jax.profiler.TraceAnnotation(WINDOW):
                    run.start, run.end = await clients.run(run.seconds)
                jax.profiler.stop_trace()
            else:
                run.start, run.end = await clients.run(run.seconds)
            run.counters = (c0, registry.snapshot())
            run.spans = tracer.events()
            clients.plane = None     # the service is freed after the window

    asyncio.run(session())
    run.window_events = {k: sum(run.start <= t <= run.end and kind == k
                                for t, kind in _EVENTS)
                         for k in JAX_EVENTS.values()}
    run.memory_peak_bytes = max(
        (d.memory_stats() or {}).get("peak_bytes_in_use", 0)
        for d in run.devices[:p.workload["chips"]])
    del svc
    gc.collect()
    if run.trace:
        pb = sorted(trace_dir.rglob("*.xplane.pb"))
        if not pb:
            raise RuntimeError(f"the profiler wrote no trace under {trace_dir}")
        kw = {} if run.devices[0].platform == "tpu" else CPU_TRACE
        run.trace_summary = trace_reduce.reduce(
            str(pb[-1]), span_names={e["name"] for e in run.spans}, **kw)
    return run


# the CPU stands in for the device in the harness's own tests
CPU_TRACE = {"device_plane": r"^/host:CPU$",
             "device_line": r"^tf_XLA(PjRt)?Cpu"}


# ---------------------------------------------------------------------------
# Metrics
# ---------------------------------------------------------------------------

def _pct(xs, q):
    import numpy as np
    return float(np.percentile(np.asarray(xs, np.float64), q))


def generated_tokens(ops) -> int | None:
    """Tokens the ops returned, where they return tokens (an LM's)."""
    toks = [len(op.result["tokens"]) for op in ops if "tokens" in op.result]
    return sum(toks) if toks else None


def end_to_end(run: Run) -> dict:
    """The cell's end-to-end metrics, over all the work of the window.
    Each is left out where the window's ops carry nothing it reads: the
    samples of a push (``n``), the tokens an op returned (``tokens``), the
    delay of a session's first token (``ttft_s``)."""
    pushes = [op for op in run.clients.window_ops("push") if op.ok]
    enrolls = [op for op in run.clients.window_ops("enroll") if op.ok]
    done = [op for op in run.clients.window_ops() if op.ok]
    firsts = [op.result["ttft_s"] for op in done if "ttft_s" in op.result]
    tokens = generated_tokens(done)
    wall = run.end - run.start
    sr = run.plan.config.get("sample_rate")
    values = {
        "setup_s": run.setup_s,
        "rt_streams": (sum(op.result["n"] for op in pushes) / sr / wall
                       if pushes and sr else None),
        "result_p95_ms": (_pct([op.t1 - op.t0 for op in pushes], 95) * 1e3
                          if pushes else None),
        "enroll_p95_ms": (_pct([op.t1 - op.t0 for op in enrolls], 95) * 1e3
                          if enrolls else None),
        "ttft_p95_ms": _pct(firsts, 95) * 1e3 if firsts else None,
        "tokens_per_s": tokens / wall if tokens else None,
    }
    return {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
            for m in run.plan.end_to_end if values.get(m["name"]) is not None}


@dataclass
class LayerContext:
    """What a per-layer reader may read (see ``layer_metrics/``)."""
    model: dict
    mix: dict
    chips: int
    peaks: dict
    window_s: float           # host clock, start of the window to its last op
    spans: list               # the program's spans in the window
    counters: tuple           # registry snapshots at the window's ends
    trace: dict | None        # trace_reduce.reduce() of the window
    samples: int | None       # samples in every push result of the window
    n_ways: int | None        # the mix's ways (None: the mix has none)
    tokens: int | None        # tokens the window's ops returned

    def spans_named(self, name: str) -> list:
        return [e for e in self.spans if e.get("name") == name
                and e.get("ph") == "X"]

    def hist_delta(self, name: str) -> tuple[float, float]:
        """(count, sum) a histogram gained over the window, all labels."""
        def tot(snap):
            rows = snap.get(name, [])
            return (sum(r.get("count", 0) for r in rows),
                    sum(r.get("sum", 0.0) for r in rows))
        (c0, s0), (c1, s1) = tot(self.counters[0]), tot(self.counters[1])
        return c1 - c0, s1 - s0


def per_layer(run: Run) -> dict:
    from benchmarks.chip import flops
    p = run.plan
    pushes = [op for op in run.clients.window_ops("push") if op.ok]
    sized = [op.result["n"] for op in pushes if "n" in op.result]
    ctx = LayerContext(
        model=run.model, mix=p.mix, chips=p.workload["chips"],
        peaks=flops.peaks(run.devices[0].device_kind)
        if run.devices[0].platform == "tpu" else None,
        window_s=run.end - run.start, spans=run.spans,
        counters=run.counters, trace=run.trace_summary,
        samples=sum(sized) if sized else None, n_ways=p.mix.get("ways"),
        tokens=generated_tokens(
            op for op in run.clients.window_ops() if op.ok))
    out = {}
    units = {m["name"]: m["unit"] for m in p.per_layer}
    for name, mod in readers(p).items():
        v = mod.read(ctx)
        extra = {}
        if isinstance(v, tuple):     # (value, {"bound": ...}) and the like
            v, extra = v
        if v is not None:
            out[name] = {"value": float(v), "unit": units[name], **extra}
    return out


# ---------------------------------------------------------------------------
# Correctness
# ---------------------------------------------------------------------------

def compare(run: Run) -> dict:
    """{number: {"value", "limit"}} of the cell's check against the
    reference, after the window."""
    numbers = check_module(run.plan).compare(run)
    out = {k: {"value": v, "limit": run.plan.limits[k]}
           for k, v in numbers.items()}
    ev = run.window_events
    out["window_compiles"] = {
        "value": float(ev.get("cache_loads", 0) + ev.get("compiles", 0)),
        "limit": 0}
    return out


def result(run: Run, compared: dict) -> dict:
    window = [op for op in run.clients.window_ops()]
    d = run.devices[0]
    device = {"platform": d.platform, "kind": d.device_kind,
              "count": len(run.devices),
              "memory_peak_bytes": int(run.memory_peak_bytes)}
    out = {"correct": all(c["value"] <= c["limit"]
                          for c in compared.values()),
           "attempted": len(window),
           "failed": sum(not op.ok for op in window)}
    if run.trace:
        out["metrics"] = per_layer(run)
        s = run.trace_summary
        device["busy_s"] = s["busy_s"]
        device["window_s"] = s["window_s"]
        out["breakdown"] = {"device_ops": s["device_ops"],
                            "idle_gaps": s["idle_gaps"]}
    else:
        out["metrics"] = end_to_end(run)
    out["device"] = device
    out["window_jax_events"] = run.window_events
    out["compared"] = compared
    return out


def main(argv=None, *, t_process: float | None = None, root: Path = ROOT,
         require_tpu: bool = True, service_hook=None) -> int:
    import argparse
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    run = Run(plan=plan(args.workload, root), seed=args.seed,
              seconds=args.seconds, trace=bool(args.trace),
              t_process=time.perf_counter() if t_process is None else t_process)
    try:
        execute(run, require_tpu=require_tpu, service_hook=service_hook)
    except NoChip as e:
        print(str(e), file=sys.stderr)
        return 2
    t0 = time.perf_counter()
    compared = compare(run)
    print(f"set-up phases (s from start): {run.phases}; check took "
          f"{time.perf_counter() - t0:.1f} s; in the window: "
          f"{run.window_events}", file=sys.stderr)
    out = result(run, compared)
    for k, c in compared.items():
        print(f"compared {k} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr, flush=True)
    print(json.dumps(out), flush=True)
    return 0
