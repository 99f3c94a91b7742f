"""Readings that the limits in ``limits/<workload>.json`` are set from.

For each seed, in one process (so that set-up after the first seed is
served from the compile cache in memory): one run of the cell as the
benchmark runs it, the numbers its check compares for the program, and the
same numbers for the control, the reference at the precision below the one
the configuration states (``harness.CONTROL``) put in the program's place.
One JSON line per seed on standard output.

    python3 benchmarks/chip/calibrate.py --workload <name> \\
        --seeds <n>,<n>,... --seconds <s>

The benchmark's own runs never run this.
"""

import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from benchmarks.chip import harness  # noqa: E402


def main(argv=None, *, root: Path = ROOT, require_tpu: bool = True) -> int:
    import argparse
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    p = harness.plan(args.workload, root)
    check = harness.check_module(p)
    for seed in (int(s) for s in args.seeds.split(",")):
        run = harness.Run(plan=p, seed=seed, seconds=args.seconds,
                          trace=False, t_process=time.perf_counter())
        try:
            harness.execute(run, require_tpu=require_tpu)
        except harness.NoChip as e:
            print(str(e), file=sys.stderr)
            return 2
        program = check.compare(run)
        metrics = harness.end_to_end(run)
        check.fabricate(run, harness.CONTROL)
        control = check.compare(run)
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "program": program, "control": control,
                          "metrics": metrics,
                          "window_jax_events": run.window_events}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
