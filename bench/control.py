"""Readings that set a cell's `max_logit_gap` limit, on the chip.

    python3 bench/control.py --workload <cell> --seconds <s> --seeds <n> <n> ...

For each seed, in one process: one run of the cell (set-up, a window of
the cell's own traffic and load, drain), then on the run's check sample
both the program's readings (the widest and the mean gap, under the
float32 reference, between the best logit and a served token's logit)
and the control's (the same for the token that the reference, computed
one precision lower, puts first at each position), the control judged
by the harness's own checks and limits. Prints one JSON line per seed,
and exits 1 if the control came out correct on any seed. The
benchmark's own runs never run the control.
"""
import argparse
import json
import sys
import time
from pathlib import Path

if __name__ == "__main__":
    root = Path(__file__).resolve().parents[1]
    sys.path[:0] = [str(root), str(root / "src")]
    from bench.harness import run_cell
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    a = ap.parse_args()
    passed = []
    for seed in a.seeds:
        res = run_cell(a.workload, seed, a.seconds, False,
                       time.perf_counter(), control=True)
        print(json.dumps({"seed": seed, "correct": res["correct"],
                          "control_correct": res["control_correct"],
                          "program": res["program_gaps"],
                          "control": res["control_gaps"],
                          "checks": res["checks"]}), flush=True)
        if res["control_correct"]:
            passed.append(seed)
    if passed:
        print(f"control came out correct on seeds {passed}",
              file=sys.stderr)
        sys.exit(1)
