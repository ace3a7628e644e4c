"""Sweep of offered load for an open-loop cell, on the chip: the highest
rate the system sustains, judged on the TTFT and inter-token tails and
on whether the queue grows through the window.

    python3 bench/sweep.py --workload <cell> --seconds <s> --seeds <n> ... --rates <r> ...

One process serves each rate on each seed in turn (set-up repeated, the
compiled programs shared) and prints one JSON line per run: the tails,
the mean queue wait of the first and the last fifth of the requests
(a queue that grows through the window is past the knee), and the share
of the window spent inside engine steps. The rate found sets
`rate_per_s` in the mix file once; benchmark runs never sweep.
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
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    a = ap.parse_args()
    for rate in a.rates:
        for seed in a.seeds:
            res = run_cell(a.workload, seed, a.seconds, False,
                           time.perf_counter(), rate=rate)
            print(json.dumps({"seed": seed, "correct": res["correct"],
                              "attempted": res["attempted"],
                              "failed": res["failed"], **res["sweep"]}),
                  flush=True)
