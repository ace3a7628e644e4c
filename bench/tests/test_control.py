"""The control at a size the CPU runs: the reference computed one
precision lower, in the program's place, reads past the limit that the
program's sound runs stay under."""
import time

from bench import harness as H
from bench.tests.tiny_root import CELL, make_root


def test_control_fails_the_limit(tmp_path):
    res = H.run_cell(CELL, 11, 3.0, False, time.perf_counter(),
                     require_chip=False, root=make_root(tmp_path),
                     control=True)
    assert res["correct"], res["checks"]
    assert res["control_correct"] is False
    failed = [k for k, v in res["checks"].items() if k in
              res["control_gaps"] and res["control_gaps"][k] > v["limit"]]
    assert failed
    for k in failed:
        assert res["control_gaps"][k] >= 3 * res["program_gaps"][k]
