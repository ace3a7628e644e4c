"""A benchmark root at a size the CPU runs: the test configuration and
mix of `bench/tests/data` as one cell, with every per-layer metric."""
import json
import shutil
from pathlib import Path

DATA = Path(__file__).resolve().parent / "data"
BENCH = DATA.parents[1]
CELL = "tiny.tiny-mix"


def make_root(tmp: Path, mix: str = "tiny-mix",
              config: str = "tiny") -> Path:
    shutil.copytree(BENCH / "metrics", tmp / "bench" / "metrics",
                    ignore=shutil.ignore_patterns("__pycache__"))
    (tmp / "bench" / "configs").mkdir(parents=True)
    (tmp / "bench" / "traffic").mkdir(parents=True)
    shutil.copy(DATA / f"{config}.json",
                tmp / "bench" / "configs" / "tiny.json")
    shutil.copy(DATA / f"{mix}.json", tmp / "bench" / "traffic" / f"{mix}.json")
    real = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    bm = dict(real)
    bm["configs"] = [{"name": "tiny", "source": "test", "reduced": [],
                      "file": "bench/configs/tiny.json", "why": "test"}]
    bm["workloads"] = [{"name": CELL, "config": "tiny",
                        "traffic": mix, "chips": 1, "why": "test"}]
    bm["end_to_end"] = [{k: v for k, v in m.items() if k != "workloads"}
                        for m in real["end_to_end"]]
    bm["per_layer"] = [{k: v for k, v in m.items() if k != "workloads"}
                       for m in real["per_layer"]]
    (tmp / "BENCHMARK.json").write_text(json.dumps(bm))
    return tmp
