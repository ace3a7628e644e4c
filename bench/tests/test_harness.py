"""Whole runs of a cell at a size the CPU runs, with the harness's look
for a chip skipped: a sound run is correct; a run whose timed path is
broken underneath is not; a metric or a configuration is a file."""
import json
import os
import subprocess
import sys
import time

import jax.numpy as jnp
import pytest

from bench import harness as H
from bench.tests.tiny_root import CELL, make_root

SEED = 2**31 + 977


def _run(root, trace=False, seconds=3.0, **kw):
    return H.run_cell(CELL, SEED, seconds, trace, time.perf_counter(),
                      require_chip=False, root=root, **kw)


def test_sound_run_is_correct_and_reports_every_metric(tmp_path):
    root = make_root(tmp_path)
    # a per-layer metric added by a file alone
    (root / "bench" / "metrics" / "requests_seen.py").write_text(
        "def read(run):\n    return len(run.reqs)\n")
    bm = json.loads((root / "BENCHMARK.json").read_text())
    bm["per_layer"].append({"name": "requests_seen", "unit": "requests",
                            "better": "higher", "source": "host_clock",
                            "layer": "scheduler", "moves": "ttft_p95_s"})
    (root / "BENCHMARK.json").write_text(json.dumps(bm))
    res = _run(root, trace=True)
    assert res["correct"], res["checks"]
    assert res["failed"] == 0 and res["attempted"] == 12
    assert res["metrics"]["requests_seen"]["value"] == 12
    assert "step_mfu" in res["metrics"]
    assert list(res)[-1] == "checks"


@pytest.mark.parametrize("change", [{"norm": "layernorm"},
                                    {"attention_bias": True}],
                         ids=["layernorm", "attention_bias"])
def test_layout_the_reference_lacks_is_refused(tmp_path, change):
    root = make_root(tmp_path)
    path = root / "bench" / "configs" / "tiny.json"
    path.write_text(json.dumps({**json.loads(path.read_text()), **change}))
    with pytest.raises(ValueError, match="unsupported layout"):
        _run(root)


def test_closed_loop_keeps_its_clients_busy(tmp_path):
    res = _run(make_root(tmp_path, mix="tiny-closed"))
    assert res["correct"], res["checks"]
    assert res["failed"] == 0 and res["attempted"] > 3
    assert res["metrics"]["itl_p95_ms"]["value"] > 0


def _alter_tokens(monkeypatch):
    from repro.serving.engine import ServingEngine
    fetch = ServingEngine._fetch_tokens

    def altered(sess, last):
        return (fetch(sess, last) + 1) % last.shape[-1]
    monkeypatch.setattr(ServingEngine, "_fetch_tokens",
                        staticmethod(altered))


def _zero_experts(monkeypatch):
    from repro.kernels import ops
    monkeypatch.setattr(ops, "expert_ffn_quant_impl",
                        lambda x, *a, **k: jnp.zeros_like(x))


def _cache_unchanged(monkeypatch):
    from repro.models import transformer as T
    step = T.decode_step

    def stale(cfg, params, batch, cache, *a, **k):
        logits, _, metrics = step(cfg, params, batch, cache, *a, **k)
        return logits, cache, metrics
    monkeypatch.setattr(T, "decode_step", stale)


@pytest.mark.parametrize(
    "fault", [_alter_tokens, _zero_experts, _cache_unchanged],
    ids=["token_altered", "experts_skipped", "kv_state_unchanged"])
def test_broken_timed_path_is_not_correct(tmp_path, monkeypatch, fault):
    fault(monkeypatch)
    res = _run(make_root(tmp_path))
    assert not res["correct"]
    gap = res["checks"]["mean_logit_gap"]
    assert gap["value"] > gap["limit"]


def test_no_chip_no_result(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, str(H.BENCH / "run.py"),
                        "--workload", "mixtral-rt1.code-skew", "--seed",
                        "1", "--seconds", "1", "--trace", "0"],
                       capture_output=True, text=True, env=env,
                       cwd=H.ROOT, timeout=300)
    assert p.returncode != 0 and p.stdout.strip() == ""


def test_without_the_program_no_result(tmp_path):
    (tmp_path / "bench").mkdir()
    for f in ("run.py", "harness.py", "__init__.py"):
        (tmp_path / "bench" / f).write_text((H.BENCH / f).read_text())
    (tmp_path / "BENCHMARK.json").write_text(
        (H.ROOT / "BENCHMARK.json").read_text())
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    p = subprocess.run([sys.executable, "bench/run.py", "--workload",
                        "mixtral-rt1.code-skew", "--seed", "1",
                        "--seconds", "1", "--trace", "0"],
                       capture_output=True, text=True, env=env,
                       cwd=tmp_path, timeout=300)
    assert p.returncode != 0 and p.stdout.strip() == ""
