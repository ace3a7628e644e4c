"""Continuous-batching serving benchmark: decode throughput of the ONE
jitted batched step over the slot pool vs one-request-at-a-time
decoding, the per-slot sampling overhead (one extra jitted call), and
the control-plane overhead per iteration — every control-plane row
drives the single ``repro.core.control.ControlPlane.step``
implementation (vectorized planning = 1 host sync).

``deterministic_counters`` is the wall-clock-free companion: the
expert-runtime lane's byte/GB-s/lifecycle meters per slot_dtype,
reproducible bit-for-bit on one platform — the numbers committed to
``benchmarks/BENCH_serving.json`` and regression-gated by
``benchmarks.bench_gate`` in CI.

  PYTHONPATH=src python -m benchmarks.serving_bench [--slots 8]
  PYTHONPATH=src python -m benchmarks.serving_bench --counters
"""
from __future__ import annotations

import argparse
import dataclasses
import os
import time

# the multi-rank counter section runs on a (data=1, ep=4, tp=1) host
# mesh — force the devices before the first jax backend init (no-op
# when the caller already set XLA_FLAGS, e.g. CI)
os.environ.setdefault("XLA_FLAGS",
                      "--xla_force_host_platform_device_count=8")

import jax
import numpy as np


def _with_slot_dtype(cfg, slot_dtype: str):
    return cfg.with_(moe=dataclasses.replace(cfg.moe,
                                             slot_dtype=slot_dtype))


def main(slots: int = 8, gen: int = 32, prompt_len: int = 16,
         arch: str = "mixtral-8x7b", impl: str = "auto",
         slot_dtype: str = "fp32"):
    from repro.configs import get_config
    from repro.core import predictor as P
    from repro.models import model as M
    from repro.serving.engine import MoElessController, ServingEngine
    from repro.serving.scheduler import GenRequest, SamplingParams

    cfg = get_config(arch, smoke=True).with_(dtype="float32", impl=impl)
    cfg = _with_slot_dtype(cfg, slot_dtype)
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    max_len = prompt_len + gen + 1

    def mk_reqs(sampling: SamplingParams = SamplingParams()):
        return [GenRequest(
            rid=i, arrival=0.0,
            prompt=rng.integers(0, cfg.vocab_size, size=prompt_len,
                                dtype=np.int32),
            max_new_tokens=gen, sampling=sampling) for i in range(slots)]

    # sequential: each request decoded alone (batch of 1)
    engine = ServingEngine(cfg, params, max_len=max_len)
    engine.serve(mk_reqs()[:1], num_slots=1)      # warm up compile
    t0 = time.perf_counter()
    for r in mk_reqs():
        engine.serve([r], num_slots=1)
    seq_s = time.perf_counter() - t0

    # continuous batching: all requests share one jitted step
    engine = ServingEngine(cfg, params, max_len=max_len)
    engine.serve(mk_reqs()[:1], num_slots=slots)  # warm up compile
    t0 = time.perf_counter()
    res = engine.serve(mk_reqs(), num_slots=slots)
    bat_s = time.perf_counter() - t0

    # same batched serve with FULL telemetry attached (registry AND
    # tracer, the most expensive configuration) — the derived column is
    # the wall overhead vs the NOOP default above; the contract is <2%.
    # The per-site cost (a guarded dict lookup + locked float add per
    # iteration) is far below run-to-run jitter, so the runs are
    # INTERLEAVED (ambient load hits both sides alike) and each side
    # takes its best of 3
    from repro.obs import Telemetry, Tracer

    tracer = Tracer(process_name="serving-bench")
    eng_tel = ServingEngine(cfg, params, max_len=max_len,
                            telemetry=Telemetry(tracer=tracer))
    eng_tel.serve(mk_reqs()[:1], num_slots=slots)  # warm up compile
    bat_min = tel_min = float("inf")
    res_t = None
    for _ in range(3):
        t0 = time.perf_counter()
        engine.serve(mk_reqs(), num_slots=slots)
        bat_min = min(bat_min, time.perf_counter() - t0)
        t0 = time.perf_counter()
        res_t = eng_tel.serve(mk_reqs(), num_slots=slots)
        tel_min = min(tel_min, time.perf_counter() - t0)
    assert res_t.iterations == res.iterations, \
        "telemetry changed the serve (observation-only invariant)"

    # batched + per-slot top-k/top-p sampling (same jitted sampler call;
    # greedy rows take the argmax lane)
    samp = SamplingParams(temperature=0.8, top_k=max(2, cfg.vocab_size // 4),
                          top_p=0.95, seed=0)
    engine.serve(mk_reqs(samp)[:1], num_slots=slots)
    t0 = time.perf_counter()
    res_s = engine.serve(mk_reqs(samp), num_slots=slots)
    smp_s = time.perf_counter() - t0

    # paged pool + chunked prefill vs solo prefill on STAGGERED arrivals:
    # with solo prefill a request joining mid-decode stalls every
    # in-flight request for a full-prompt B=1 prefill; chunked prefill
    # folds <= chunk prompt tokens into the shared batched step, so the
    # worst per-iteration stall is bounded by the chunk size. The row's
    # derived column reports the max single-step wall time of each mode
    # (the TPOT stall a co-resident request observes).
    from repro.configs import ServingSpec

    def _stagger_serve(spec):
        eng = ServingEngine(cfg, params, max_len=max_len, serving=spec)
        eng.serve(mk_reqs()[:1], num_slots=slots)     # warm up compile
        reqs = mk_reqs()
        for i, r in enumerate(reqs):
            r.arrival = 0.05 * i                      # joins mid-decode
        eng.start(num_slots=slots)
        for r in reqs:
            eng.submit(r)
        total = stall = 0.0
        first = True
        while eng.has_work:
            t0 = time.perf_counter()
            eng.step()
            dt = time.perf_counter() - t0
            total += dt
            if not first:                 # steady state: joins included
                stall = max(stall, dt)
            first = False
        eng.close()
        return total, stall

    solo_s, solo_stall = _stagger_serve(ServingSpec(kv="paged"))
    chk_s, chk_stall = _stagger_serve(
        ServingSpec(kv="paged", prefill_chunk=8))

    # batched + full MoEless control plane (vectorized planning through
    # the one ControlPlane.step implementation)
    pred = P.from_gates(cfg, params, distance=1)
    ctrl = MoElessController(cfg, num_devices=8, predictor=pred)
    engine = ServingEngine(cfg, params, max_len=max_len, controller=ctrl)
    engine.serve(mk_reqs()[:1], num_slots=slots)
    n0 = ctrl.host_transfers
    t0 = time.perf_counter()
    res_c = engine.serve(mk_reqs(), num_slots=slots)
    ctl_s = time.perf_counter() - t0

    # batched + control plane + EXECUTING expert runtime: the plans are
    # applied as slot diffs and BOTH phases' MoE layers (prefill and
    # decode) run through the EP slot data plane with drop-equivalent
    # capacity semantics; cold/warm/prewarm and bytes moved come from
    # the runtime's own meters
    engine = ServingEngine(cfg, params, max_len=max_len,
                           expert_runtime="on")
    engine.serve(mk_reqs()[:1], num_slots=slots,
                 control=MoElessController(cfg, num_devices=8,
                                           predictor=pred))
    ctrl_r = MoElessController(cfg, num_devices=8, predictor=pred)
    t0 = time.perf_counter()
    res_r = engine.serve(mk_reqs(), num_slots=slots, control=ctrl_r)
    rtm_s = time.perf_counter() - t0
    rst = res_r.runtime.finalize(res_r.clock_s)

    # measured materialisation/compute overlap: the double-buffered
    # flush returns once the scatter into the BACK bank is dispatched —
    # blocking on the swapped-in bank then measures the copy itself.
    # The hidden window is compared against the analytic per-copy
    # cold-start bound the control plane plans with.
    import math

    from repro.core.control import MOELESS_EXEC_TIME, PlanEvent
    from repro.core.placer import place_layer
    from repro.core.plan import static_plan
    from repro.core.scaler import scale_layer
    from repro.serving.expert_runtime import ExpertRuntime

    rt2 = ExpertRuntime(cfg, params, num_devices=8, keep_alive=1e9)
    n_exp = cfg.moe.num_experts
    p0 = static_plan(n_exp, 8)
    rt2.apply(0.0, [PlanEvent(plan=p0, served=p0, lead_time=math.inf,
                              exec_time=MOELESS_EXEC_TIME)
                    for _ in range(rt2.n_layers)])
    jax.block_until_ready([rt2.banks[j] for j in rt2.moe_positions])
    loads = np.random.default_rng(1).integers(
        1, 100, size=n_exp).astype(np.float64)
    p1 = place_layer(loads, scale_layer(loads, max_total_replicas=12),
                     8, prev=p0)
    ev1 = [PlanEvent(plan=p1, served=p0, lead_time=0.0,
                     exec_time=MOELESS_EXEC_TIME)
           for _ in range(rt2.n_layers)]
    t0 = time.perf_counter()
    rep_o = rt2.apply(1.0, ev1)
    disp_s = time.perf_counter() - t0
    jax.block_until_ready([rt2.banks[j] for j in rt2.moe_positions])
    tot_s = time.perf_counter() - t0
    hidden_s = max(tot_s - disp_s, 0.0)
    n_el = max(rep_o.overlap_eligible, 1)

    # gateway lane: the same burst routed through the multi-replica
    # router (2 threaded replicas, least-outstanding-tokens balancing)
    # — measures the whole submit -> step-thread -> event-fanout path
    from repro.serving.gateway import AutoscalerConfig, EngineDriver, Router

    def _replica(i: int) -> EngineDriver:
        eng = ServingEngine(cfg, params, max_len=max_len)
        return EngineDriver(eng, replica_id=i, num_slots=slots,
                            max_pending=2 * slots)

    router = Router(_replica, threaded=True,
                    scaler=AutoscalerConfig(min_replicas=2,
                                            max_replicas=2))

    def _routed_burst(n: int) -> float:
        t0 = time.perf_counter()
        for _ in range(n):
            router.submit(GenRequest(
                rid=router.next_rid(), arrival=float("nan"),
                prompt=rng.integers(0, cfg.vocab_size, size=prompt_len,
                                    dtype=np.int32),
                max_new_tokens=gen))
        want = router.metrics()["router"]["admitted"]
        while router.metrics()["router"]["completed"] < want:
            time.sleep(0.002)
        return time.perf_counter() - t0

    _routed_burst(2)                   # warm up both replicas' compiles
    gw_n = 2 * slots
    gw_s = _routed_burst(gw_n)
    gw_m = router.metrics()["router"]
    router.stop()

    # rows in the harness format: (name, us_per_token, derived)
    tokens = slots * gen
    syncs = ctrl.host_transfers - n0
    iters = res_c.iterations + res_c.prefills
    return [
        ("serve_sequential", seq_s / tokens * 1e6,
         f"{tokens / seq_s:.1f} tok/s"),
        ("serve_batched", bat_s / tokens * 1e6,
         f"{tokens / bat_s:.1f} tok/s "
         f"(occupancy {res.mean_batch_occupancy:.1f})"),
        ("telemetry_overhead", (tel_min - bat_min) / tokens * 1e6,
         f"instrumented {tokens / tel_min:.1f} tok/s vs noop "
         f"{tokens / bat_min:.1f} tok/s, interleaved best of 3 "
         f"({(tel_min / bat_min - 1) * 100:+.2f}% wall, contract <2%; "
         f"registry + {len(tracer)} trace events)"),
        ("serve_batched+sampling", smp_s / tokens * 1e6,
         f"{tokens / smp_s:.1f} tok/s "
         f"(temp={samp.temperature}, top-k={samp.top_k}, "
         f"top-p={samp.top_p}, occupancy "
         f"{res_s.mean_batch_occupancy:.1f})"),
        ("serve_paged_chunked", chk_s / tokens * 1e6,
         f"{tokens / chk_s:.1f} tok/s (paged pool, chunk=8); max decode "
         f"stall {chk_stall * 1e3:.2f}ms chunked vs "
         f"{solo_stall * 1e3:.2f}ms solo prefill "
         f"({tokens / solo_s:.1f} tok/s paged-solo)"),
        ("serve_batched+control", ctl_s / tokens * 1e6,
         f"{tokens / ctl_s:.1f} tok/s "
         f"({syncs / max(iters, 1):.2f} host syncs/iter)"),
        ("serve_batched+runtime", rtm_s / tokens * 1e6,
         f"{tokens / rtm_s:.1f} tok/s "
         f"(slot_dtype={slot_dtype}, cold/warm/prewarm "
         f"{rst.cold_starts}/{rst.warm_starts}/"
         f"{rst.prewarmed}, {rst.transfers} slot transfers, "
         f"{rst.bytes_moved / 1e6:.1f}MB moved, "
         f"{rst.instance_seconds_gb:.3g} GB-s, "
         f"{rst.by_phase.get('prefill', {}).get('iterations', 0)} EP "
         f"prefills, {res_r.dropped_tokens:.0f} dropped)"),
        ("runtime_overlap_copy", hidden_s / n_el * 1e6,
         f"{rep_o.overlap_eligible} overlap-eligible copies: dispatched "
         f"in {disp_s * 1e3:.2f}ms, completed in {tot_s * 1e3:.2f}ms "
         f"({hidden_s * 1e3:.2f}ms hidden behind compute; analytic "
         f"cold-start bound {rt2.cold_start_latency() * 1e3:.2f}ms/copy)"),
        ("serve_gateway_2rep", gw_s / (gw_n * gen) * 1e6,
         f"{gw_n * gen / gw_s:.1f} tok/s across 2 threaded replicas "
         f"(admitted {gw_m['admitted']}, completed {gw_m['completed']}, "
         f"rejected {gw_m['rejected']})"),
    ]


def deterministic_counters(slots: int = 6, gen: int = 8,
                           prompt_len: int = 16,
                           arch: str = "mixtral-8x7b", impl: str = "auto"):
    """The serving numbers that are DETERMINISTIC on one platform — no
    wall-clock anywhere. One expert-runtime serving run per slot_dtype
    under the MoEless control plane: the serving clock advances by
    MODELED iteration latency, so lifecycle counts, bytes moved and
    GB-s billed are pure functions of (seed, config). These rows are
    the committed ``BENCH_serving.json`` baseline that
    ``benchmarks.bench_gate`` diffs in CI."""
    from repro.configs import get_config
    from repro.configs.base import SLOT_DTYPES
    from repro.core import predictor as P
    from repro.models import model as M
    from repro.serving.engine import MoElessController, ServingEngine
    from repro.serving.scheduler import GenRequest

    out = {"arch": arch, "slots": slots, "gen": gen,
           "prompt_len": prompt_len}
    for slot_dtype in SLOT_DTYPES:
        cfg = get_config(arch, smoke=True).with_(dtype="float32",
                                                 impl=impl)
        cfg = _with_slot_dtype(cfg, slot_dtype)
        params = M.init_params(cfg, jax.random.PRNGKey(0))
        rng = np.random.default_rng(0)
        reqs = [GenRequest(
            rid=i, arrival=0.0,
            prompt=rng.integers(0, cfg.vocab_size, size=prompt_len,
                                dtype=np.int32),
            max_new_tokens=gen) for i in range(slots)]
        pred = P.from_gates(cfg, params, distance=1)
        ctrl = MoElessController(cfg, num_devices=8, predictor=pred)
        engine = ServingEngine(cfg, params, max_len=prompt_len + gen + 1,
                               expert_runtime="on")
        res = engine.serve(reqs, num_slots=slots, control=ctrl)
        st = res.runtime.finalize(res.clock_s)
        out[f"serve_{slot_dtype}"] = {
            "iterations": int(res.iterations),
            "prefills": int(res.prefills),
            "ep_prefill_iterations": int(
                st.by_phase.get("prefill", {}).get("iterations", 0)),
            "cold_starts": int(st.cold_starts),
            "warm_starts": int(st.warm_starts),
            "prewarmed": int(st.prewarmed),
            "transfers": int(st.transfers),
            "evictions": int(st.evictions),
            "bytes_moved": float(st.bytes_moved),
            "instance_seconds_gb": float(st.instance_seconds_gb),
            "dropped_tokens": float(res.dropped_tokens),
            "overlap_eligible_copies": int(st.overlap_eligible_copies),
            "exposed_copies": int(st.exposed_copies),
            "overlap_hidden_s": float(st.overlap_hidden_s),
        }
    f32, i8 = out["serve_fp32"], out["serve_int8"]
    # the headline contract (ISSUE/ROADMAP 4a): quantized slot banks
    # move <= 0.30x the bytes behind every cold start
    out["int8_over_fp32_bytes"] = i8["bytes_moved"] / f32["bytes_moved"]
    out["int8_over_fp32_gb_s"] = (
        i8["instance_seconds_gb"] / f32["instance_seconds_gb"])

    # multi-rank lane: the SAME fp32 serve on a (data=1, ep=4, tp=1)
    # host mesh — lifecycle counts, bytes and drops must be IDENTICAL
    # to the 1-device run (mesh-invariant capacity semantics), with
    # per-rank byte attribution and the overlap split as extra leaves
    if len(jax.devices()) < 4:
        raise RuntimeError(
            "multi-rank serving counters need >= 4 XLA devices; run "
            "with XLA_FLAGS=--xla_force_host_platform_device_count=8")
    from repro.launch.mesh import make_serving_mesh
    cfg = get_config(arch, smoke=True).with_(dtype="float32", impl=impl)
    cfg = _with_slot_dtype(cfg, "fp32")
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    reqs = [GenRequest(
        rid=i, arrival=0.0,
        prompt=rng.integers(0, cfg.vocab_size, size=prompt_len,
                            dtype=np.int32),
        max_new_tokens=gen) for i in range(slots)]
    pred = P.from_gates(cfg, params, distance=1)
    ctrl = MoElessController(cfg, num_devices=8, predictor=pred)
    engine = ServingEngine(cfg, params, max_len=prompt_len + gen + 1,
                           expert_runtime="on",
                           mesh=make_serving_mesh(4, ep=4))
    res = engine.serve(reqs, num_slots=slots, control=ctrl)
    st = res.runtime.finalize(res.clock_s)
    out["serve_multirank_ep4"] = {
        "iterations": int(res.iterations),
        "cold_starts": int(st.cold_starts),
        "warm_starts": int(st.warm_starts),
        "prewarmed": int(st.prewarmed),
        "transfers": int(st.transfers),
        "bytes_moved": float(st.bytes_moved),
        "dropped_tokens": float(res.dropped_tokens),
        "rank_bytes": {r: float(b)
                       for r, b in sorted(st.rank_bytes.items())},
        "overlap_eligible_copies": int(st.overlap_eligible_copies),
        "exposed_copies": int(st.exposed_copies),
        "overlap_hidden_s": float(st.overlap_hidden_s),
        # mesh-invariance contract: zero drift vs the 1-device meters
        "bytes_moved_minus_fp32": (float(st.bytes_moved)
                                   - f32["bytes_moved"]),
        "dropped_minus_fp32": (float(res.dropped_tokens)
                               - f32["dropped_tokens"]),
    }

    out["gateway"] = _gateway_counters(arch=arch, impl=impl)
    out["telemetry"] = _telemetry_counters(arch=arch, impl=impl)
    out["paged_kv"] = _paged_kv_counters(arch=arch, impl=impl)
    return out


def _paged_kv_counters(*, arch: str = "mixtral-8x7b", impl: str = "auto",
                       slots: int = 3, gen: int = 8):
    """Deterministic paged-KV / prefix-cache / chunked-prefill scenario —
    no wall clock. A shared-system-prompt burst (one priming request
    carrying only the 12-token system prompt, then 5 requests extending
    it) over 3 slots: the second admission wave hits the radix cache,
    each hit ends inside a block (block=5) so every warm admission
    copies exactly one boundary block (COW). All identity leaves compare
    greedy tokens bit-for-bit, so the run is drop-free (ample capacity
    factor — the documented boundary of the identity contract)."""
    from repro.configs import ServingSpec, get_config
    from repro.models import model as M
    from repro.serving.engine import ServingEngine
    from repro.serving.scheduler import GenRequest

    cfg = get_config(arch, smoke=True).with_(dtype="float32", impl=impl)
    cfg = cfg.with_(moe=dataclasses.replace(
        cfg.moe, capacity_factor=float(cfg.moe.num_experts)))
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    sys_prompt = rng.integers(0, cfg.vocab_size, size=12, dtype=np.int32)
    tails = [rng.integers(0, cfg.vocab_size, size=4, dtype=np.int32)
             for _ in range(5)]
    max_len = 12 + 4 + gen + 1

    def burst():
        reqs = [GenRequest(rid=0, arrival=0.0, prompt=sys_prompt.copy(),
                           max_new_tokens=gen)]
        reqs += [GenRequest(
            rid=i + 1, arrival=0.0,
            prompt=np.concatenate([sys_prompt, t]), max_new_tokens=gen)
            for i, t in enumerate(tails)]
        return reqs

    def run(spec):
        eng = ServingEngine(cfg, params, max_len=max_len, serving=spec)
        reqs = burst()
        eng.start(num_slots=slots)
        for r in reqs:
            eng.submit(r)
        res = eng.run()
        kv = eng._sess.kv
        eng.close()
        return {r.rid: tuple(r.tokens) for r in reqs}, res, kv

    # kv_blocks=32: roomy pool, so the scenario meters prefix sharing
    # alone (eviction under pressure is covered by tests/test_paged_kv)
    base, res_b, _ = run(ServingSpec())
    solo, _, _ = run(ServingSpec(kv="paged", kv_block=5, kv_blocks=32))
    chunked, res_nc, _ = run(ServingSpec(kv="paged", kv_block=5,
                                         kv_blocks=32, prefill_chunk=4))
    warm, res_w, kv = run(ServingSpec(kv="paged", kv_block=5,
                                      kv_blocks=32, prefill_chunk=4,
                                      prefix_cache=True))
    return {
        "kv_block": 5,
        "prefill_chunk": 4,
        "block_bytes": int(kv.block_bytes),
        # bit-identity contract leaves (all must stay 1)
        "disjoint_identical": int(solo == base),
        "chunked_equals_solo": int(chunked == base),
        "shared_prefix_identical": int(warm == base),
        # sharing meters: wave 2 (requests 3..5) hits the cached system
        # prompt; each hit ends 2 tokens into block 2 -> one COW copy
        "prefix_hits": int(kv.prefix.hits),
        "prefix_tokens_saved": int(kv.prefix.tokens_saved),
        "cow_blocks": int(kv.cow_blocks),
        "pool_blocks": int(kv.num_blocks),
        # chunked prefill steps skipped by the prefix cache: each warm
        # admission prefills only the unmatched tail, so the whole burst
        # drains in fewer engine iterations (TTFT iterations saved)
        "iterations_chunked": int(res_nc.iterations + res_nc.prefills),
        "iterations_prefix": int(res_w.iterations + res_w.prefills),
        "ttft_iters_saved": int((res_nc.iterations + res_nc.prefills)
                                - (res_w.iterations + res_w.prefills)),
    }


# registry series whose value is a pure function of (seed, config):
# event counts, modeled bytes/seconds, and histogram _count leaves —
# never wall-clock sums (those stay out of the committed baseline)
_DETERMINISTIC_TELEMETRY_SERIES = frozenset({
    "scheduler_admitted_total", "scheduler_finished_total",
    "scheduler_queue_delay_seconds_count",
    "engine_steps_total", "engine_tokens_total",
    "engine_step_seconds_count",
    "runtime_replica_starts_total", "runtime_transfers_total",
    "runtime_bytes_moved_total", "runtime_rank_bytes_total",
    "runtime_evictions_total", "runtime_overlap_copies_total",
    "runtime_bank_flush_seconds_count",
    "control_iterations_total", "control_dropped_tokens_total",
    "control_stragglers_total", "control_pred_load_l1_error",
    "control_step_seconds_count",
})


def _telemetry_counters(*, arch: str = "mixtral-8x7b", impl: str = "auto",
                        slots: int = 4, gen: int = 8,
                        prompt_len: int = 16):
    """Registry snapshot of ONE instrumented expert-runtime serve on the
    modeled clock, filtered to the deterministic series above. Doubles
    as a consistency gate: the registry counters must agree exactly with
    the runtime's own legacy meters."""
    from repro.configs import get_config
    from repro.core import predictor as P
    from repro.models import model as M
    from repro.obs import Telemetry
    from repro.serving.engine import MoElessController, ServingEngine
    from repro.serving.scheduler import GenRequest

    cfg = get_config(arch, smoke=True).with_(dtype="float32", impl=impl)
    cfg = _with_slot_dtype(cfg, "fp32")
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    reqs = [GenRequest(
        rid=i, arrival=0.0,
        prompt=rng.integers(0, cfg.vocab_size, size=prompt_len,
                            dtype=np.int32),
        max_new_tokens=gen) for i in range(slots)]
    tel = Telemetry()
    pred = P.from_gates(cfg, params, distance=1)
    ctrl = MoElessController(cfg, num_devices=8, predictor=pred,
                             telemetry=tel)
    engine = ServingEngine(cfg, params, max_len=prompt_len + gen + 1,
                           expert_runtime="on", telemetry=tel)
    res = engine.serve(reqs, num_slots=slots, control=ctrl)
    st = res.runtime.finalize(res.clock_s)
    d = tel.registry.as_dict()
    keep = {k: float(v) for k, v in d.items()
            if k.split("{", 1)[0] in _DETERMINISTIC_TELEMETRY_SERIES}
    # registry == legacy meters, or the instrumentation dropped events
    assert keep["runtime_transfers_total"] == st.transfers, \
        (keep["runtime_transfers_total"], st.transfers)
    assert keep["runtime_bytes_moved_total"] == float(st.bytes_moved), \
        (keep["runtime_bytes_moved_total"], st.bytes_moved)
    assert keep["engine_tokens_total"] == slots * gen, \
        (keep["engine_tokens_total"], slots * gen)
    return keep


def _gateway_counters(*, arch: str = "mixtral-8x7b", impl: str = "auto",
                      slots: int = 2, gen: int = 8, prompt_len: int = 8,
                      n_requests: int = 10):
    """Deterministic gateway/router/autoscaler scenario — NO wall clock.

    An unthreaded router (the caller drives ``step_all``) over replicas
    whose sessions run on the MODELED serving clock (the MoEless control
    plane is attached as session control), so admissions, rejections,
    queue delays and every autoscale decision are pure functions of
    (seed, config): a tiny replica (2 KV slots, 2-deep admission queue)
    takes a 10-request burst, backpressure rejects the overflow,
    sustained queue delay scales the fleet up toward ``max_replicas``,
    one request is cancelled mid-flight, and post-drain idle ticks burn
    enough resident GB-s to retire the extra replicas back to
    ``min_replicas``."""
    from repro.configs import get_config
    from repro.core import predictor as P
    from repro.models import model as M
    from repro.serving.engine import MoElessController, ServingEngine
    from repro.serving.gateway import (AutoscalerConfig, Backpressure,
                                       EngineDriver, Router)
    from repro.serving.scheduler import GenRequest

    cfg = get_config(arch, smoke=True).with_(dtype="float32", impl=impl)
    cfg = _with_slot_dtype(cfg, "fp32")
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    pred = P.from_gates(cfg, params, distance=1)

    def factory(i: int) -> EngineDriver:
        ctrl = MoElessController(cfg, num_devices=8, predictor=pred)
        eng = ServingEngine(cfg, params, max_len=prompt_len + gen + 1)
        return EngineDriver(eng, replica_id=i, num_slots=slots,
                            max_pending=2, control=ctrl)

    router = Router(factory, threaded=False, scaler=AutoscalerConfig(
        min_replicas=1, max_replicas=3, queue_delay_up_s=1e-9, sustain=2,
        idle_gb_s_down=1e-6, cooldown_s=0.0))
    rng = np.random.default_rng(0)
    token_events = 0
    handles = []
    for k in range(n_requests):
        req = GenRequest(
            rid=router.next_rid(), arrival=float("nan"),
            prompt=rng.integers(0, cfg.vocab_size, size=prompt_len,
                                dtype=np.int32),
            max_new_tokens=gen)
        try:
            d, h = router.submit(req)
            if h.status != "rejected":
                handles.append((d, h))
        except Backpressure:
            pass
        # the first 4 submits land as one cold burst before any step:
        # the lone replica's 2-deep admission queue overflows and the
        # tail of the burst bounces with 429-style backpressure
        if k >= 3:
            token_events += router.step_all()
            router.autoscale(router.clock())
    # cancel the youngest request still in flight (frees its KV slot)
    for d, h in reversed(handles):
        if h.status in ("pending", "running"):
            router.cancel(d, h)
            break
    for _ in range(10_000):
        if not any(d.engine.has_work for d in router.replicas.values()
                   if d.healthy):
            break
        token_events += router.step_all()
        router.autoscale(router.clock())
    else:
        raise RuntimeError("gateway counter scenario did not drain")
    # idle ticks on a synthetic clock: each tick bills dt x resident_gb
    # of idle burn per replica until the fleet is back at min_replicas
    t_end = router.clock()
    for i in range(1, 7):
        router.autoscale(t_end + 0.05 * i)
    m = router.metrics()["router"]
    router.stop()
    return {
        "requests": n_requests,
        "admitted": int(m["admitted"]),
        "rejected": int(m["rejected"]),
        "cancelled": int(m["cancelled"]),
        "completed": int(m["completed"]),
        "token_events": int(token_events),
        "scale_up_events": int(m["scale_ups"]),
        "scale_down_events": int(m["scale_downs"]),
        "max_replicas_seen": int(m["max_replicas_seen"]),
        "final_replicas": int(m["num_replicas"]),
    }


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--slots", type=int, default=8)
    ap.add_argument("--gen", type=int, default=32)
    from repro.configs.base import SLOT_DTYPES
    from repro.kernels import IMPLS
    ap.add_argument("--impl", default="auto", choices=IMPLS)
    ap.add_argument("--slot-dtype", default="fp32", choices=SLOT_DTYPES,
                    help="expert slot-bank storage format for the "
                         "runtime lane")
    ap.add_argument("--counters", action="store_true",
                    help="print the deterministic counter JSON "
                         "(the BENCH_serving.json payload) instead of "
                         "the wall-clock rows")
    a = ap.parse_args()
    if a.counters:
        import json
        print(json.dumps(deterministic_counters(impl=a.impl), indent=1))
    else:
        for name, us, derived in main(slots=a.slots, gen=a.gen,
                                      impl=a.impl,
                                      slot_dtype=a.slot_dtype):
            print(f"{name},{us:.1f},{derived}")
