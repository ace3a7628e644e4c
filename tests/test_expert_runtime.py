"""Serverless expert runtime: the slot state machine that executes the
control plane's plans.

Covers the PR's acceptance criteria:
  * locality — zero slot transfers when the plan is unchanged,
    transfers == plan diff size otherwise;
  * engine parity — identical greedy tokens with the runtime off vs on;
  * pool cross-check — runtime-metered cold/warm/prewarm counts and
    GB-seconds match the analytic ServerlessExpertPool on the same plan
    sequence;
plus the satellite fixes: plan_to_tables spill warning / overflow error
and the diff-aware materialise_slots.
"""
import dataclasses
import math
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core.control import MOELESS_EXEC_TIME, ControlPlane, PlanEvent
from repro.core.costmodel import derive_coeffs
from repro.core.placer import place_layer, placement_migrations
from repro.core.plan import LayerPlan, static_plan
from repro.core.scaler import scale_layer
from repro.core.serverless import ServerlessExpertPool
from repro.distributed import ep as EP
from repro.launch.mesh import make_serving_mesh
from repro.models import model as M
from repro.serving.engine import ServingEngine
from repro.serving.expert_runtime import ExpertRuntime
from repro.serving.scheduler import GenRequest


def smoke_cfg(capacity_factor: float | None = None):
    cfg = get_config("mixtral-8x7b", smoke=True).with_(dtype="float32")
    if capacity_factor is not None:
        cfg = cfg.with_(moe=dataclasses.replace(
            cfg.moe, capacity_factor=capacity_factor))
    return cfg


@pytest.fixture(scope="module")
def cfg_params():
    # ample capacity: the GShard dispatch and the EP data plane now
    # share ONE capacity/drop semantics (tests/test_drop_equivalence),
    # but under drops their outputs only agree to float tolerance
    # (different summation order), so bit-exact token parity is asserted
    # drop-free
    cfg = smoke_cfg(capacity_factor=float(
        get_config("mixtral-8x7b", smoke=True).moe.num_experts))
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    return cfg, params


def make_requests(cfg, n=3, prompt_len=8, max_new=6):
    rng = np.random.default_rng(7)
    return [GenRequest(
        rid=i, arrival=0.05 * i,
        prompt=rng.integers(0, cfg.vocab_size, size=prompt_len,
                            dtype=np.int32),
        max_new_tokens=max_new) for i in range(n)]


def events_for(rt, plan, lead=math.inf, exec_time=MOELESS_EXEC_TIME):
    return [PlanEvent(plan=plan, served=plan, lead_time=lead,
                      exec_time=exec_time) for _ in range(rt.n_layers)]


# ------------------------------------------------------------- locality


class TestLocality:
    def _runtime(self, cfg_params):
        cfg, params = cfg_params
        return ExpertRuntime(cfg, params, num_devices=4,
                             slots_per_device=3, keep_alive=1e9)

    def test_unchanged_plan_moves_nothing(self, cfg_params):
        rt = self._runtime(cfg_params)
        plan = static_plan(rt.num_experts, 4)
        r1 = rt.apply(0.0, events_for(rt, plan))
        assert r1.transfers == plan.total_replicas * rt.n_layers
        assert r1.bytes_moved > 0
        # identical plan next iteration: every replica is warm in its
        # slot — zero transfers, zero bytes (function locality)
        r2 = rt.apply(1.0, events_for(rt, plan))
        assert r2.transfers == 0
        assert r2.bytes_moved == 0.0
        assert r2.warm_starts == plan.total_replicas * rt.n_layers

    def test_transfers_equal_plan_diff(self, cfg_params):
        rt = self._runtime(cfg_params)
        e = rt.num_experts
        loads1 = np.array([100.0, 10.0, 10.0, 10.0])
        plan1 = place_layer(loads1, scale_layer(loads1,
                                                max_total_replicas=6), 4)
        rt.apply(0.0, events_for(rt, plan1))
        loads2 = np.array([10.0, 10.0, 100.0, 10.0])
        plan2 = place_layer(loads2, scale_layer(loads2,
                                                max_total_replicas=6), 4,
                            prev=plan1)
        r = rt.apply(1.0, events_for(rt, plan2))
        diff = placement_migrations(plan1, plan2)
        assert diff > 0
        assert r.transfers == diff * rt.n_layers
        assert r.per_layer_transfers == [diff] * rt.n_layers
        assert r.bytes_moved == r.transfers * \
            rt._slot_row_bytes[rt.moe_positions[0]]
        # the untouched replicas were warm starts
        assert r.warm_starts == (plan2.total_replicas - diff) * rt.n_layers
        assert e == 4  # the scenario above assumes the smoke expert count

    def test_slot_stability_across_growth(self, cfg_params):
        """An expert that keeps its replica keeps its SLOT even when
        other experts gain replicas (incremental assignment — rebuilding
        tables from scratch would shuffle everyone)."""
        rt = self._runtime(cfg_params)
        plan1 = static_plan(rt.num_experts, 4)
        rt.apply(0.0, events_for(rt, plan1))
        slots_before = {k: i.slot for k, i in rt.instances[0].items()}
        reps = np.array([2, 1, 1, 1], np.int64)
        plan2 = LayerPlan(4, 4, reps, [[0, 1], [1], [2], [3]])
        rt.apply(1.0, events_for(rt, plan2))
        for key, slot in slots_before.items():
            if key in rt.instances[0]:
                assert rt.instances[0][key].slot == slot


# ----------------------------------------------------- pool cross-check


class TestPoolParity:
    def test_runtime_matches_analytic_pool(self, cfg_params):
        """Same plan sequence, same timestamps, same lead/exec times —
        the executing runtime and the analytic pool must agree on every
        cold/warm/prewarm classification AND on the GB-seconds billed."""
        cfg, params = cfg_params
        coeffs = derive_coeffs(cfg)
        keep_alive = 2.0
        rt = ExpertRuntime(cfg, params, num_devices=4, slots_per_device=3,
                           keep_alive=keep_alive, coeffs=coeffs)
        pools = [ServerlessExpertPool(expert_bytes=coeffs.expert_bytes,
                                      keep_alive=keep_alive)
                 for _ in range(rt.n_layers)]
        cs = rt.cold_start_latency()
        assert cs == pools[0].cold_start_latency()
        rng = np.random.default_rng(3)
        prev = [None] * rt.n_layers
        # uneven gaps: some within keep-alive (warm), one far beyond it
        # (reap + re-create); leads straddle the cold-start latency so
        # all three classifications occur
        times = [0.0, 0.5, 1.0, 8.0, 8.5]
        leads = [0.0, 2 * cs, 0.0, cs / 2, 2 * cs]
        for t, lead in zip(times, leads):
            events = []
            for l in range(rt.n_layers):
                loads = rng.uniform(1.0, 100.0, size=rt.num_experts)
                plan = place_layer(
                    loads, scale_layer(loads, max_total_replicas=8), 4,
                    prev=prev[l], alive=set(pools[l].instances),
                    max_replicas_per_device=3)
                prev[l] = plan
                pools[l].commit(plan, t, MOELESS_EXEC_TIME, lead)
                events.append(PlanEvent(plan=plan, served=plan,
                                        lead_time=lead,
                                        exec_time=MOELESS_EXEC_TIME,
                                        serverless=True))
            rt.apply(t, events)
        pc = (sum(p.stats.cold_starts for p in pools),
              sum(p.stats.warm_starts for p in pools),
              sum(p.stats.prewarmed for p in pools))
        assert rt.stats.counts() == pc
        assert rt.stats.cold_starts > 0 and rt.stats.warm_starts > 0 \
            and rt.stats.prewarmed > 0      # all three paths exercised
        assert rt.stats.evictions > 0       # keep-alive reaping ran
        end = times[-1] + 1.0
        gb_pool = sum(p.finalize(end).instance_seconds_gb for p in pools)
        gb_rt = rt.finalize(end).instance_seconds_gb
        assert gb_rt == pytest.approx(gb_pool, rel=1e-9)
        assert gb_rt > 0

    def test_eviction_frees_slots_for_reuse(self, cfg_params):
        cfg, params = cfg_params
        rt = ExpertRuntime(cfg, params, num_devices=2, slots_per_device=2,
                           keep_alive=1.0)
        plan = static_plan(rt.num_experts, 2)   # 4 replicas = all slots
        rt.apply(0.0, events_for(rt, plan, lead=0.0, exec_time=0.0))
        assert rt.resident_replicas() == plan.total_replicas * rt.n_layers
        # long idle gap: everything reaped, the full plan re-applies into
        # the freed slots (no "no free slot" failure)
        r = rt.apply(10.0, events_for(rt, plan, lead=0.0, exec_time=0.0))
        assert r.evictions == plan.total_replicas * rt.n_layers
        assert r.transfers == plan.total_replicas * rt.n_layers
        assert r.cold_starts == r.transfers  # lead 0 hides nothing

    def test_serverful_redeploy_frees_slots(self, cfg_params):
        """Regression: a serverful strategy whose placement churns (EPLB
        rebalances) must RELEASE the slots of abandoned replicas — with
        keep-alive-only eviction (lead ∞ ⇒ last_used ∞) every historical
        placement stayed pinned and the pool ran out of slots."""
        cfg, params = cfg_params
        rt = ExpertRuntime(cfg, params, num_devices=2, slots_per_device=2,
                           keep_alive=60.0)
        e = rt.num_experts
        plan_a = static_plan(e, 2)                       # e on device e%2
        plan_b = LayerPlan(e, 2, np.ones(e, np.int64),   # devices swapped
                           [[(ei + 1) % 2] for ei in range(e)])
        for i in range(6):   # fills all 4 slots/layer twice over
            plan = plan_a if i % 2 == 0 else plan_b
            rt.apply(float(i), events_for(rt, plan))     # serverful events
        assert rt.resident_replicas() == e * rt.n_layers
        # each swap rewrites every slot — locality can't help here, but
        # nothing leaks and nothing crashes
        assert rt.stats.evictions > 0

    def test_serverless_churn_reclaims_lru_slots(self, cfg_params):
        """Regression: under serverless keep-alive, instances the new
        plan no longer wants can pin every slot; the runtime used to
        raise "no free slot". It now evicts the least recently used of
        them (billed until now) and keeps the plan's warm replicas."""
        cfg, params = cfg_params
        rt = ExpertRuntime(cfg, params, num_devices=2, slots_per_device=2,
                           keep_alive=60.0)
        e = rt.num_experts
        plan_a = static_plan(e, 2)                       # e on device e%2
        plan_b = LayerPlan(e, 2, np.ones(e, np.int64),   # devices swapped
                           [[(ei + 1) % 2] for ei in range(e)])

        def events(plan):
            return [PlanEvent(plan=plan, served=plan, lead_time=math.inf,
                              exec_time=MOELESS_EXEC_TIME, serverless=True)
                    for _ in range(rt.n_layers)]

        rt.apply(0.0, events(plan_a))                    # all 4 slots
        gbs0 = rt.stats.instance_seconds_gb
        r = rt.apply(1.0, events(plan_b))
        assert r.evictions == e * rt.n_layers
        assert r.transfers == e * rt.n_layers
        assert rt.stats.instance_seconds_gb > gbs0       # billed to t=1
        for layer in range(rt.n_layers):
            assert rt.residency_set(layer) == set(plan_b.iter_replicas())


# ------------------------------------------------------- engine parity


class TestEngineParity:
    def test_tokens_identical_and_counts_match(self, cfg_params):
        """Acceptance: greedy tokens from ServingEngine are identical
        with expert_runtime off vs on (same trace, same seed), and the
        runtime's cold/warm/prewarm counts match the analytic pool the
        control plane metered with."""
        cfg, params = cfg_params
        reqs_off = make_requests(cfg)
        reqs_on = make_requests(cfg)

        eng_off = ServingEngine(cfg, params, max_len=32)
        res_off = eng_off.serve(
            reqs_off, num_slots=3,
            control=ControlPlane(cfg, "moeless", num_devices=8,
                                 max_replicas_per_device=2))

        eng_on = ServingEngine(cfg, params, max_len=32,
                               expert_runtime="on")
        ctl_on = ControlPlane(cfg, "moeless", num_devices=8,
                              max_replicas_per_device=2)
        res_on = eng_on.serve(reqs_on, num_slots=3, control=ctl_on)

        assert {r.rid: tuple(r.tokens) for r in reqs_off} \
            == {r.rid: tuple(r.tokens) for r in reqs_on}
        assert res_off.iterations == res_on.iterations

        rt = res_on.runtime
        assert rt is not None
        pool_counts = (
            sum(p.stats.cold_starts for p in ctl_on.bal.pools.values()),
            sum(p.stats.warm_starts for p in ctl_on.bal.pools.values()),
            sum(p.stats.prewarmed for p in ctl_on.bal.pools.values()))
        assert rt.stats.counts() == pool_counts
        assert rt.stats.transfers > 0 and rt.stats.bytes_moved > 0
        end = res_on.clock_s + 1.0
        gb_pool = sum(p.finalize(end).instance_seconds_gb
                      for p in ctl_on.bal.pools.values())
        assert rt.finalize(end).instance_seconds_gb \
            == pytest.approx(gb_pool, rel=1e-9)

    def test_serverful_strategy_executes_too(self, cfg_params):
        """The runtime also executes non-serverless plans: Megatron's
        static plan costs exactly one initial load, then every iteration
        is all-warm with zero transfers."""
        cfg, params = cfg_params
        eng = ServingEngine(cfg, params, max_len=32, expert_runtime="on")
        ctl = ControlPlane(cfg, "megatron-lm", num_devices=8)
        res = eng.serve(make_requests(cfg), num_slots=3, control=ctl)
        rt = res.runtime
        lm, e = rt.n_layers, rt.num_experts
        assert rt.stats.transfers == e * lm        # initial load only
        assert rt.stats.cold_starts == 0           # lead ∞: all prewarmed
        assert rt.stats.prewarmed == e * lm

    def test_runtime_requires_control(self, cfg_params):
        cfg, params = cfg_params
        eng = ServingEngine(cfg, params, max_len=32, expert_runtime="on")
        with pytest.raises(ValueError, match="control"):
            eng.start(num_slots=2)

    def test_unknown_knob_rejected(self, cfg_params):
        cfg, params = cfg_params
        with pytest.raises(ValueError, match="expert_runtime"):
            ServingEngine(cfg, params, expert_runtime="maybe")


# ---------------------------------------- quantized slot banks (int8)


class TestQuantizedSlots:
    """cfg.moe.slot_dtype='int8': the runtime's banks store int8 values
    + fp32 per-row scales, every byte meter shrinks to
    ``param_bytes(cfg)`` exactly, and runtime==analytic parity holds
    bit-for-bit on the smaller byte base."""

    def _cfg8(self, cfg):
        return cfg.with_(moe=dataclasses.replace(cfg.moe,
                                                 slot_dtype="int8"))

    def test_banks_are_quantized(self, cfg_params):
        cfg, params = cfg_params
        rt = ExpertRuntime(self._cfg8(cfg), params, num_devices=4)
        for j in rt.moe_positions:
            bank = rt.banks[j]
            for k in ("w_gate", "w_up", "w_down"):
                assert bank[k].dtype == jnp.int8
                assert bank[k + "_scale"].dtype == jnp.float32
                # scale sits on the contraction axis of its partner
                assert bank[k + "_scale"].shape == bank[k].shape[:-1]

    def test_runtime_matches_analytic_pool_exactly_int8(self, cfg_params):
        """The PR-4 exactness contract survives quantization: same plan
        sequence => identical lifecycle counts, GB-seconds equal to the
        analytic pool on the int8 byte base, and bytes_moved ==
        transfers * param_bytes(cfg)."""
        from repro.core.costmodel import param_bytes

        cfg, params = cfg_params
        cfg8 = self._cfg8(cfg)
        coeffs = derive_coeffs(cfg8)
        assert coeffs.expert_bytes == param_bytes(cfg8)
        keep_alive = 2.0
        rt = ExpertRuntime(cfg8, params, num_devices=4,
                           slots_per_device=3, keep_alive=keep_alive,
                           coeffs=coeffs)
        for j in rt.moe_positions:
            assert rt._slot_row_bytes[j] == coeffs.expert_bytes
        pools = [ServerlessExpertPool(expert_bytes=coeffs.expert_bytes,
                                      keep_alive=keep_alive)
                 for _ in range(rt.n_layers)]
        assert rt.cold_start_latency() == pools[0].cold_start_latency()
        cs = rt.cold_start_latency()
        rng = np.random.default_rng(11)
        prev = [None] * rt.n_layers
        times = [0.0, 0.5, 8.0, 8.5]
        leads = [0.0, 2 * cs, cs / 2, 0.0]
        for t, lead in zip(times, leads):
            events = []
            for l in range(rt.n_layers):
                loads = rng.uniform(1.0, 100.0, size=rt.num_experts)
                plan = place_layer(
                    loads, scale_layer(loads, max_total_replicas=8), 4,
                    prev=prev[l], alive=set(pools[l].instances),
                    max_replicas_per_device=3)
                prev[l] = plan
                pools[l].commit(plan, t, MOELESS_EXEC_TIME, lead)
                events.append(PlanEvent(plan=plan, served=plan,
                                        lead_time=lead,
                                        exec_time=MOELESS_EXEC_TIME,
                                        serverless=True))
            rt.apply(t, events)
        pc = (sum(p.stats.cold_starts for p in pools),
              sum(p.stats.warm_starts for p in pools),
              sum(p.stats.prewarmed for p in pools))
        assert rt.stats.counts() == pc
        assert rt.stats.bytes_moved \
            == rt.stats.transfers * coeffs.expert_bytes
        end = times[-1] + 1.0
        gb_pool = sum(p.finalize(end).instance_seconds_gb for p in pools)
        gb_rt = rt.finalize(end).instance_seconds_gb
        assert gb_rt == pytest.approx(gb_pool, rel=1e-9)
        assert gb_rt > 0

    def test_int8_moves_at_most_030x_of_fp32(self, cfg_params):
        """The headline perf contract: the same bootstrap load moves
        <= 0.30x the bytes (and bills <= 0.30x the cold-start seconds)
        with int8 slot banks vs fp32 — on the float32 smoke config the
        exact ratio is (3df + (2d+f)*4) / (3df*4) ~ 0.253."""
        cfg, params = cfg_params
        rt32 = ExpertRuntime(cfg, params, num_devices=4)
        rt8 = ExpertRuntime(self._cfg8(cfg), params, num_devices=4)
        r32 = rt32.bootstrap()
        r8 = rt8.bootstrap()
        assert r8.transfers == r32.transfers
        assert 0 < r8.bytes_moved <= 0.30 * r32.bytes_moved
        assert rt8.cold_start_latency() < rt32.cold_start_latency()


# ------------------------------------- satellite: plan_to_tables spill


class TestPlanToTables:
    def test_spill_warns_and_stays_consistent(self):
        plan = LayerPlan(3, 2, np.ones(3, np.int64), [[0], [0], [0]])
        with pytest.warns(RuntimeWarning, match="spilled"):
            tables = EP.plan_to_tables(plan, ep=2, slots_per_device=2)
        se = np.asarray(tables["slot_expert"])
        es = np.asarray(tables["expert_slots"])
        # every expert got exactly one slot, and the slot table agrees
        for e in range(3):
            s = int(es[e, 0])
            assert se[s] == e
        # rank 0 holds 2 slots; the third replica spilled to rank 1
        assert (se[:2] != 3).all() and (se[2:] != 3).sum() == 1

    def test_no_spill_no_warning(self):
        plan = static_plan(4, 2)
        with warnings.catch_warnings():
            warnings.simplefilter("error")
            EP.plan_to_tables(plan, ep=2, slots_per_device=2)

    def test_total_overflow_raises(self):
        plan = LayerPlan(5, 2, np.ones(5, np.int64),
                         [[0], [0], [1], [1], [0]])
        with pytest.raises(ValueError, match="slot"):
            EP.plan_to_tables(plan, ep=2, slots_per_device=2)


# --------------------------------- satellite: diff-aware materialise


class TestMaterialiseDiff:
    def _weights(self, e=4, d=8, f=16):
        ks = jax.random.split(jax.random.PRNGKey(5), 3)
        return {"w_gate": jax.random.normal(ks[0], (e, d, f), jnp.float32),
                "w_up": jax.random.normal(ks[1], (e, d, f), jnp.float32),
                "w_down": jax.random.normal(ks[2], (e, f, d), jnp.float32)}

    def test_incremental_equals_full(self):
        mesh = make_serving_mesh(1, ep=1)
        w = self._weights()
        padded = EP.pad_expert_bank(w)
        t1 = EP.plan_to_tables(static_plan(4, 1), ep=1, slots_per_device=8)
        full1 = EP.materialise_slots(w, t1["slot_expert"], mesh,
                                     padded=padded)
        loads = np.array([50.0, 5.0, 5.0, 5.0])
        plan2 = place_layer(loads, scale_layer(loads,
                                               max_total_replicas=6), 1)
        t2 = EP.plan_to_tables(plan2, ep=1, slots_per_device=8)
        full2 = EP.materialise_slots(w, t2["slot_expert"], mesh)
        inc = EP.materialise_slots(w, t2["slot_expert"], mesh,
                                   padded=padded, prev=full1,
                                   prev_slot_expert=t1["slot_expert"])
        for k in full2:
            np.testing.assert_array_equal(np.asarray(full2[k]),
                                          np.asarray(inc[k]))

    def test_unchanged_plan_returns_prev_banks(self):
        mesh = make_serving_mesh(1, ep=1)
        w = self._weights()
        t1 = EP.plan_to_tables(static_plan(4, 1), ep=1, slots_per_device=8)
        full1 = EP.materialise_slots(w, t1["slot_expert"], mesh)
        again = EP.materialise_slots(w, t1["slot_expert"], mesh,
                                     prev=full1,
                                     prev_slot_expert=t1["slot_expert"])
        assert again is full1   # zero gathers, zero copies
