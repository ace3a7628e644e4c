"""Drop-equivalent capacity semantics across the two MoE data planes.

The contract (one capacity/drop semantics, ISSUE 5):
  * both ``models.moe.dispatch_moe`` and ``distributed.ep.moe_ep_layer``
    derive capacity from the SAME ``capacity_factor`` with the same
    formula (ceil(cf * k * T / E)) and the same GShard priority order
    (lower k-slots first, then token order);
  * both emit the same metrics dict (``expert_load``, ``dropped``,
    ``aux_loss``), with ``dropped`` masked by ``token_mask`` on both;
  * a token kept by one path is kept by the other — under forced
    overflow the dropped COUNTS and the kept token SETS agree (tested
    via equal outputs), and with no overflow greedy tokens are
    bit-identical between dispatch-prefill and EP-prefill;
  * with ``ServingEngine(expert_runtime="on")`` prefill executes
    through the EP slot data plane (no ``dispatch_moe`` call), and the
    control plane meters drops per phase off the same single host sync.

Plus the zero-replica regression: a plan that leaves an expert with no
replica must not divide by zero in the round-robin replica choice — the
assignment is routed to a valid slot, masked out, and counted dropped.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core.plan import LayerPlan, static_plan
from repro.distributed import ep as EP
from repro.launch.mesh import make_serving_mesh
from repro.models import model as M
from repro.models import moe as MOE
from repro.models import transformer as T
from repro.serving.engine import ControlPlane, ServingEngine
from repro.serving.expert_runtime import ExpertRuntime
from repro.serving.scheduler import GenRequest

KEY = jax.random.PRNGKey(11)
D, F = 16, 32


def _params(e, key=KEY):
    ks = jax.random.split(key, 2)
    return {"router": MOE.init_router(ks[0], D, e, jnp.float32),
            "experts": MOE.init_experts(ks[1], D, F, e, "swiglu",
                                        jnp.float32)}


def _single_replica_tables(e):
    return EP.plan_to_tables(static_plan(e, 1), ep=1, slots_per_device=2 * e)


def _ep(p, x, e, k, cf, tables=None, token_mask=None):
    mesh = make_serving_mesh(1, ep=1)
    tables = tables if tables is not None else _single_replica_tables(e)
    with mesh:
        slot_w = EP.materialise_slots(p["experts"], tables["slot_expert"],
                                      mesh)
        return EP.moe_ep_layer(
            x, p["router"]["w_gate"], slot_w, tables, mesh=mesh,
            num_experts=e, top_k=k, slots_per_device=2 * e,
            capacity_factor=cf, impl="ref", token_mask=token_mask)


# --------------------------------------------------- layer-level contract


@pytest.mark.parametrize("cf", [0.25, 0.5, 1.0])
def test_forced_overflow_equal_drops_and_kept_sets(cf):
    """Under forced overflow, the two paths drop the SAME count AND the
    same assignments (equal dropped scalars; allclose outputs prove the
    kept sets coincide — a differently-kept token would change y)."""
    e, k = 4, 2
    p = _params(e)
    x = jax.random.normal(jax.random.fold_in(KEY, 1), (2, 8, D),
                          jnp.float32)
    yd, md = MOE.dispatch_moe(p, x, top_k=k, num_experts=e,
                              capacity_factor=cf, impl="ref")
    ye, me = _ep(p, x, e, k, cf)
    assert float(md["dropped"]) > 0          # overflow actually forced
    assert float(md["dropped"]) == float(me["dropped"])
    np.testing.assert_array_equal(np.asarray(md["expert_load"]),
                                  np.asarray(me["expert_load"]))
    np.testing.assert_allclose(np.asarray(yd), np.asarray(ye), atol=1e-5)


def test_metrics_dicts_share_shape():
    e, k = 4, 2
    p = _params(e)
    x = jax.random.normal(jax.random.fold_in(KEY, 2), (1, 6, D),
                          jnp.float32)
    _, md = MOE.dispatch_moe(p, x, top_k=k, num_experts=e,
                             capacity_factor=1.0, impl="ref")
    _, me = _ep(p, x, e, k, 1.0)
    for key in ("expert_load", "dropped", "aux_loss"):
        assert key in md and key in me
        assert jnp.asarray(md[key]).shape == jnp.asarray(me[key]).shape


def test_no_overflow_zero_dropped_both():
    e, k = 4, 2
    p = _params(e)
    x = jax.random.normal(jax.random.fold_in(KEY, 3), (2, 8, D),
                          jnp.float32)
    _, md = MOE.dispatch_moe(p, x, top_k=k, num_experts=e,
                             capacity_factor=float(e), impl="ref")
    _, me = _ep(p, x, e, k, float(e))
    assert float(md["dropped"]) == float(me["dropped"]) == 0.0


def test_capacity_factor_is_required():
    """The per-function defaults (1.25 vs 2.0) that silently
    desynchronised the two paths are gone: capacity_factor must be
    threaded from cfg.moe.capacity_factor."""
    e, k = 4, 1
    p = _params(e)
    x = jnp.zeros((1, 4, D), jnp.float32)
    with pytest.raises(TypeError):
        MOE.dispatch_moe(p, x, top_k=k, num_experts=e, impl="ref")
    tables = _single_replica_tables(e)
    mesh = make_serving_mesh(1, ep=1)
    with mesh:
        slot_w = EP.materialise_slots(p["experts"], tables["slot_expert"],
                                      mesh)
        with pytest.raises(TypeError):
            EP.moe_ep_layer(x, p["router"]["w_gate"], slot_w, tables,
                            mesh=mesh, num_experts=e, top_k=k,
                            slots_per_device=2 * e, impl="ref")


# ------------------------------------------------- token_mask on dropped


def test_dispatch_dropped_excludes_masked_tokens():
    """Satellite: inactive continuous-batching slots occupied capacity
    AND inflated the drop metric — the mask now applies to ``dropped``
    exactly as it applies to ``expert_load``."""
    e, k = 4, 2
    p = _params(e)
    x = jax.random.normal(jax.random.fold_in(KEY, 4), (1, 8, D),
                          jnp.float32)
    mask = jnp.array([[1, 1, 1, 1, 0, 0, 0, 0]], jnp.int32)
    _, m_all = MOE.dispatch_moe(p, x, top_k=k, num_experts=e,
                                capacity_factor=0.4, impl="ref")
    _, m_mask = MOE.dispatch_moe(p, x, top_k=k, num_experts=e,
                                 capacity_factor=0.4, token_mask=mask,
                                 impl="ref")
    assert float(m_all["dropped"]) > float(m_mask["dropped"])
    # active-only run at the same capacity: compute differs (fewer
    # tokens contend), but masking never counts MORE than the total
    assert float(m_mask["dropped"]) >= 0


def test_ep_dropped_excludes_masked_tokens_and_matches_dispatch():
    e, k = 4, 2
    p = _params(e)
    x = jax.random.normal(jax.random.fold_in(KEY, 5), (1, 8, D),
                          jnp.float32)
    mask = jnp.array([[1, 1, 1, 1, 0, 0, 0, 0]], jnp.int32)
    _, md = MOE.dispatch_moe(p, x, top_k=k, num_experts=e,
                             capacity_factor=0.4, token_mask=mask,
                             impl="ref")
    _, me = _ep(p, x, e, k, 0.4, token_mask=mask)
    assert float(md["dropped"]) == float(me["dropped"])
    np.testing.assert_array_equal(np.asarray(md["expert_load"]),
                                  np.asarray(me["expert_load"]))


# ------------------------------------------------ zero-replica regression


def test_zero_replica_expert_routes_safely():
    """Regression: ``jnp.mod(..., nrep[top_i])`` was mod-by-zero when a
    plan left an expert with zero replicas. The guarded path indexes a
    valid slot, contributes nothing for that assignment, and counts it
    dropped; everything stays finite."""
    e, k = 4, 2
    p = _params(e)
    # bias the router so expert 0 is ALWAYS the top-1 choice (positive
    # inputs make the biased column's logit strictly dominate)
    p["router"]["w_gate"] = p["router"]["w_gate"].at[:, 0].add(10.0)
    x = jnp.abs(jax.random.normal(jax.random.fold_in(KEY, 6), (1, 8, D),
                                  jnp.float32)) + 0.1
    plan = LayerPlan(e, 1, np.array([0, 1, 1, 1], np.int64),
                     [[], [0], [0], [0]])
    tables = EP.plan_to_tables(plan, ep=1, slots_per_device=2 * e)
    assert int(np.asarray(tables["nrep"])[0]) == 0
    y, m = _ep(p, x, e, k, float(e), tables=tables)
    assert bool(jnp.isfinite(y).all())
    # every token's top-1 assignment (expert 0) was unservable
    assert float(m["dropped"]) == 8.0
    # the load metric still reports what the ROUTER asked for — that is
    # what the control plane needs to scale expert 0 back up
    assert int(np.asarray(m["expert_load"])[0]) == 8


# --------------------------------------------- prefill forward via EP


def _runtime_state(cfg, params, num_devices=4):
    rt = ExpertRuntime(cfg, params, num_devices=num_devices,
                       slots_per_device=2, keep_alive=1e9)
    rt.bootstrap(None)     # no prewarmed balancer: static initial plan
    return rt


@pytest.fixture(scope="module")
def smoke():
    cfg = get_config("mixtral-8x7b", smoke=True).with_(dtype="float32")
    cfg = cfg.with_(moe=dataclasses.replace(
        cfg.moe, capacity_factor=float(cfg.moe.num_experts)))
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    return cfg, params


def test_forward_ep_prefill_tokens_bit_identical(smoke):
    """No-overflow prefill parity at the ``forward`` entry point: the
    EP slot data plane (static single-replica plan on a 1-device mesh)
    and the capacity dispatch produce bit-identical greedy tokens."""
    cfg, params = smoke
    rt = _runtime_state(cfg, params)
    batch = {"tokens": jnp.asarray(
        np.random.default_rng(0).integers(0, cfg.vocab_size, (2, 16),
                                          dtype=np.int32))}
    logits_ref, m_ref = T.forward(cfg, params, batch)
    logits_ep, m_ep = T.forward(cfg, params, batch, ep_ctx=rt.ctx,
                                ep_state=rt.ep_state())
    assert float(m_ref["dropped"].sum()) == 0.0
    assert float(m_ep["dropped"].sum()) == 0.0
    np.testing.assert_array_equal(
        np.asarray(jnp.argmax(logits_ref, -1)),
        np.asarray(jnp.argmax(logits_ep, -1)))


def test_forward_ep_forced_overflow_equal_drops(smoke):
    """Forced overflow through the full stacked model: per-layer dropped
    counts from the shared capacity_factor agree between the two
    prefill paths."""
    cfg, params = smoke
    cfg_tight = cfg.with_(moe=dataclasses.replace(cfg.moe,
                                                  capacity_factor=0.25))
    rt = _runtime_state(cfg_tight, params)
    batch = {"tokens": jnp.asarray(
        np.random.default_rng(1).integers(0, cfg.vocab_size, (1, 16),
                                          dtype=np.int32))}
    _, m_ref = T.forward(cfg_tight, params, batch)
    _, m_ep = T.forward(cfg_tight, params, batch, ep_ctx=rt.ctx,
                        ep_state=rt.ep_state())
    d_ref = np.asarray(m_ref["dropped"])
    d_ep = np.asarray(m_ep["dropped"])
    assert d_ref.shape == d_ep.shape
    assert d_ref.sum() > 0
    np.testing.assert_array_equal(d_ref, d_ep)


# ------------------------------------------------- engine-level contract


def test_engine_prefill_uses_ep_plane_and_tokens_match(smoke,
                                                       monkeypatch):
    """Acceptance: with expert_runtime='on' prefill executes through
    ``moe_ep_layer`` (zero ``dispatch_moe`` calls anywhere in the
    session), greedy tokens are identical to expert_runtime='off' at
    drop-free capacity, and the control plane meters both phases."""
    cfg, params = smoke

    def mk():
        rng = np.random.default_rng(7)
        return [GenRequest(
            rid=i, arrival=0.05 * i,
            prompt=rng.integers(0, cfg.vocab_size, size=8, dtype=np.int32),
            max_new_tokens=6) for i in range(3)]

    reqs_off = mk()
    eng_off = ServingEngine(cfg, params, max_len=32)
    ctl_off = ControlPlane(cfg, "moeless", num_devices=8,
                           max_replicas_per_device=2)
    res_off = eng_off.serve(reqs_off, num_slots=3, control=ctl_off)

    calls = {"n": 0}
    orig = MOE.dispatch_moe

    def spy(*a, **kw):
        calls["n"] += 1
        return orig(*a, **kw)

    monkeypatch.setattr(MOE, "dispatch_moe", spy)
    reqs_on = mk()
    eng_on = ServingEngine(cfg, params, max_len=32, expert_runtime="on")
    ctl_on = ControlPlane(cfg, "moeless", num_devices=8,
                          max_replicas_per_device=2)
    res_on = eng_on.serve(reqs_on, num_slots=3, control=ctl_on)

    assert calls["n"] == 0          # no capacity-dispatch in the branch
    assert {r.rid: tuple(r.tokens) for r in reqs_off} \
        == {r.rid: tuple(r.tokens) for r in reqs_on}
    # both phases drove the one control plane off EP loads...
    assert ctl_on.phase_iterations["prefill"] == res_on.prefills
    assert ctl_on.phase_iterations["decode"] == res_on.iterations
    # ...and the runtime executed plans for both phases (plus bootstrap)
    ph = res_on.runtime.stats.by_phase
    assert ph["prefill"]["iterations"] == res_on.prefills
    assert ph["decode"]["iterations"] == res_on.iterations
    assert ph["bootstrap"]["transfers"] > 0
    # drop-free capacity: the metered drop count is zero on both paths
    assert res_off.dropped_tokens == res_on.dropped_tokens == 0.0


def test_engine_forced_overflow_prefill_drops_match(smoke):
    """Engine-level forced overflow: one admission, no decode — the
    prefill-phase dropped counts metered by the control plane are equal
    and positive in both modes (same shared capacity_factor)."""
    cfg, params = smoke
    cfg_tight = cfg.with_(moe=dataclasses.replace(cfg.moe,
                                                  capacity_factor=0.25))
    params_t = M.init_params(cfg_tight, jax.random.PRNGKey(0))
    prompt = np.random.default_rng(3).integers(
        0, cfg_tight.vocab_size, size=16, dtype=np.int32)

    def one(expert_runtime):
        eng = ServingEngine(cfg_tight, params_t, max_len=32,
                            expert_runtime=expert_runtime)
        ctl = ControlPlane(cfg_tight, "moeless", num_devices=8,
                           max_replicas_per_device=2)
        eng.serve([GenRequest(rid=0, arrival=0.0, prompt=prompt,
                              max_new_tokens=1)],
                  num_slots=1, control=ctl)
        return ctl.phase_dropped.get("prefill", 0.0)

    d_off, d_on = one("off"), one("on")
    assert d_off > 0
    assert d_off == d_on
