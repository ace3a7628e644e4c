"""Cross-implementation parity harness for the `impl` kernel-backend knob.

The same routed batch runs through every execution path of the MoE hot
spot — {capacity dispatch + ref FFN, capacity dispatch + Pallas
interpret FFN, GShard einsum dispatch semantics (dense per-token
oracle), EP shard_map path} — and must produce allclose outputs with
IDENTICAL per-expert load histograms, swept over adversarial shapes:
capacity not a multiple of the 128 kernel block, empty experts
(group_sizes == 0), E == 1, top_k == E, and capacity-overflow drops.

Property tests (hypothesis, optional dep): token-permutation
equivariance of the dispatch path and replica-count invariance of the
EP combined outputs.

Quantized lane (cfg.moe.slot_dtype='int8', kernels.quant): the
dequantizing kernel family must be ref==interpret EXACT, match the
fp32 kernels within the stated tolerance (per-row int8 rounding:
|w - deq(q)| <= max|row|/254, ~0.4% of the row amax), and leave greedy
tokens unchanged on the engine smoke config.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.distributed import ep as EP
from repro.core.placer import place_layer
from repro.core.plan import static_plan
from repro.core.scaler import scale_layer
from repro.kernels import quant as QT
from repro.launch.mesh import make_serving_mesh
from repro.models import model as M
from repro.models import moe as MOE

KEY = jax.random.PRNGKey(3)
D, F = 16, 32


def _params(e, d=D, f=F, dead_experts=(), key=KEY):
    """Router + expert weights; experts in `dead_experts` get a constant
    strongly-negative router column so that POSITIVE inputs never route
    to them (deterministically empty -> group_sizes == 0 downstream)."""
    ks = jax.random.split(key, 2)
    p = {"router": MOE.init_router(ks[0], d, e, jnp.float32),
         "experts": MOE.init_experts(ks[1], d, f, e, "swiglu", jnp.float32)}
    for j in dead_experts:
        p["router"]["w_gate"] = p["router"]["w_gate"].at[:, j].set(-10.0)
    return p


def _mk_case(case, fold):
    """(p, x, e, k, cf) for a named adversarial case."""
    e, k, (b, s), cf, dead = CASES[case][:5]
    p = _params(e, dead_experts=dead, key=jax.random.fold_in(KEY, fold))
    x = jax.random.normal(jax.random.fold_in(KEY, fold + 100), (b, s, D),
                          jnp.float32)
    if dead:   # positive inputs make the dead-column logits strictly min
        x = jnp.abs(x) + 0.1
    return p, x, e, k, cf


def _dense_oracle(p, x, e, k):
    """Per-token loop-over-experts reference (no capacity, no drops)."""
    logits = jnp.einsum("bsd,de->bse", x, p["router"]["w_gate"])
    tw, ti = jax.lax.top_k(logits.astype(jnp.float32), k)
    tw = jax.nn.softmax(tw, -1)
    out = jnp.zeros(x.shape, jnp.float32)
    w = p["experts"]
    for ei in range(e):
        fe = (jax.nn.silu(x @ w["w_gate"][ei]) * (x @ w["w_up"][ei])) \
            @ w["w_down"][ei]
        for kk in range(k):
            out += jnp.where((ti[..., kk] == ei)[..., None],
                             tw[..., kk:kk + 1] * fe.astype(jnp.float32),
                             0.0)
    loads = np.asarray(jnp.bincount(ti.reshape(-1), length=e))
    return np.asarray(out), loads


def _ep_path(p, x, e, k, impl):
    """The shard_map EP data plane on a 1-device ('data','ep','tp') mesh
    (exercises pack / all_to_all / grouped-FFN / combine end-to-end)."""
    mesh = make_serving_mesh(1, ep=1)
    spd = 2 * e
    tables = EP.plan_to_tables(static_plan(e, 1), ep=1,
                               slots_per_device=spd)
    with mesh:
        slot_w = EP.materialise_slots(p["experts"], tables["slot_expert"],
                                      mesh)
        y, m = EP.moe_ep_layer(
            x, p["router"]["w_gate"], slot_w, tables, mesh=mesh,
            num_experts=e, top_k=k, slots_per_device=spd,
            capacity_factor=float(e), impl=impl)
    return np.asarray(y, np.float32), np.asarray(m["expert_load"])


# name -> (E, top_k, (B, S), capacity_factor, dead_experts, drops_possible)
CASES = {
    "cap_not_mxu_aligned": (4, 2, (2, 7), 1.0, (), True),
    "empty_expert": (5, 1, (2, 8), 5.0, (4,), False),
    "single_expert": (1, 1, (2, 6), 1.0, (), False),
    "topk_equals_E": (4, 4, (2, 5), 4.0, (), False),
    "capacity_overflow": (4, 2, (2, 8), 0.4, (), True),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_dispatch_backends_interchangeable(case):
    """ref and pallas_interpret FFN backends under the SAME capacity
    dispatch: allclose outputs, identical histograms — including under
    drops (identical routing => identical drop set)."""
    p, x, e, k, cf = _mk_case(case, 1)
    y_ref, m_ref = MOE.dispatch_moe(p, x, top_k=k, num_experts=e,
                                    capacity_factor=cf, impl="ref")
    y_pi, m_pi = MOE.dispatch_moe(p, x, top_k=k, num_experts=e,
                                  capacity_factor=cf,
                                  impl="pallas_interpret")
    np.testing.assert_allclose(np.asarray(y_ref), np.asarray(y_pi),
                               atol=1e-4)
    np.testing.assert_array_equal(np.asarray(m_ref["expert_load"]),
                                  np.asarray(m_pi["expert_load"]))
    assert float(m_ref["dropped"]) == float(m_pi["dropped"])
    if case == "empty_expert":
        assert int(np.asarray(m_ref["expert_load"])[-1]) == 0
    if case == "capacity_overflow":
        assert float(m_ref["dropped"]) > 0


@pytest.mark.parametrize("case",
                         [c for c, v in sorted(CASES.items()) if not v[5]])
def test_all_paths_match_dense_oracle(case):
    """With ample capacity every path — dense oracle, einsum dispatch
    with either FFN backend, and the EP shard_map path — agrees in value
    AND per-expert load histogram. (EP x pallas_interpret crossings are
    covered by the regression test below and the slow nightly sweep:
    each shard_map compile costs ~15 s on CPU.)"""
    p, x, e, k, cf = _mk_case(case, 2)
    y_dense, loads_dense = _dense_oracle(p, x, e, k)

    for impl in ("ref", "pallas_interpret"):
        y, m = MOE.dispatch_moe(p, x, top_k=k, num_experts=e,
                                capacity_factor=cf, impl=impl)
        assert float(m["dropped"]) == 0.0
        np.testing.assert_allclose(np.asarray(y), y_dense, atol=1e-4)
        np.testing.assert_array_equal(np.asarray(m["expert_load"]),
                                      loads_dense)

    y_ep, loads_ep = _ep_path(p, x, e, k, "ref")
    np.testing.assert_allclose(y_ep, y_dense, atol=1e-4)
    np.testing.assert_array_equal(loads_ep, loads_dense)


@pytest.mark.slow
@pytest.mark.parametrize("case",
                         [c for c, v in sorted(CASES.items()) if not v[5]])
def test_ep_interpret_matches_dense_oracle_sweep(case):
    """Nightly: the EP shard_map path with the Pallas interpret backend
    over the full no-drop adversarial sweep."""
    p, x, e, k, _ = _mk_case(case, 2)
    y_dense, loads_dense = _dense_oracle(p, x, e, k)
    y_ep, loads_ep = _ep_path(p, x, e, k, "pallas_interpret")
    np.testing.assert_allclose(y_ep, y_dense, atol=1e-4)
    np.testing.assert_array_equal(loads_ep, loads_dense)


def test_ep_impl_regression_ref_vs_interpret():
    """Satellite regression: `impl` on moe_ep_layer is honored — 'ref'
    and 'pallas_interpret' agree through the shard_map EP path on a CPU
    mesh (the parameter used to be accepted and ignored)."""
    e, k = 4, 2
    p = _params(e, key=jax.random.fold_in(KEY, 7))
    x = jax.random.normal(jax.random.fold_in(KEY, 8), (2, 6, D),
                          jnp.float32)
    y_ref, l_ref = _ep_path(p, x, e, k, "ref")
    y_pi, l_pi = _ep_path(p, x, e, k, "pallas_interpret")
    np.testing.assert_allclose(y_ref, y_pi, atol=1e-4)
    np.testing.assert_array_equal(l_ref, l_pi)


def test_ep_replica_count_invariance():
    """Combined outputs are invariant to how many replicas each expert
    gets (round-robin replica choice only changes WHERE compute runs)."""
    e, k = 4, 2
    p = _params(e, key=jax.random.fold_in(KEY, 9))
    x = jax.random.normal(jax.random.fold_in(KEY, 10), (2, 8, D),
                          jnp.float32)
    mesh = make_serving_mesh(1, ep=1)
    loads = np.array([40.0, 10.0, 5.0, 5.0])
    plans = [static_plan(e, 1),
             place_layer(loads, scale_layer(loads, max_total_replicas=7),
                         1, max_replicas_per_device=2 * e)]
    outs = []
    for plan in plans:
        tables = EP.plan_to_tables(plan, ep=1, slots_per_device=2 * e)
        with mesh:
            slot_w = EP.materialise_slots(p["experts"],
                                          tables["slot_expert"], mesh)
            y, _ = EP.moe_ep_layer(
                x, p["router"]["w_gate"], slot_w, tables, mesh=mesh,
                num_experts=e, top_k=k, slots_per_device=2 * e,
                capacity_factor=2.0, impl="ref")
        outs.append(np.asarray(y, np.float32))
    assert plans[1].total_replicas > plans[0].total_replicas
    np.testing.assert_allclose(outs[0], outs[1], atol=1e-5)


def test_serve_trace_generates_identical_tokens_across_impls():
    """Acceptance: the real-model serving path produces identical greedy
    tokens under impl='ref' and impl='pallas_interpret' (exercises both
    the MoE kernel in prefill/decode and the decode-attention kernel)."""
    from repro.serving.engine import ServingEngine
    from repro.serving.scheduler import GenRequest

    cfg = get_config("mixtral-8x7b", smoke=True).with_(dtype="float32")
    params = M.init_params(cfg, jax.random.fold_in(KEY, 11))
    rng = np.random.default_rng(0)

    def run(impl):
        reqs = [GenRequest(rid=i, arrival=0.0,
                           prompt=rng.integers(0, cfg.vocab_size, size=6,
                                               dtype=np.int32),
                           max_new_tokens=4) for i in range(2)]
        engine = ServingEngine(cfg, params, max_len=24, impl=impl)
        res = engine.serve(reqs, num_slots=2)
        assert len(res.records) == len(reqs)
        return {r.rid: list(r.tokens) for r in reqs}

    # identical request objects per run (rng reseeded via fresh generator)
    rng = np.random.default_rng(0)
    toks_ref = run("ref")
    rng = np.random.default_rng(0)
    toks_pi = run("pallas_interpret")
    assert toks_ref == toks_pi
    assert all(len(t) > 0 for t in toks_ref.values())


# ------------------------------------------------------- quantized lane


def _quantized(p):
    return {"router": p["router"],
            "experts": QT.quantize_expert_bank(p["experts"])}


def test_quantize_rows_error_bound():
    """Symmetric per-row int8: |w - deq(q)| <= amax_row / 254 (half a
    quantization step), exactly zero for all-zero rows — the tolerance
    contract every downstream allclose leans on."""
    w = jax.random.normal(jax.random.fold_in(KEY, 20), (3, 8, 16),
                          jnp.float32)
    w = w.at[1, 3].set(0.0)
    q, s = QT.quantize_rows(w)
    assert q.dtype == jnp.int8 and s.dtype == jnp.float32
    deq = QT.dequantize_rows(q, s)
    amax = np.asarray(jnp.max(jnp.abs(w), axis=-1))
    err = np.asarray(jnp.abs(deq - w))
    assert (err <= amax[..., None] / 254 + 1e-7).all()
    np.testing.assert_array_equal(np.asarray(deq[1, 3]), 0.0)
    # idempotence: re-quantizing a quantized bank is the identity
    bank = {"w_up": w}
    qb = QT.quantize_expert_bank(bank)
    assert QT.quantize_expert_bank(qb) is qb


@pytest.mark.parametrize("case", sorted(CASES))
def test_quant_backends_exact_ref_vs_interpret(case):
    """The dequantizing kernels are EXACTLY equal between 'ref' and
    'pallas_interpret' under the same capacity dispatch (both dequantize
    to f32 then matmul; single contraction tile at these shapes)."""
    p, x, e, k, cf = _mk_case(case, 3)
    pq = _quantized(p)
    y_ref, m_ref = MOE.dispatch_moe(pq, x, top_k=k, num_experts=e,
                                    capacity_factor=cf, impl="ref")
    y_pi, m_pi = MOE.dispatch_moe(pq, x, top_k=k, num_experts=e,
                                  capacity_factor=cf,
                                  impl="pallas_interpret")
    np.testing.assert_allclose(np.asarray(y_ref), np.asarray(y_pi),
                               atol=1e-5)
    np.testing.assert_array_equal(np.asarray(m_ref["expert_load"]),
                                  np.asarray(m_pi["expert_load"]))
    assert float(m_ref["dropped"]) == float(m_pi["dropped"])


@pytest.mark.parametrize("case", sorted(CASES))
def test_quant_dispatch_close_to_fp32(case):
    """Quantized-vs-fp32 expert FFN through the capacity dispatch:
    same routing (the router is NOT quantized => identical histograms
    and drops), outputs within the int8 rounding tolerance."""
    p, x, e, k, cf = _mk_case(case, 4)
    y, m = MOE.dispatch_moe(p, x, top_k=k, num_experts=e,
                            capacity_factor=cf, impl="ref")
    yq, mq = MOE.dispatch_moe(_quantized(p), x, top_k=k, num_experts=e,
                              capacity_factor=cf, impl="ref")
    np.testing.assert_array_equal(np.asarray(m["expert_load"]),
                                  np.asarray(mq["expert_load"]))
    assert float(m["dropped"]) == float(mq["dropped"])
    np.testing.assert_allclose(np.asarray(yq), np.asarray(y), atol=5e-2)


def test_quant_ep_path_matches_fp32():
    """The EP shard_map path accepts the quantized slot bank through the
    same plumbing (scale leaves shard with their weights) and matches
    the fp32 EP output within tolerance, with identical loads."""
    e, k = 4, 2
    p = _params(e, key=jax.random.fold_in(KEY, 21))
    x = jax.random.normal(jax.random.fold_in(KEY, 22), (2, 6, D),
                          jnp.float32)
    mesh = make_serving_mesh(1, ep=1)
    spd = 2 * e
    tables = EP.plan_to_tables(static_plan(e, 1), ep=1,
                               slots_per_device=spd)
    outs = {}
    for name, bank in (("fp32", p["experts"]),
                       ("int8", QT.quantize_expert_bank(p["experts"]))):
        with mesh:
            slot_w = EP.materialise_slots(bank, tables["slot_expert"],
                                          mesh)
            y, m = EP.moe_ep_layer(
                x, p["router"]["w_gate"], slot_w, tables, mesh=mesh,
                num_experts=e, top_k=k, slots_per_device=spd,
                capacity_factor=float(e), impl="ref")
        outs[name] = (np.asarray(y, np.float32),
                      np.asarray(m["expert_load"]))
    np.testing.assert_array_equal(outs["fp32"][1], outs["int8"][1])
    np.testing.assert_allclose(outs["int8"][0], outs["fp32"][0],
                               atol=5e-2)


def test_engine_greedy_tokens_stable_under_int8_slots():
    """Acceptance: the full serving engine with the expert runtime ON
    emits IDENTICAL greedy tokens whether the slot banks are fp32 or
    int8 — the int8 rounding perturbation stays below the greedy argmax
    margins of the smoke config."""
    from repro.serving.engine import MoElessController, ServingEngine
    from repro.serving.scheduler import GenRequest

    base = get_config("mixtral-8x7b", smoke=True).with_(dtype="float32")
    params = M.init_params(base, jax.random.fold_in(KEY, 12))

    def run(slot_dtype):
        cfg = base.with_(moe=dataclasses.replace(
            base.moe, slot_dtype=slot_dtype))
        rng = np.random.default_rng(0)
        reqs = [GenRequest(rid=i, arrival=0.0,
                           prompt=rng.integers(0, cfg.vocab_size, size=6,
                                               dtype=np.int32),
                           max_new_tokens=4) for i in range(2)]
        engine = ServingEngine(cfg, params, max_len=24,
                               expert_runtime="on")
        ctrl = MoElessController(cfg, num_devices=4)
        res = engine.serve(reqs, num_slots=2, control=ctrl)
        assert len(res.records) == len(reqs)
        st = res.runtime.finalize(res.clock_s)
        return {r.rid: list(r.tokens) for r in reqs}, st

    toks32, st32 = run("fp32")
    toks8, st8 = run("int8")
    assert toks32 == toks8
    assert all(len(t) > 0 for t in toks32.values())
    # the headline byte contract rides along: int8 cold starts move
    # <= 0.30x the fp32 bytes
    assert st8.transfers == st32.transfers
    assert st8.bytes_moved <= 0.30 * st32.bytes_moved


# ------------------------------------------------------------ properties
# hypothesis is optional: only the property tests skip without it (a
# module-level importorskip would silence the whole parity harness)

try:
    from hypothesis import given, settings, strategies as st
    _HAS_HYPOTHESIS = True
except ImportError:                                    # pragma: no cover
    _HAS_HYPOTHESIS = False

    def _identity_deco(*a, **k):
        return lambda f: f
    given = settings = _identity_deco

    class st:                                          # noqa: N801
        @staticmethod
        def integers(*a, **k):
            return None

        @staticmethod
        def floats(*a, **k):
            return None

        @staticmethod
        def lists(*a, **k):
            return None


needs_hypothesis = pytest.mark.skipif(
    not _HAS_HYPOTHESIS, reason="hypothesis not installed")


@needs_hypothesis
@given(st.integers(0, 2 ** 31 - 1), st.integers(2, 6),
       st.integers(1, 3))
@settings(max_examples=15, deadline=None)
def test_token_permutation_equivariance(seed, e, k):
    """With ample capacity, permuting the tokens permutes the outputs —
    routing is per-token, so the dispatch machinery must not couple
    tokens. Holds for both FFN backends by the parity tests above."""
    k = min(k, e)
    key = jax.random.PRNGKey(seed)
    p = _params(e, key=key)
    t = 12
    x = jax.random.normal(jax.random.fold_in(key, 1), (1, t, D),
                          jnp.float32)
    perm = jax.random.permutation(jax.random.fold_in(key, 2), t)
    y, m = MOE.dispatch_moe(p, x, top_k=k, num_experts=e,
                            capacity_factor=float(e), impl="ref")
    yp, mp = MOE.dispatch_moe(p, x[:, perm], top_k=k, num_experts=e,
                              capacity_factor=float(e), impl="ref")
    assert float(m["dropped"]) == float(mp["dropped"]) == 0.0
    np.testing.assert_allclose(np.asarray(yp), np.asarray(y)[:, perm],
                               atol=1e-4)
    np.testing.assert_array_equal(np.asarray(m["expert_load"]),
                                  np.asarray(mp["expert_load"]))


@needs_hypothesis
@pytest.mark.slow
@given(st.integers(0, 2 ** 31 - 1),
       st.lists(st.floats(1.0, 100.0), min_size=4, max_size=4))
@settings(max_examples=5, deadline=None)
def test_ep_replica_invariance_property(seed, loads):
    """EP combined outputs are invariant to the replica plan for ANY
    scaled placement the control plane can emit."""
    e, k = 4, 2
    key = jax.random.PRNGKey(seed)
    p = _params(e, key=key)
    x = jax.random.normal(jax.random.fold_in(key, 1), (1, 6, D),
                          jnp.float32)
    base = _ep_path(p, x, e, k, "ref")[0]
    loads = np.asarray(loads)
    plan = place_layer(loads, scale_layer(loads, max_total_replicas=8),
                       1, max_replicas_per_device=2 * e)
    mesh = make_serving_mesh(1, ep=1)
    tables = EP.plan_to_tables(plan, ep=1, slots_per_device=2 * e)
    with mesh:
        slot_w = EP.materialise_slots(p["experts"],
                                      tables["slot_expert"], mesh)
        y, _ = EP.moe_ep_layer(
            x, p["router"]["w_gate"], slot_w, tables, mesh=mesh,
            num_experts=e, top_k=k, slots_per_device=2 * e,
            capacity_factor=2.0, impl="ref")
    np.testing.assert_allclose(np.asarray(y, np.float32), base, atol=1e-5)
