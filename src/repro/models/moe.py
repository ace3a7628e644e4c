"""Mixture-of-Experts layer.

Two execution paths:
  * ``dispatch_moe`` — GShard-style grouped capacity dispatch expressed as
    einsums (differentiable, GSPMD-shardable: the dispatch contraction
    lowers to all-to-all when tokens are sharded over `data` and experts
    over `model`). Used by train steps under pjit, and by serving
    prefill/decode when the expert runtime is OFF.
  * the explicit EP path with replica slots lives in
    ``repro.distributed.ep`` (shard_map + lax.all_to_all) — the
    paper-faithful serving path with MoEless serverless replica slots;
    with ``ServingEngine(expert_runtime="on")`` BOTH prefill and decode
    run through it. The two paths share one capacity/drop semantics
    (same ``cfg.moe.capacity_factor``, same metrics dict, same kept
    token set — see ``moe_ep_layer``).

The router also emits the per-expert token-load histogram that feeds the
MoEless Expert Load Predictor / Scaler (paper §4).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def init_router(key, d: int, num_experts: int, dtype):
    return {"w_gate": jax.random.normal(key, (d, num_experts), dtype)
            / math.sqrt(d)}


def init_experts(key, d: int, f: int, num_experts: int, act: str, dtype):
    ks = jax.random.split(key, 3)
    sd, sf = 1.0 / math.sqrt(d), 1.0 / math.sqrt(f)
    p = {"w_up": jax.random.normal(ks[1], (num_experts, d, f), dtype) * sd,
         "w_down": jax.random.normal(ks[2], (num_experts, f, d), dtype) * sf}
    if act == "swiglu":
        p["w_gate"] = jax.random.normal(ks[0], (num_experts, d, f), dtype) * sd
    return p


def router_topk(logits, top_k: int):
    """Returns (weights (T,k) softmax-normalised over the selected experts,
    indices (T,k), full softmax probs (T,E))."""
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    top_w, top_i = jax.lax.top_k(logits.astype(jnp.float32), top_k)
    top_w = jax.nn.softmax(top_w, axis=-1)
    return top_w, top_i, probs


def expert_loads(top_i, num_experts: int, token_mask=None):
    """Token count per expert — the paper's W_{l,e} (§3.3). `token_mask`
    (T,) excludes tokens (e.g. inactive continuous-batching slots)."""
    oh = jax.nn.one_hot(top_i, num_experts, dtype=jnp.int32)  # (T,k,E)
    if token_mask is not None:
        oh = oh * token_mask.reshape(-1, 1, 1).astype(jnp.int32)
    return oh.sum(axis=(0, 1))


def load_balance_loss(probs, top_i, num_experts: int):
    """Switch-Transformer auxiliary loss: E * sum_e f_e * p_e."""
    sel = jax.nn.one_hot(top_i[..., 0], num_experts, dtype=jnp.float32)
    f = sel.mean(axis=0)
    p = probs.mean(axis=0)
    return num_experts * jnp.sum(f * p)


def experts_ffn(p, x, act: str, *, group_sizes=None, impl: str = "ref"):
    """x: (E, N, D) -> (E, N, D), grouped per-expert FFN through the
    kernels.ops backend selector. `group_sizes` (E,) marks rows beyond
    it as padding (outputs zeroed; the Pallas backends neither copy nor
    compute the row tiles there). None => all rows active.

    `p` may be a native-dtype bank ({w_gate, w_up, w_down}) or an int8
    quantized slot bank carrying `*_scale` companions
    (repro.kernels.quant layout, cfg.moe.slot_dtype='int8'); the
    quantized form routes through the dequantizing kernel family so the
    fp32 weights never materialise in HBM."""
    # lazy import: consumers of the jnp-only model paths never pull in
    # pallas-tpu
    from repro.kernels import ops as OPS
    if group_sizes is None:
        group_sizes = jnp.full((x.shape[0],), x.shape[1], jnp.int32)
    if "w_up_scale" in p:
        if act == "swiglu":
            return OPS.expert_ffn_quant_impl(
                x, p["w_gate"], p["w_gate_scale"], p["w_up"],
                p["w_up_scale"], p["w_down"], p["w_down_scale"],
                group_sizes, impl)
        h = jax.nn.gelu(OPS.gmm_quant_impl(x, p["w_up"], p["w_up_scale"],
                                           group_sizes, impl))
        return OPS.gmm_quant_impl(h, p["w_down"], p["w_down_scale"],
                                  group_sizes, impl)
    if act == "swiglu":
        return OPS.expert_ffn_impl(x, p["w_gate"], p["w_up"], p["w_down"],
                                   group_sizes, impl)
    h = jax.nn.gelu(OPS.gmm_impl(x, p["w_up"], group_sizes, impl))
    return OPS.gmm_impl(h, p["w_down"], group_sizes, impl)


def dispatch_moe(p, x, *, top_k: int, num_experts: int,
                 capacity_factor: float, act: str = "swiglu",
                 groups: int = 1, token_mask=None, impl: str = "ref"):
    """Grouped capacity dispatch (GShard).

    x: (B, S, D). Tokens are flattened and split into `groups` dispatch
    groups (set groups = number of data shards so each group's dispatch
    tensor stays local); capacity C = ceil(cf * k * Tg / E) per group.
    `capacity_factor` has no default on purpose: it must be threaded
    from ``cfg.moe.capacity_factor`` so this path and the EP slot data
    plane (``distributed.ep.moe_ep_layer``) share ONE capacity/drop
    semantics — the two used to default to different values (1.25 vs
    2.0), silently desynchronising their drop behaviour.
    `token_mask` (B, S) marks tokens whose routing should be EXCLUDED
    from the expert-load and dropped metrics (inactive
    continuous-batching slots) — compute is unaffected. The expert FFN
    over the capacity layout runs through the `impl` kernel backend
    (kernels.ops). Returns (y, metrics) where metrics carries the
    expert-load histogram, the dropped-assignment count, and aux loss.
    """
    b, s, d = x.shape
    t = b * s
    groups = max(1, min(groups, t))
    while t % groups:
        groups -= 1
    tg = t // groups
    cap = max(1, math.ceil(capacity_factor * top_k * tg / num_experts))
    xg = x.reshape(groups, tg, d)

    logits = jnp.einsum("gtd,de->gte", xg, p["router"]["w_gate"])
    top_w, top_i, probs = router_topk(
        logits.reshape(t, num_experts), top_k)
    top_w = top_w.reshape(groups, tg, top_k)
    top_i = top_i.reshape(groups, tg, top_k)

    # position-in-expert with priority to lower k-slots (GShard order)
    sel = jax.nn.one_hot(top_i, num_experts, dtype=jnp.float32)  # (g,t,k,e)
    sel_flat = sel.transpose(0, 2, 1, 3).reshape(groups, top_k * tg,
                                                 num_experts)
    pos = jnp.cumsum(sel_flat, axis=1) - 1.0
    pos = pos.reshape(groups, top_k, tg, num_experts).transpose(0, 2, 1, 3)
    keep = (pos < cap) & (sel > 0)
    pos = jnp.clip(pos, 0, cap - 1).astype(jnp.int32)

    disp = jax.nn.one_hot(pos, cap, dtype=x.dtype) * \
        keep[..., None].astype(x.dtype)            # (g, t, k, e, c)
    disp_te = disp.sum(axis=2)                     # (g, t, e, c)
    comb = (disp * top_w[..., None, None].astype(x.dtype)).sum(axis=2)

    expert_in = jnp.einsum("gtec,gtd->egcd", disp_te, xg)
    # capacity-layout group sizes for the kernel: with one dispatch group
    # the kept rows of every expert form a contiguous prefix (GShard
    # cumsum positions), so the Pallas backends can skip/mask the tail;
    # with several groups the prefixes interleave per group, so all rows
    # stay active (unused rows are zero vectors -> FFN output is zero).
    if groups == 1:
        gs = keep.sum(axis=(1, 2))[0].astype(jnp.int32)          # (E,)
    else:
        gs = jnp.full((num_experts,), groups * cap, jnp.int32)
    expert_out = experts_ffn(p["experts"],
                             expert_in.reshape(num_experts, groups * cap, d),
                             act, group_sizes=gs,
                             impl=impl).reshape(num_experts, groups, cap, d)
    y = jnp.einsum("gtec,egcd->gtd", comb, expert_out)

    # dropped = routed assignments of ACTIVE tokens that overflowed
    # capacity. Inactive continuous-batching slots still OCCUPY capacity
    # (compute is mask-free, same as the EP data plane) but must not
    # inflate the drop metric the control plane meters.
    kept_per_tok = keep.astype(jnp.float32).sum(axis=(2, 3))  # (g, tg)
    if token_mask is None:
        dropped = jnp.asarray(top_k * t, jnp.float32) - kept_per_tok.sum()
    else:
        am = token_mask.reshape(groups, tg).astype(jnp.float32)
        dropped = top_k * am.sum() - (kept_per_tok * am).sum()
    metrics = {
        "expert_load": expert_loads(
            top_i.reshape(t, top_k), num_experts,
            None if token_mask is None else token_mask.reshape(t)),
        "aux_loss": load_balance_loss(probs, top_i.reshape(t, top_k),
                                      num_experts),
        "dropped": dropped,
        "router_logits": logits.reshape(t, num_experts),
    }
    return y.reshape(b, s, d), metrics


def init_moe(key, d: int, spec, act: str, dtype):
    k1, k2 = jax.random.split(key)
    return {"router": init_router(k1, d, spec.num_experts, dtype),
            "experts": init_experts(k2, d, spec.d_ff, spec.num_experts, act,
                                    dtype)}
