"""Expert parallelism with serverless replica slots — the paper-faithful
serving path (§2.2/§3.2): non-expert modules data/tensor-parallel, expert
*function instances* (slots) sharded over an 'ep' mesh axis, two
all-to-alls (scatter/gather) per MoE layer, and the MoEless replica plan
applied as slot tables re-programmed between iterations without
recompilation (DESIGN.md §2).

Mesh ("data", "ep", "tp"): the production 16x16 model axis factorised
into expert-parallel x tensor-parallel so architectures with E < 16
(grok-1: 8 experts) still fill 256 chips. Activations are sharded over
("data", "ep") and replicated over "tp" (TP semantics); expert weights
shard their FFN width over "tp".

Serverless slots: every EP rank owns `slots_per_device` weight slots —
the TPU analogue of function instances. ``materialise_slots`` fills them
from the expert weight bank according to the plan (the weight movement IS
the cold start; its bytes are metered). Tokens are routed to slots
round-robin over an expert's replicas (paper step 4), all-to-all'd to
the slot's rank, processed by a grouped FFN in the Pallas capacity
layout, and gathered back.
"""
from __future__ import annotations

import math
import warnings
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

def ep_factorisation(num_experts: int, model_degree: int) -> tuple[int, int]:
    ep = math.gcd(num_experts, model_degree)
    return ep, model_degree // ep


# ------------------------------------------------------------ slot tables


def device_rank(g: int, *, num_devices: int, ep: int) -> int:
    """EP mesh rank owning logical control-plane device `g`. The control
    plane plans over `num_devices` logical devices; the data plane runs
    on `ep` mesh ranks. Contiguous blocks of num_devices // ep logical
    devices map to one rank, so a plan's locality structure (ring
    neighbourhoods) survives the projection. Requires ep | num_devices —
    the controller's `ep_factorisation` (gcd) always satisfies this."""
    if num_devices % ep:
        raise ValueError(
            f"device_rank: num_devices={num_devices} is not a multiple "
            f"of ep={ep}; no block mapping of logical devices onto mesh "
            f"ranks exists")
    return (g % num_devices) // (num_devices // ep)


def plan_to_tables(plan, *, ep: int, slots_per_device: int,
                   num_devices: int | None = None):
    """LayerPlan -> routing tables (all shapes static).

    `num_devices` maps the plan's LOGICAL devices onto the `ep` mesh
    ranks explicitly via ``device_rank`` (block mapping). Without it the
    legacy `g % ep` fold is used — correct only when the plan already
    places on mesh ranks (num_devices == ep).

    A plan that asks for more replicas on a rank than `slots_per_device`
    (reachable: the Scaler is not told the per-rank slot cap) degrades
    gracefully — the overflowing replica SPILLS to the nearest rank with
    free slots, with a warning. Only a plan whose total replica count
    exceeds ep * slots_per_device is an error.

    Returns dict:
      expert_slots (E, R_max): global slot id of each replica (-1 pad)
      nrep         (E,)
      slot_expert  (ep*slots_per_device,): expert id materialised in each
                   slot (E => empty). Rank of slot s = s // slots_per_device.
    """
    e_count = plan.num_experts
    if plan.total_replicas > ep * slots_per_device:
        raise ValueError(
            f"plan places {plan.total_replicas} replicas but the slot "
            f"tables hold only {ep} ranks x {slots_per_device} slots")
    r_max = int(plan.replicas.max())
    expert_slots = -np.ones((e_count, r_max), np.int32)
    slot_expert = np.full(ep * slots_per_device, e_count, np.int32)
    used = np.zeros(ep, np.int32)
    spilled = 0
    for e in range(e_count):
        for r, g in enumerate(plan.placement[e]):
            if num_devices is not None:
                g = device_rank(int(g), num_devices=num_devices, ep=ep)
            else:
                g = g % ep
            if used[g] >= slots_per_device:
                # nearest rank (ring distance, either direction) with a
                # free slot
                g = min((int(gg) for gg in range(ep)
                         if used[gg] < slots_per_device),
                        key=lambda gg: min((gg - g) % ep, (g - gg) % ep))
                spilled += 1
            s = g * slots_per_device + used[g]
            used[g] += 1
            expert_slots[e, r] = s
            slot_expert[s] = e
    if spilled:
        warnings.warn(
            f"plan_to_tables: {spilled} replica(s) overflowed their rank "
            f"(cap {slots_per_device}/rank) and spilled to neighbours",
            RuntimeWarning, stacklevel=2)
    return {"expert_slots": jnp.asarray(expert_slots),
            "nrep": jnp.asarray(plan.replicas.astype(np.int32)),
            "slot_expert": jnp.asarray(slot_expert)}


def uniform_tables(num_experts: int, *, ep: int, slots_per_device: int):
    """Static EP (Megatron baseline): expert e in slot 0 of rank e % ep
    ... filling ranks round-robin."""
    from repro.core.plan import static_plan
    return plan_to_tables(static_plan(num_experts, ep), ep=ep,
                          slots_per_device=slots_per_device)


def pad_expert_bank(expert_weights):
    """Expert bank with one zero row appended (the empty-slot expert id
    E indexes it). Pad ONCE and reuse across iterations — re-padding the
    whole bank per materialise call was the old hot-path waste."""
    return {k: jnp.concatenate([w, jnp.zeros_like(w[:1])], axis=0)
            for k, w in expert_weights.items()}


def _slot_spec(k):
    """Sharding spec of one slot-bank leaf. Quantized banks
    (cfg.moe.slot_dtype='int8', repro.kernels.quant) carry a fp32
    `*_scale` companion per weight whose single trailing axis is the
    matmul contraction axis of its int8 partner — D (replicated) for
    w_gate/w_up, F (tp-sharded) for w_down — so each scale shards
    exactly like the axis it rescales."""
    if k == "w_down":
        return P("ep", "tp", None)
    if k == "w_down_scale":
        return P("ep", "tp")
    if k.endswith("_scale"):
        return P("ep", None)
    return P("ep", None, "tp")


def materialise_slots(expert_weights, slot_expert, mesh, *, padded=None,
                      prev=None, prev_slot_expert=None):
    """Fill the per-rank slot weight banks from the expert bank.
    expert_weights: dict w_gate/w_up (E, D, F), w_down (E, F, D), plus a
    zero row appended for empty slots. Returns dict of (S_total, ...)
    arrays sharded P('ep', None, 'tp'). The gather moves exactly the
    replica weights — the serverless cold-start traffic.

    `padded` is an optional pre-padded bank from ``pad_expert_bank``
    (skips re-padding every call). When `prev` (the previous slot banks)
    and `prev_slot_expert` are given, only slots whose resident expert
    CHANGED are gathered and written — warm slots are never re-copied
    (function locality), so an unchanged plan moves zero bytes."""
    if padded is None:
        padded = pad_expert_bank(expert_weights)
    if prev is not None and prev_slot_expert is not None:
        changed = np.flatnonzero(np.asarray(slot_expert)
                                 != np.asarray(prev_slot_expert))
        if changed.size == 0:
            return prev
        new_experts = jnp.asarray(np.asarray(slot_expert)[changed])
        idx = jnp.asarray(changed)
        out = {}
        for k, w in padded.items():
            upd = prev[k].at[idx].set(w[new_experts])
            out[k] = jax.lax.with_sharding_constraint(
                upd, NamedSharding(mesh, _slot_spec(k)))
        return out
    out = {}
    for k, w in padded.items():
        gathered = w[slot_expert]
        out[k] = jax.lax.with_sharding_constraint(
            gathered, NamedSharding(mesh, _slot_spec(k)))
    return out


# ------------------------------------------------------------ the layer


def moe_ep_layer(x, router_w, slot_w, tables, *, mesh, num_experts: int,
                 top_k: int, slots_per_device: int,
                 capacity_factor: float, act: str = "swiglu",
                 impl: str = "auto", token_mask=None,
                 pad_rows: int = 0):
    """x: (B, S, D), batch sharded P(('data', 'ep'), None, None)
    (replicated over 'tp'); B must be a multiple of data*ep.
    slot_w: dict of slot banks from materialise_slots.
    `impl` selects the grouped-FFN kernel backend for the per-rank slot
    compute (kernels.ops: auto | pallas | pallas_interpret | ref).
    `token_mask` (B, S) excludes tokens (inactive continuous-batching
    slots) from the expert-load and dropped metrics; compute is
    unaffected.
    `pad_rows` (static) marks the LAST pad_rows rows of the global
    batch as mesh-padding artifacts (the engine pads B up to a multiple
    of data*ep): they are excluded from the capacity formula AND made
    unroutable, so a padded multi-rank batch keeps/drops exactly the
    tokens its unpadded 1-device equivalent would. This is distinct
    from `token_mask`: inactive continuous-batching slots are REAL
    batch rows that occupy capacity on both data planes (metrics-only
    exclusion), while pad rows do not exist on the reference mesh at
    all.

    Capacity / drop semantics are DROP-EQUIVALENT to
    ``models.moe.dispatch_moe`` and MESH-INVARIANT: every replica slot
    gets the per-expert capacity ``ceil(capacity_factor * top_k * T /
    E)`` computed from the GLOBAL logical token count
    T = (B - pad_rows)*S (equivalence with
    the dispatch path is exact when it runs one group — extra dispatch
    groups (> 2048 tokens, ``transformer._moe_groups``) divide dispatch
    capacity per group and the counts can diverge). Each assignment's
    priority position within its slot is its GLOBAL GShard rank (lower
    k-slots everywhere first, then global token order), computed from
    all-gathered per-(k, slot) shard counts, so the kept token set is
    IDENTICAL on a (1,1,1) and a (1,4,1) mesh — keep/drop never depends
    on how tokens landed on shards. Overflow is COUNTED, not silently
    zeroed. With single-replica plans the kept set equals the capacity
    dispatch; extra replicas only ADD capacity, so a token the dispatch
    path keeps is always kept here.
    `capacity_factor` has no default on purpose — thread
    ``cfg.moe.capacity_factor`` so both data planes share one value.

    Tokens are sharded P(('data','ep')) over the BATCH axis (B must be
    a multiple of data*ep — the serving engine pads batches to this
    multiple), so each shard owns a contiguous global token range and
    shard-major order IS global token order. Per-slot send/recv blocks
    are sized to the full global capacity: the budget is global, so a
    single shard can legally hold up to `cap` survivors of one slot
    (worst-case burst); the a2a'd kept counts mark real extents so the
    kernel backends still skip the zero tail.

    Returns (y, metrics) with y sharded like x and metrics in the
    ``dispatch_moe`` shape: ``expert_load`` (E,) and ``dropped``
    (scalars psum'd over ('data','ep')), plus ``aux_loss`` (always 0 —
    the serving hot path does not pay for the full-softmax probs)."""
    # lazy import: consumers of the slot-table helpers never pull in
    # pallas-tpu
    from repro.kernels import ops as KOPS
    ep = mesh.shape["ep"]
    n_data = mesh.shape["data"]
    n_shards = n_data * ep
    sd_ = slots_per_device
    n_slots = ep * sd_
    if x.shape[0] % n_shards:
        raise ValueError(
            f"moe_ep_layer: batch {x.shape[0]} is not a multiple of "
            f"data*ep = {n_shards}; pad the batch (the serving engine "
            f"does this automatically)")
    if not 0 <= pad_rows < x.shape[0]:
        raise ValueError(f"pad_rows={pad_rows} outside [0, B={x.shape[0]})")
    # mesh-invariant capacity: the formula sees the LOGICAL token count
    # (pad rows are artifacts of this mesh's shard multiple, absent on
    # the 1-device reference)
    logical_t = (x.shape[0] - pad_rows) * x.shape[1]
    impl = KOPS.resolve_impl(impl)   # fail fast on unknown backends
    if token_mask is None:
        token_mask = jnp.ones(x.shape[:2], jnp.int32)

    # slot_w is either the native bank (w_gate/w_up/w_down) or the int8
    # quantized bank with `*_scale` companions (kernels.quant layout);
    # thread whichever keys are present through shard_map so a plan
    # change — and a slot-dtype change — never forces a different trace
    # shape for the same bank format
    wkeys = tuple(k for k in ("w_gate", "w_gate_scale", "w_up",
                              "w_up_scale", "w_down", "w_down_scale")
                  if k in slot_w)
    quantized = "w_up_scale" in wkeys

    def local(x_loc, mask_loc, rw, expert_slots, nrep, *ws):
        bank = dict(zip(wkeys, ws))
        b, s, d = x_loc.shape
        t = b * s
        xf = x_loc.reshape(t, d)
        logits = xf @ rw
        top_w, top_i = jax.lax.top_k(logits.astype(jnp.float32), top_k)
        top_w = jax.nn.softmax(top_w, -1)

        # replica choice: round robin over the expert's replicas (step
        # 4). A plan can leave an expert with zero replicas (scaler edge
        # case): guard the modulus against mod-by-zero, route the
        # assignment to slot 0 so indexing stays in bounds, and mask it
        # out below (it contributes nothing and is counted as dropped).
        tok = jnp.arange(t, dtype=jnp.int32)[:, None]
        nrep_t = nrep[top_i]                                 # (t, k)
        r_idx = jnp.mod(tok + jnp.arange(top_k, dtype=jnp.int32),
                        jnp.maximum(nrep_t, 1))
        slot = expert_slots[top_i, r_idx]                    # (t, k)
        routable = (nrep_t > 0) & (slot >= 0)
        me = jax.lax.axis_index("data") * ep + jax.lax.axis_index("ep")
        if pad_rows:
            # mesh-padding rows (the LAST pad_rows of the global batch)
            # must never consume capacity — on the 1-device reference
            # they do not exist. Shards own contiguous row ranges, so
            # this shard's global rows are [me*b, (me+1)*b).
            real_row = (me * b + jnp.arange(b, dtype=jnp.int32)
                        < b * n_shards - pad_rows)           # (b,)
            routable = routable & jnp.repeat(real_row, s)[:, None]
        slot = jnp.where(routable, slot, 0)

        # drop-equivalent capacity: dispatch_moe's per-expert formula on
        # the GLOBAL LOGICAL token count (pad rows excluded), applied
        # per SLOT (each replica carries the full per-expert capacity,
        # so replication only raises headroom). A local-count capacity
        # would make keep/drop depend on the mesh factorisation — the
        # latent 1-device-only bug this layer used to have.
        cap = max(1, math.ceil(capacity_factor * top_k * logical_t
                               / num_experts))

        # GShard priority order: flatten k-major (all k=0 assignments in
        # token order, then k=1, ...) so position-in-slot matches
        # dispatch_moe's cumsum positions and both paths drop the SAME
        # assignments. Unroutable assignments sort last (sentinel slot).
        fslot = slot.T.reshape(-1)                           # (k*t,)
        skey = jnp.where(routable.T.reshape(-1), fslot, n_slots)
        ftok = jnp.tile(jnp.arange(t, dtype=jnp.int32), top_k)
        forder = jnp.argsort(skey)                           # stable
        ssl = skey[forder]
        stok = ftok[forder]
        sw = top_w.T.reshape(-1)[forder]
        counts = jnp.bincount(ssl, length=n_slots + 1)[:n_slots]
        starts = jnp.concatenate(
            [jnp.zeros(1, jnp.int32),
             jnp.cumsum(counts).astype(jnp.int32)[:-1]])
        pos = jnp.arange(t * top_k, dtype=jnp.int32) \
            - starts[jnp.clip(ssl, 0, n_slots - 1)]

        # global GShard rank: each sorted assignment's priority position
        # within its slot across ALL shards. Shards hold contiguous
        # global token ranges (P(('data','ep')) batch sharding), so the
        # global order within a slot is (k, shard, local order). Tiny
        # per-(k, slot) count tables are all-gathered; the rank is
        #   prior-k total everywhere + same-k counts of earlier shards
        #   + local position within (k, slot).
        # On one shard this reduces exactly to `pos`.
        cnt_km = jax.vmap(
            lambda sl, rt: jnp.bincount(
                jnp.where(rt, sl, n_slots),
                length=n_slots + 1)[:n_slots])(
            slot.T, routable.T).astype(jnp.int32)            # (k, S)
        allc = jax.lax.all_gather(
            jax.lax.all_gather(cnt_km, "ep"), "data") \
            .reshape(n_shards, top_k, n_slots)               # (sh, k, S)
        tot = allc.sum(0)                                    # (k, S)
        prek = jnp.cumsum(tot, 0) - tot                      # excl k-cumsum
        before = jnp.sum(
            allc * (jnp.arange(n_shards)[:, None, None] < me), 0)
        prelk = jnp.cumsum(cnt_km, 0) - cnt_km               # local excl
        sk = jnp.repeat(jnp.arange(top_k, dtype=jnp.int32), t)[forder]
        mclip = jnp.clip(ssl, 0, n_slots - 1)
        gpos = pos + (prek + before - prelk)[sk, mclip]
        # gpos >= pos and gpos is strictly increasing along each slot's
        # local order, so keep is a prefix of the slot group: kept rows
        # stay contiguous at local positions [0, kept-count) and always
        # fit the cap-row block below.
        keep = (gpos < cap) & (ssl < n_slots)

        # pack send buffers: destination rank = slot // sd_, and the
        # buffer layout itself encodes the slot — rows [m*cap, (m+1)*cap)
        # of a rank's block belong to its local slot m, so the receiver
        # needs no sort. Every dropped/unroutable assignment writes to
        # one trash row that is sliced off before the all-to-all (a
        # clipped scatter would let a dropped zero overwrite the kept
        # row at position cap-1 — the old silent-drop corruption).
        dst = jnp.clip(ssl // sd_, 0, ep - 1)
        lpos = jnp.where(keep, (ssl % sd_) * cap + jnp.clip(pos, 0, cap - 1),
                         sd_ * cap)
        send = jnp.zeros((ep, sd_ * cap + 1, d), x_loc.dtype)
        send = send.at[dst, lpos].set(
            jnp.where(keep[:, None], xf[stok], 0.0))

        # scatter
        recv = jax.lax.all_to_all(send[:, :sd_ * cap], "ep", 0, 0)

        # local grouped FFN over this rank's slots: rows of local slot m
        # are every source rank's [m*cap, (m+1)*cap) block; empty rows
        # are zero vectors and the FFN maps them to zero. Each sender
        # also all-to-alls its kept per-slot counts (a tiny int array)
        # so group_sizes can mark each slot's occupied extent and the
        # kernel backends skip the zero tail tiles. The counts are the
        # TRUE kept counts from `keep` (not min(local count, cap) — the
        # global budget means another shard may have consumed capacity,
        # and undercounting would let `gs` cut off an occupied source
        # block at ep > 1).
        buf = recv.reshape(ep, sd_, cap, d).transpose(1, 0, 2, 3) \
            .reshape(sd_, ep * cap, d)
        kc = jnp.bincount(jnp.where(keep, ssl, n_slots),
                          length=n_slots + 1)[:n_slots] \
            .astype(jnp.int32).reshape(ep, sd_)
        recv_cnt = jax.lax.all_to_all(kc, "ep", 0, 0)       # (src, sd_)
        src = jnp.arange(ep, dtype=jnp.int32)[:, None]
        gs = jnp.max(jnp.where(recv_cnt > 0, src * cap + recv_cnt, 0),
                     axis=0)
        if quantized:
            out = KOPS.expert_ffn_quant_impl(
                buf, bank["w_gate"], bank["w_gate_scale"], bank["w_up"],
                bank["w_up_scale"], bank["w_down"], bank["w_down_scale"],
                gs, impl)
        else:
            out = KOPS.expert_ffn_impl(buf, bank["w_gate"], bank["w_up"],
                                       bank["w_down"], gs, impl)
        out = jax.lax.psum(out.astype(jnp.float32), "tp")  # f sharded on tp
        y = out.reshape(sd_, ep, cap, d).transpose(1, 0, 2, 3) \
            .reshape(ep, sd_ * cap, d)

        # gather
        back = jax.lax.all_to_all(y.astype(x_loc.dtype), "ep", 0, 0)

        # weighted combine at the source
        contrib = back[dst, jnp.clip(lpos, 0, sd_ * cap - 1)] \
            .astype(jnp.float32)
        contrib = contrib * jnp.where(keep, sw, 0.0)[:, None]
        comb = jnp.zeros((t, d), jnp.float32).at[stok].add(contrib)

        mask_flat = mask_loc.reshape(-1).astype(jnp.int32)   # (t,)
        loads = jnp.zeros(num_experts, jnp.int32).at[
            top_i.reshape(-1)].add(jnp.repeat(mask_flat, top_k))
        loads = jax.lax.psum(loads, ("data", "ep"))
        # dropped = routed assignments of ACTIVE tokens that were not
        # kept (capacity overflow or a zero-replica expert); inactive
        # continuous-batching slots never inflate the count
        active = mask_flat[stok]
        dropped = (top_k * jnp.sum(mask_flat)
                   - jnp.sum(keep * active)).astype(jnp.float32)
        dropped = jax.lax.psum(dropped, ("data", "ep"))
        return comb.reshape(b, s, d).astype(x_loc.dtype), loads, dropped

    # the replication checker is off: pallas_call has no replication
    # rule, and the psum'd loads/dropped are replicated by construction
    fn = jax.shard_map(
        local, mesh=mesh, check_vma=False,
        in_specs=(P(("data", "ep"), None, None), P(("data", "ep"), None),
                  P(), P(), P())
        + tuple(_slot_spec(k) for k in wkeys),
        out_specs=(P(("data", "ep"), None, None), P(), P()))
    y, loads, dropped = fn(
        x, token_mask, router_w, tables["expert_slots"], tables["nrep"],
        *(slot_w[k] for k in wkeys))
    return y, {"expert_load": loads, "dropped": dropped,
               "aux_loss": jnp.asarray(0.0, jnp.float32)}


# ----------------------------------------------- serving hot-path hookup


@dataclass(frozen=True)
class EPContext:
    """Static (trace-time) context for running MoE sublayers through the
    EP slot data plane inside the jitted decode step. Closed over by the
    engine's jitted step, never traced — only the slot tables/weights in
    the per-layer ``ep_state`` pytree change between iterations, so the
    replica plan is re-programmed without recompilation."""
    mesh: object
    slots_per_device: int          # PHYSICAL slots per EP mesh rank
    capacity_factor: float
    # trailing rows of the batch that are mesh-padding artifacts (the
    # engine pads B to a multiple of data*ep); they neither consume nor
    # contribute capacity, so keep/drop matches the unpadded 1-device
    # batch bit for bit. Differs per phase (prefill pads 1 -> data*ep,
    # decode pads num_slots -> the KV pool's row multiple), so the
    # engine closes a per-phase replace() of the runtime's ctx over
    # each jitted step.
    pad_rows: int = 0


def moe_ep_ffn(moe_params, h, state, ctx: EPContext, cfg,
               token_mask=None):
    """One MoE sublayer through ``moe_ep_layer`` with the runtime's live
    slot tables/weights — the drop-in replacement for
    ``models.moe.dispatch_moe`` in the batched-decode hot path.

    `state`: {'expert_slots' (E, R_cap), 'nrep' (E,), 'w_gate'/'w_up'
    (S, D, F), 'w_down' (S, F, D)} for THIS layer, maintained by
    ``serving.expert_runtime.ExpertRuntime``. Under
    ``cfg.moe.slot_dtype='int8'`` the weight leaves are int8 and carry
    fp32 ``*_scale`` companions (kernels.quant layout) — they pass
    through the same plumbing and select the dequantizing kernels.
    Returns (y, metrics) in the ``dispatch_moe`` metrics shape
    (expert_load, dropped, aux_loss)."""
    slot_w = {k: state[k]
              for k in ("w_gate", "w_gate_scale", "w_up", "w_up_scale",
                        "w_down", "w_down_scale") if k in state}
    tables = {"expert_slots": state["expert_slots"], "nrep": state["nrep"]}
    return moe_ep_layer(
        h, moe_params["router"]["w_gate"], slot_w, tables, mesh=ctx.mesh,
        num_experts=cfg.moe.num_experts, top_k=cfg.moe.top_k,
        slots_per_device=ctx.slots_per_device,
        capacity_factor=ctx.capacity_factor, act=cfg.act, impl=cfg.impl,
        token_mask=token_mask, pad_rows=ctx.pad_rows)
