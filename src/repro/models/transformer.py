"""Model assembly: stacked-parameter transformer with lax.scan over layer
*periods*.

A "period" is the smallest repeating pattern of sublayers (1 for uniform
models; 8 for Jamba's 1:7 attn:mamba interleave with MoE every 2; 2 for
xLSTM's mLSTM/sLSTM alternation). Parameters of sublayer j are stacked
over num_periods, so the whole depth lowers as ONE scan — HLO size is
independent of depth, which is what makes the 80-layer dry-runs cheap.

Entry points:
  init_params(cfg, key)                      -> params
  forward(cfg, params, batch)                -> (logits, metrics)      # train/prefill
  decode_step(cfg, params, batch, cache)     -> (logits, new_cache)    # 1 token
  init_cache(cfg, params, batch, max_len)    -> cache pytree
"""
from __future__ import annotations

import math
from dataclasses import dataclass

import jax
import jax.numpy as jnp

from repro.models import layers as L
from repro.models import moe as MOE
from repro.models import ssm as S


@dataclass(frozen=True)
class SubLayer:
    mixer: str          # attn | mamba | mlstm | slstm
    ffn: str            # dense | moe | none
    cross_attn: bool = False


def layer_pattern(cfg) -> list[SubLayer]:
    """The repeating sublayer pattern (one period) for a config."""
    if cfg.family == "ssm":                      # xLSTM: mLSTM/sLSTM blocks
        period = cfg.ssm.slstm_every
        return [SubLayer("slstm" if (i % period == period - 1) else "mlstm",
                         "none") for i in range(period)]
    if cfg.family == "hybrid":                   # Jamba
        pa = cfg.attn_every_n
        pm = cfg.moe.every_n_layers if cfg.moe else 1
        period = max(pa, pm)
        while period % pa or period % pm:
            period += 1
        return [SubLayer("attn" if (i % pa == pa // 2) else "mamba",
                         "moe" if (i % pm == pm - 1) else "dense")
                for i in range(period)]
    if cfg.is_moe and cfg.moe.every_n_layers > 1:
        pm = cfg.moe.every_n_layers
        return [SubLayer("attn", "moe" if (i % pm == pm - 1) else "dense")
                for i in range(pm)]
    ffn = "moe" if cfg.is_moe else "dense"
    return [SubLayer("attn", ffn)]


def _sinusoidal(seq_len: int, d: int):
    pos = jnp.arange(seq_len, dtype=jnp.float32)[:, None]
    dim = jnp.arange(d // 2, dtype=jnp.float32)[None, :]
    ang = pos / jnp.power(10000.0, 2 * dim / d)
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)


# ---------------------------------------------------------------- init


def _init_sublayer(key, cfg, sub: SubLayer, dtype):
    ks = jax.random.split(key, 6)
    p = {"norm1": L.init_norm(ks[0], cfg.d_model, cfg.norm, dtype)}
    if sub.mixer == "attn":
        p["attn"] = L.init_attention(ks[1], cfg, dtype)
    elif sub.mixer == "mamba":
        p["mamba"] = S.init_mamba(ks[1], cfg.d_model, cfg.ssm, dtype)
    elif sub.mixer == "mlstm":
        p["mlstm"] = S.init_mlstm(ks[1], cfg.d_model, cfg.num_heads,
                                  cfg.ssm.expand, dtype)
    elif sub.mixer == "slstm":
        p["slstm"] = S.init_slstm(ks[1], cfg.d_model, cfg.num_heads, dtype)
    if sub.cross_attn:
        p["norm_x"] = L.init_norm(ks[2], cfg.d_model, cfg.norm, dtype)
        p["xattn"] = L.init_attention(ks[3], cfg, dtype)
    if sub.ffn != "none":
        p["norm2"] = L.init_norm(ks[4], cfg.d_model, cfg.norm, dtype)
        if sub.ffn == "moe":
            p["moe"] = MOE.init_moe(ks[5], cfg.d_model, cfg.moe, cfg.act,
                                    dtype)
        else:
            p["ffn"] = L.init_ffn(ks[5], cfg.d_model, cfg.d_ff, cfg.act,
                                  dtype)
    return p


def _stack_layers(key, cfg, pattern, num_periods: int, dtype):
    """Returns a list (one per sublayer in the pattern) of param dicts whose
    leaves are stacked over num_periods."""
    out = []
    for j, sub in enumerate(pattern):
        keys = jax.random.split(jax.random.fold_in(key, j), num_periods)
        stacked = jax.vmap(
            lambda k: _init_sublayer(k, cfg, sub, dtype))(keys)
        out.append(stacked)
    return out


def init_params(cfg, key):
    dtype = jnp.dtype(cfg.dtype)
    pattern = layer_pattern(cfg)
    assert cfg.num_layers % len(pattern) == 0, \
        f"{cfg.name}: num_layers={cfg.num_layers} not divisible by " \
        f"period={len(pattern)}"
    np_ = cfg.num_layers // len(pattern)
    k_emb, k_layers, k_head, k_enc, k_fin = jax.random.split(key, 5)
    params = {
        "embed": jax.random.normal(k_emb, (cfg.padded_vocab, cfg.d_model),
                                   dtype) * 0.02,
        "layers": _stack_layers(k_layers, cfg, pattern, np_, dtype),
        "final_norm": L.init_norm(k_fin, cfg.d_model, cfg.norm, dtype),
    }
    if not cfg.tie_embeddings:
        params["head"] = jax.random.normal(
            k_head, (cfg.d_model, cfg.padded_vocab), dtype) \
            / math.sqrt(cfg.d_model)
    if cfg.encdec is not None:
        enc_pattern = [SubLayer("attn", "dense")]
        params["encoder"] = {
            "layers": _stack_layers(k_enc, cfg, enc_pattern,
                                    cfg.encdec.num_encoder_layers, dtype),
            "final_norm": L.init_norm(k_fin, cfg.d_model, cfg.norm, dtype),
        }
        # decoder sublayers get cross-attention
        dec_pattern = [SubLayer("attn", "dense", cross_attn=True)]
        params["layers"] = _stack_layers(k_layers, cfg, dec_pattern, np_,
                                         dtype)
    return params


# ---------------------------------------------------------------- forward


def _apply_sublayer(cfg, sub: SubLayer, p, x, positions, *, cache=None,
                    cache_len=None, enc_out=None, window=0,
                    collect: bool = False, token_mask=None,
                    ep_ctx=None, ep_state=None, block_tables=None,
                    new_counts=None):
    """One sublayer (mixer + optional cross-attn + ffn) with residuals.
    When `ep_ctx`/`ep_state` are given, a MoE FFN executes through the
    EP slot data plane (``distributed.ep.moe_ep_ffn``) with the expert
    runtime's live slot tables/weights instead of the GShard capacity
    dispatch. Returns (x, new_cache, metrics)."""
    new_cache = {}
    metrics = {}
    h = L.norm(x, p["norm1"], cfg.norm)
    if sub.mixer == "attn":
        y, nc = L.attention_block(p["attn"], cfg, h, positions,
                                  cache=None if cache is None
                                  else cache["attn"],
                                  cache_len=cache_len, window=window,
                                  impl=cfg.impl,
                                  block_tables=block_tables,
                                  new_counts=new_counts,
                                  mesh=None if ep_ctx is None
                                  else ep_ctx.mesh)
        if nc is not None:
            new_cache["attn"] = nc
    elif sub.mixer == "mamba":
        if cache is None:
            y, _ = S.mamba_seq(p["mamba"], h, cfg.ssm)
        else:
            y, st = S.mamba_step(p["mamba"], h, cache["mamba"], cfg.ssm)
            new_cache["mamba"] = st
    elif sub.mixer == "mlstm":
        if cache is None:
            y, _ = S.mlstm_seq(p["mlstm"], h, cfg.num_heads)
        else:
            y, st = S.mlstm_step(p["mlstm"], h, cache["mlstm"],
                                 cfg.num_heads)
            new_cache["mlstm"] = st
    elif sub.mixer == "slstm":
        if cache is None:
            y, _ = S.slstm_seq(p["slstm"], h, cfg.num_heads)
        else:
            y, st = S.slstm_step(p["slstm"], h, cache["slstm"],
                                 cfg.num_heads)
            new_cache["slstm"] = st
    x = x + y

    if sub.cross_attn and enc_out is not None:
        h = L.norm(x, p["norm_x"], cfg.norm)
        # cross attention: keys/values from encoder output (not cached
        # per-step — enc_out is static during decode)
        b, sq, _ = h.shape
        enc_pos = jnp.broadcast_to(
            jnp.arange(enc_out.shape[1], dtype=jnp.int32)[None],
            (b, enc_out.shape[1]))
        q_pos = positions[..., 0] if positions.ndim == 3 else positions
        hd, nh = cfg.resolved_head_dim, cfg.num_heads
        pa = p["xattn"]
        q = (h @ pa["wq"]).reshape(b, sq, nh, hd)
        k = (enc_out @ pa["wk"]).reshape(b, enc_out.shape[1],
                                         cfg.num_kv_heads, hd)
        v = (enc_out @ pa["wv"]).reshape(b, enc_out.shape[1],
                                         cfg.num_kv_heads, hd)
        y = L.attention(q, k, v, q_pos, enc_pos, causal=False)
        x = x + (y.reshape(b, sq, nh * hd) @ pa["wo"]).astype(x.dtype)

    if sub.ffn != "none":
        h = L.norm(x, p["norm2"], cfg.norm)
        if sub.ffn == "moe":
            if ep_ctx is not None and ep_state is not None:
                # serving hot path: EP slot data plane with the expert
                # runtime's live tables/weights (lazy import keeps the
                # jnp-only model paths pallas-free)
                from repro.distributed.ep import moe_ep_ffn
                y, m = moe_ep_ffn(p["moe"], h, ep_state, ep_ctx, cfg,
                                  token_mask=token_mask)
            else:
                y, m = MOE.dispatch_moe(
                    p["moe"], h, top_k=cfg.moe.top_k,
                    num_experts=cfg.moe.num_experts,
                    capacity_factor=cfg.moe.capacity_factor, act=cfg.act,
                    groups=_moe_groups(cfg, h), token_mask=token_mask,
                    impl=cfg.impl)
            metrics["expert_load"] = m["expert_load"]
            metrics["aux_loss"] = m["aux_loss"]
            metrics["dropped"] = m["dropped"]
            if collect:   # predictor fine-tuning dataset (paper §5)
                metrics["gate_input"] = h
                if "router_logits" in m:
                    metrics["router_logits"] = m["router_logits"].reshape(
                        h.shape[0], h.shape[1], -1)
        else:
            y = L.ffn(p["ffn"], h, cfg.act)
        x = x + y
    return x, new_cache, metrics


_MOE_GROUPS = {"groups": 1}


def set_moe_dispatch_groups(n: int) -> None:
    """Global dispatch-group count (= number of data shards) for the GShard
    einsum path; launchers set this to the mesh's data-parallel degree."""
    _MOE_GROUPS["groups"] = n


def _moe_groups(cfg, h):
    # dispatch-group size capped at ~2048 tokens: the (t_g, k, E, C) one-hot
    # dispatch tensor is O(t_g^2) per group, so groups scale with tokens
    t = h.shape[0] * h.shape[1]
    return max(_MOE_GROUPS["groups"], t // 2048)


def _embed(cfg, params, batch):
    tokens = batch["tokens"]
    x = params["embed"][tokens]
    if "vis_embeds" in batch:            # VLM early fusion: patch embeddings
        x = jnp.where(batch["vis_mask"][..., None],
                      batch["vis_embeds"].astype(x.dtype), x)
    return x


def _positions(cfg, batch, seq_len: int, bsz: int):
    if "positions" in batch:
        return batch["positions"]
    pos = jnp.broadcast_to(jnp.arange(seq_len, dtype=jnp.int32)[None],
                           (bsz, seq_len))
    if cfg.rope == "mrope":
        pos = jnp.repeat(pos[..., None], 3, axis=-1)
    return pos


def _run_encoder(cfg, params, batch):
    """Whisper-style encoder over precomputed frame embeddings (stub
    frontend per spec)."""
    x = batch["enc_embeds"]
    x = x + _sinusoidal(x.shape[1], cfg.d_model).astype(x.dtype)[None]
    pos = jnp.broadcast_to(jnp.arange(x.shape[1], dtype=jnp.int32)[None],
                           (x.shape[0], x.shape[1]))
    enc = params["encoder"]

    def body_bidir(h, lp):
        hn = L.norm(h, lp["norm1"], cfg.norm)
        b, s, _ = hn.shape
        hd, nh, kvh = cfg.resolved_head_dim, cfg.num_heads, cfg.num_kv_heads
        q = (hn @ lp["attn"]["wq"]).reshape(b, s, nh, hd)
        k = (hn @ lp["attn"]["wk"]).reshape(b, s, kvh, hd)
        v = (hn @ lp["attn"]["wv"]).reshape(b, s, kvh, hd)
        y = L.attention(q, k, v, pos, pos, causal=False)
        h = h + (y.reshape(b, s, nh * hd) @ lp["attn"]["wo"]).astype(h.dtype)
        hn = L.norm(h, lp["norm2"], cfg.norm)
        h = h + L.ffn(lp["ffn"], hn, cfg.act)
        return h, None

    x, _ = jax.lax.scan(body_bidir, x, enc["layers"][0])
    return L.norm(x, enc["final_norm"], cfg.norm)


def forward(cfg, params, batch, *, window: int = 0, collect: bool = False,
            remat: str = "none", last_only: bool = False, ep_ctx=None,
            ep_state=None, token_mask=None):
    """Train / prefill forward. batch: {tokens (B,S), [positions],
    [vis_embeds, vis_mask], [enc_embeds]} -> (logits, metrics).

    `ep_ctx` (static) + `ep_state` (traced pytree, same layout as
    ``decode_step``'s) route every MoE sublayer through the EP slot
    data plane with the expert runtime's live tables/weights — the
    serving prefill analogue of the decode hot path, so both phases run
    ONE routing semantics. `token_mask` (B, S) excludes tokens (padded
    prefill) from the expert-load / dropped metrics."""
    pattern = layer_pattern(cfg)
    x = _embed(cfg, params, batch)
    bsz, seq_len = batch["tokens"].shape
    pos = _positions(cfg, batch, seq_len, bsz)
    if token_mask is None:
        token_mask = batch.get("token_mask")
    if cfg.encdec is not None:
        enc_out = _run_encoder(cfg, params, batch)
        x = x + _sinusoidal(seq_len, cfg.d_model).astype(x.dtype)[None]
        pattern = [SubLayer("attn", "dense", cross_attn=True)]
    else:
        enc_out = None

    from repro.distributed.sharding import constrain_activations

    def body(h, xs):
        if ep_state is None:
            layer_params = xs
            layer_ep = [None] * len(pattern)
        else:
            layer_params, layer_ep = xs
        h = constrain_activations(h)
        ms = []
        for j, sub in enumerate(pattern):
            h, _, m = _apply_sublayer(cfg, sub, layer_params[j], h, pos,
                                      enc_out=enc_out, window=window,
                                      collect=collect,
                                      token_mask=token_mask,
                                      ep_ctx=ep_ctx, ep_state=layer_ep[j])
            ms.append(m)
        loads = [m["expert_load"] for m in ms if "expert_load" in m]
        aux = sum(m.get("aux_loss", 0.0) for m in ms)
        y = {"aux_loss": jnp.asarray(aux, jnp.float32)}
        if loads:
            y["expert_load"] = jnp.stack(loads)   # (moe_per_period, E)
            y["dropped"] = jnp.stack(
                [m["dropped"] for m in ms if "dropped" in m])
        if collect and loads:
            y["gate_input"] = jnp.stack(
                [m["gate_input"] for m in ms if "gate_input" in m])
            rl = [m["router_logits"] for m in ms if "router_logits" in m]
            if rl:   # the EP data plane does not emit router logits
                y["router_logits"] = jnp.stack(rl)
        return h, y

    if remat == "full":
        body = jax.checkpoint(body)
    elif remat == "dots":
        body = jax.checkpoint(
            body, policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable)
    xs_in = params["layers"] if ep_state is None \
        else (params["layers"], ep_state)
    x, ys = jax.lax.scan(body, x, xs_in)
    if last_only:   # prefill: only the last position feeds sampling
        x = x[:, -1:]
    x = L.norm(x, params["final_norm"], cfg.norm)
    logits = _lm_head(cfg, params, x)
    metrics = {"aux_loss": ys["aux_loss"].sum()}
    if "expert_load" in ys:
        # (P, moe_per_period, E) -> (num_moe_layers, E)
        el = ys["expert_load"]
        metrics["expert_load"] = el.reshape(-1, el.shape[-1])
        metrics["dropped"] = ys["dropped"].reshape(-1)
    if "gate_input" in ys:
        gi = ys["gate_input"]       # (P, mpp, B, S, D)
        metrics["gate_input"] = gi.reshape((-1,) + gi.shape[2:])
        if "router_logits" in ys:
            rl = ys["router_logits"]
            metrics["router_logits"] = rl.reshape((-1,) + rl.shape[2:])
    return logits, metrics


# ---------------------------------------------------------------- decode


def init_cache(cfg, params, batch: int, max_len: int):
    """Cache pytree mirroring params['layers'] structure, stacked over
    periods."""
    pattern = layer_pattern(cfg)
    np_ = cfg.num_layers // len(pattern)
    dtype = jnp.dtype(cfg.dtype)

    def one(sub: SubLayer):
        c = {}
        if sub.mixer == "attn":
            c["attn"] = L.init_attn_cache(cfg, batch, max_len, dtype)
        elif sub.mixer == "mamba":
            di = cfg.ssm.expand * cfg.d_model
            c["mamba"] = {"conv": jnp.zeros((batch, cfg.ssm.d_conv - 1, di),
                                            dtype),
                          "ssm": jnp.zeros((batch, di, cfg.ssm.d_state),
                                           jnp.float32)}
        elif sub.mixer == "mlstm":
            c["mlstm"] = S.init_mlstm_state(cfg, batch, cfg.ssm.expand)
        elif sub.mixer == "slstm":
            c["slstm"] = S.init_slstm_state(cfg.d_model, cfg.num_heads,
                                            batch)
        return c

    caches = []
    for sub in pattern:
        c = one(sub)
        caches.append(jax.tree.map(
            lambda a: jnp.broadcast_to(a[None], (np_,) + a.shape), c))
    return caches


def init_paged_cache(cfg, params, num_blocks: int, block: int):
    """Paged-pool cache pytree: same per-period stacking as ``init_cache``
    but each attention sublayer holds ONE global block pool
    ``(num_blocks, block, kvh, hd)`` addressed by per-row block tables
    instead of per-slot contiguous rows. Attention-only decoder patterns
    only — recurrent mixers keep per-slot state, which block tables
    cannot express."""
    pattern = layer_pattern(cfg)
    assert cfg.encdec is None and all(s.mixer == "attn" for s in pattern), \
        f"{cfg.name}: paged KV requires an attention-only decoder"
    np_ = cfg.num_layers // len(pattern)
    dtype = jnp.dtype(cfg.dtype)
    caches = []
    for _ in pattern:
        c = {"attn": L.init_paged_attn_cache(cfg, num_blocks, block, dtype)}
        caches.append(jax.tree.map(
            lambda a: jnp.broadcast_to(a[None], (np_,) + a.shape), c))
    return caches


def decode_step(cfg, params, batch, cache, cache_len, ep_state=None, *,
                window: int = 0, collect: bool = False, ep_ctx=None):
    """One decode iteration: batch['tokens'] is (B, S_new) — S_new=1 for
    token-by-token decode, S_new=prompt_len for prefill-into-cache
    (cache_len=0). `cache_len` is a scalar, or a (B,) vector of per-row
    cache depths for the continuous-batching slot pool (encoder-decoder
    models require the scalar form).

    `ep_ctx` (static, closed over by jit) + `ep_state` (traced pytree:
    one entry per sublayer pattern position, None for non-MoE positions,
    else per-layer slot tables/weights stacked over periods) route every
    MoE sublayer through the EP slot data plane — the expert runtime's
    replica plans execute here without recompilation. Returns
    (logits (B,S_new,V), new_cache, metrics)."""
    pattern = layer_pattern(cfg)
    x = _embed(cfg, params, batch)
    bsz, s_new = batch["tokens"].shape
    cache_len = jnp.asarray(cache_len, jnp.int32)
    pos = batch.get("positions")
    if pos is None:
        base = cache_len if cache_len.ndim == 0 else cache_len[:, None]
        pos = base + jnp.broadcast_to(
            jnp.arange(s_new, dtype=jnp.int32)[None], (bsz, s_new))
        if cfg.rope == "mrope":
            pos = jnp.repeat(pos[..., None], 3, axis=-1)
    enc_out = batch.get("enc_out")
    if cfg.encdec is not None:
        x = x + _sinusoidal_at(cache_len, cfg.d_model).astype(x.dtype)
    # continuous batching: mask of tokens whose routing counts toward the
    # control plane's expert-load metric — per-token (B, S_new) via
    # batch['token_mask'] (padded prefill) or per-slot (B,) via
    # batch['active'] (batched decode over the slot pool)
    token_mask = batch.get("token_mask")
    if token_mask is None and "active" in batch:
        token_mask = jnp.broadcast_to(batch["active"][:, None],
                                      (bsz, s_new))
    # paged KV: per-row block tables (B, blocks_per_slot) into the global
    # pool, plus per-row new-token counts (chunked prefill writes up to
    # S_new tokens for prefilling rows, 1 for decoding rows, 0 for
    # inactive rows — whose writes are redirected to the trash block)
    block_tables = batch.get("block_tables")
    new_counts = batch.get("new_counts")
    if block_tables is not None and new_counts is not None and \
            token_mask is None:
        token_mask = jnp.arange(s_new, dtype=jnp.int32)[None] \
            < jnp.asarray(new_counts, jnp.int32)[:, None]

    def body(h, xs):
        if ep_state is None:
            layer_params, layer_cache = xs
            layer_ep = [None] * len(pattern)
        else:
            layer_params, layer_cache, layer_ep = xs
        new_caches = []
        ms = []
        for j, sub in enumerate(pattern):
            h, nc, m = _apply_sublayer(cfg, sub, layer_params[j], h, pos,
                                       cache=layer_cache[j],
                                       cache_len=cache_len,
                                       enc_out=enc_out, window=window,
                                       collect=collect,
                                       token_mask=token_mask,
                                       ep_ctx=ep_ctx,
                                       ep_state=layer_ep[j],
                                       block_tables=block_tables,
                                       new_counts=new_counts)
            new_caches.append(nc)
            ms.append(m)
        y = {}
        loads = [m["expert_load"] for m in ms if "expert_load" in m]
        if loads:
            y["expert_load"] = jnp.stack(loads)
            y["dropped"] = jnp.stack(
                [m["dropped"] for m in ms if "dropped" in m])
        if collect and loads:
            y["gate_input"] = jnp.stack(
                [m["gate_input"] for m in ms if "gate_input" in m])
        return h, (new_caches, y)

    xs_in = (params["layers"], cache) if ep_state is None \
        else (params["layers"], cache, ep_state)
    x, (new_cache, ys) = jax.lax.scan(body, x, xs_in)
    x = L.norm(x, params["final_norm"], cfg.norm)
    metrics = {}
    if "expert_load" in ys:
        el = ys["expert_load"]
        metrics["expert_load"] = el.reshape(-1, el.shape[-1])
        metrics["dropped"] = ys["dropped"].reshape(-1)
    if "gate_input" in ys:
        gi = ys["gate_input"]
        metrics["gate_input"] = gi.reshape((-1,) + gi.shape[2:])
    return _lm_head(cfg, params, x), new_cache, metrics


# ---------------------------------------------------------------- sampling


def _filter_top_k_top_p(lg, k, p):
    """Mask one row of logits (V,) to its top-k entries (k<=0 => all) and
    its top-p nucleus (smallest prefix of the descending-probability
    ordering with cumulative mass >= p; the argmax always survives).
    Both `k` and `p` are traced per-row scalars, so the filter works with
    a DIFFERENT k/p on every slot of the batched step."""
    order = jnp.argsort(-lg)                    # descending logits
    ranks = jnp.argsort(order)                  # rank of each vocab id
    keep_k = (k <= 0) | (ranks < k)
    probs = jax.nn.softmax(lg[order])
    cum = jnp.cumsum(probs) - probs             # exclusive prefix mass
    keep_p = (cum < p)[ranks]                   # rank 0 always kept
    return jnp.where(keep_k & keep_p, lg, -jnp.inf)


@jax.jit
def sample_tokens(logits, temperature, top_k, top_p, seed, step):
    """Sample next tokens for EVERY slot in one jitted call.

    logits (B, V); temperature/top_p (B,) float32; top_k (B,) int32;
    seed (B,) int32 per-request RNG seeds; step (B,) int32 = how many
    tokens each request has already sampled. Rows with temperature <= 0
    take ``jnp.argmax`` — bit-identical to the pre-sampling greedy path.
    Sampled rows draw from the temperature-scaled, top-k/top-p-filtered
    distribution with key ``fold_in(PRNGKey(seed), step)``: keyed by the
    request, not the slot or the batch, so a request's sample stream is
    deterministic and independent of batch composition (batched decode
    == sequential decode, the same identity the greedy path has)."""
    greedy = jnp.argmax(logits, axis=-1)

    def row(lg, t, k, p, s, n):
        key = jax.random.fold_in(jax.random.PRNGKey(s), n)
        lg = lg.astype(jnp.float32) / jnp.maximum(t, 1e-6)
        return jax.random.categorical(key, _filter_top_k_top_p(lg, k, p))

    sampled = jax.vmap(row)(logits, temperature, top_k, top_p, seed, step)
    return jnp.where(temperature > 0, sampled, greedy).astype(jnp.int32)


def _lm_head(cfg, params, x):
    head = params["embed"].T if cfg.tie_embeddings else params["head"]
    logits = x @ head
    if cfg.padded_vocab != cfg.vocab_size:   # mask pad entries to -inf
        bias = jnp.where(jnp.arange(cfg.padded_vocab) < cfg.vocab_size,
                         0.0, -1e9).astype(logits.dtype)
        logits = logits + bias
    return logits


def _sinusoidal_at(pos, d: int):
    dim = jnp.arange(d // 2, dtype=jnp.float32)[None, :]
    ang = jnp.asarray(pos, jnp.float32)[..., None, None] \
        / jnp.power(10000.0, 2 * dim / d)
    out = jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)
    return out.reshape((1, 1, d))
