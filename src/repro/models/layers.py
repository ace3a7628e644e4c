"""Core neural-net layers: norms, rotary embeddings (RoPE / M-RoPE),
grouped-query attention (full / chunked-online-softmax / sliding window /
decode-with-cache), and FFNs.

All functions are pure; parameters are plain dicts of jnp arrays.
Shape conventions:  x: (B, S, D)   q/k/v: (B, S, H, hd)   cache: (B, Smax, KV, hd)
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

# ---------------------------------------------------------------- norms


def rms_norm(x, weight, eps: float = 1e-6):
    dt = x.dtype
    x = x.astype(jnp.float32)
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return (x * weight).astype(dt)


def layer_norm(x, weight, bias, eps: float = 1e-5):
    dt = x.dtype
    x = x.astype(jnp.float32)
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    y = (x - mu) * jax.lax.rsqrt(var + eps)
    return (y * weight + bias).astype(dt)


def norm(x, p, kind: str):
    if kind == "rmsnorm":
        return rms_norm(x, p["scale"])
    return layer_norm(x, p["scale"], p["bias"])


def init_norm(key, d, kind: str, dtype):
    if kind == "rmsnorm":
        return {"scale": jnp.ones((d,), dtype)}
    return {"scale": jnp.ones((d,), dtype), "bias": jnp.zeros((d,), dtype)}


# ---------------------------------------------------------------- rotary


def rope_cos_sin(positions, head_dim: int, theta: float):
    """positions: (...,) int -> cos/sin (..., head_dim//2) in f32."""
    half = head_dim // 2
    freqs = 1.0 / (theta ** (jnp.arange(0, half, dtype=jnp.float32) / half))
    ang = positions.astype(jnp.float32)[..., None] * freqs
    return jnp.cos(ang), jnp.sin(ang)


def apply_rotary(x, cos, sin):
    """x: (B, S, H, hd); cos/sin: (B, S, hd//2) -> rotated x (half-split)."""
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    cos = cos[:, :, None, :].astype(x.dtype)
    sin = sin[:, :, None, :].astype(x.dtype)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)


def mrope_cos_sin(positions3, head_dim: int, theta: float,
                  sections=(1, 1, 2)):
    """Multimodal RoPE (Qwen2-VL, arXiv:2409.12191).

    positions3: (B, S, 3) = (temporal, height, width) position ids.
    The rotary spectrum is partitioned among the three axes in the ratio
    `sections` (temporal : h : w); text tokens carry identical ids on all
    three axes which makes M-RoPE degenerate to 1-D RoPE exactly.
    """
    half = head_dim // 2
    freqs = 1.0 / (theta ** (jnp.arange(0, half, dtype=jnp.float32) / half))
    total = sum(sections)
    bounds = []
    acc = 0
    for s in sections:
        acc += s
        bounds.append(half * acc // total)
    sec_of_freq = jnp.zeros((half,), jnp.int32)
    prev = 0
    for i, b in enumerate(bounds):
        sec_of_freq = sec_of_freq.at[prev:b].set(i)
        prev = b
    # gather the per-frequency position id: (B, S, half)
    pos = jnp.take_along_axis(
        positions3.astype(jnp.float32),
        jnp.broadcast_to(sec_of_freq[None, None, :],
                         positions3.shape[:2] + (half,)).astype(jnp.int32),
        axis=-1)
    ang = pos * freqs
    return jnp.cos(ang), jnp.sin(ang)


# ---------------------------------------------------------------- attention

NEG_INF = -0.7 * float(jnp.finfo(jnp.float32).max)


def _repeat_kv(k, num_groups: int):
    # (B, S, KV, hd) -> (B, S, KV*G, hd)
    b, s, kv, hd = k.shape
    return jnp.broadcast_to(k[:, :, :, None, :], (b, s, kv, num_groups, hd)
                            ).reshape(b, s, kv * num_groups, hd)


def attention(q, k, v, q_positions, kv_positions, *, causal: bool = True,
              window: int = 0, kv_len=None, chunk: int = 1024):
    """Chunked online-softmax GQA attention (flash-style in pure jnp).

    q: (B, Sq, H, hd), k/v: (B, Sk, KV, hd). Positions give the absolute
    token index of every slot (enables caches / ring buffers). `window`>0
    masks keys older than `q_pos - window + 1` (sliding window). `kv_len`
    (scalar or (B,)) masks unwritten cache slots.
    """
    b, sq, h, hd = q.shape
    sk, kv = k.shape[1], k.shape[2]
    groups = h // kv
    k = _repeat_kv(k, groups)
    v = _repeat_kv(v, groups)
    scale = 1.0 / math.sqrt(hd)
    q = q.astype(jnp.float32) * scale
    k = k.astype(jnp.float32)
    v = v.astype(jnp.float32)

    if kv_len is None:
        kv_len = jnp.asarray(sk, jnp.int32)
    kv_len = jnp.broadcast_to(jnp.asarray(kv_len, jnp.int32), (b,))

    nblk = max(1, -(-sk // chunk))
    pad = nblk * chunk - sk
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        kv_positions = jnp.pad(kv_positions, ((0, 0), (0, pad)),
                               constant_values=-(10 ** 9))
    kb = k.reshape(b, nblk, chunk, h, hd)
    vb = v.reshape(b, nblk, chunk, h, hd)
    pb = kv_positions.reshape(b, nblk, chunk)

    def body(carry, blk):
        m, l, acc = carry
        kc, vc, pc, valid = blk  # (B,C,H,hd), (B,C,H,hd), (B,C), (B,C)
        s = jnp.einsum("bqhd,bchd->bhqc", q, kc)
        msk = valid[:, None, None, :]
        if causal:
            msk = msk & (pc[:, None, None, :] <= q_positions[:, None, :, None])
        if window:
            msk = msk & (pc[:, None, None, :]
                         > q_positions[:, None, :, None] - window)
        s = jnp.where(msk, s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l = l * corr + jnp.sum(p, axis=-1)
        acc = acc * corr[..., None] + jnp.einsum("bhqc,bchd->bhqd", p, vc)
        return (m_new, l, acc), None

    slot = jnp.arange(nblk * chunk).reshape(nblk, chunk)
    valid = slot[None] < kv_len[:, None, None]  # (B, nblk, C)
    init = (jnp.full((b, h, sq), NEG_INF, jnp.float32),
            jnp.zeros((b, h, sq), jnp.float32),
            jnp.zeros((b, h, sq, hd), jnp.float32))
    # flash-style backward: recompute each KV-block's probabilities rather
    # than saving (Sq x Sk) softmax residuals
    body = jax.checkpoint(body)
    (m, l, acc), _ = jax.lax.scan(
        body, init,
        (kb.transpose(1, 0, 2, 3, 4), vb.transpose(1, 0, 2, 3, 4),
         pb.transpose(1, 0, 2), valid.transpose(1, 0, 2)))
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return out.transpose(0, 2, 1, 3)  # (B, Sq, H, hd)


def init_attention(key, cfg, dtype):
    d, h, kvh, hd = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, \
        cfg.resolved_head_dim
    ks = jax.random.split(key, 4)
    sd = 1.0 / math.sqrt(d)
    p = {
        "wq": jax.random.normal(ks[0], (d, h * hd), dtype) * sd,
        "wk": jax.random.normal(ks[1], (d, kvh * hd), dtype) * sd,
        "wv": jax.random.normal(ks[2], (d, kvh * hd), dtype) * sd,
        "wo": jax.random.normal(ks[3], (h * hd, d), dtype) * (1.0 / math.sqrt(h * hd)),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((h * hd,), dtype)
        p["bk"] = jnp.zeros((kvh * hd,), dtype)
        p["bv"] = jnp.zeros((kvh * hd,), dtype)
    if cfg.qk_norm:
        p["q_norm"] = jnp.ones((hd,), dtype)
        p["k_norm"] = jnp.ones((hd,), dtype)
    return p


def attention_block(p, cfg, x, positions, *, cache=None, cache_len=None,
                    window: int = 0, impl: str = "ref",
                    block_tables=None, new_counts=None, mesh=None):
    """Full attention sublayer: qkv proj -> rope -> attention -> out proj.

    Without a cache this is a training/prefill pass over x: (B, S, D).
    With cache=(k, v) of shape (B, Smax, KV, hd) plus cache_len it is a
    decode step: x is (B, 1, D), the new k/v are written at
    `cache_len % Smax` (ring buffer — exact for full attention when
    Smax >= context, and the natural layout for sliding windows).
    `cache_len` may be a scalar (uniform batch) or a (B,) vector of
    per-row lengths — the continuous-batching slot pool, where every
    sequence in the batch is at a different depth.

    With `block_tables` (B, nbs) int32 the cache is a PAGED pool instead:
    k/v/pos leaves are (NB, block, ...) global block pools and row b's
    positions [i*block, (i+1)*block) live in pool block
    ``block_tables[b, i]``. `new_counts` (B,) gives how many of this
    step's S tokens are real per row — rows write their first
    ``new_counts[b]`` tokens at positions ``cache_len[b] + j`` through
    the table and redirect the rest to reserved trash block 0 (so a row
    whose table went stale, or a masked chunk tail, can never corrupt a
    recycled block). Attention then gathers the row's dense
    (nbs*block)-wide KV view from the table; lanes >= cache_len +
    new_counts are masked to the same exact NEG_INF as the contiguous
    path, which is what keeps paged and contiguous decoding bit-
    identical.

    `impl` selects the kernel backend for the single-new-token decode
    hot spot (kernels.ops / kernels.decode_attn); 'ref'/'auto'-on-CPU
    keep the chunked jnp path. Prefill and multi-token steps always use
    the chunked path (the decode kernel is one-query-per-sequence).
    `mesh` is the serving mesh when the step runs on one: the kernel is
    then mapped over it with the rows split over ('data', 'ep').
    Returns (out, new_cache).
    """
    b, s, d = x.shape
    h, kvh, hd = cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim
    q = x @ p["wq"]
    k = x @ p["wk"]
    v = x @ p["wv"]
    if cfg.qkv_bias:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    q = q.reshape(b, s, h, hd)
    k = k.reshape(b, s, kvh, hd)
    v = v.reshape(b, s, kvh, hd)
    if cfg.qk_norm:
        q = rms_norm(q, p["q_norm"])
        k = rms_norm(k, p["k_norm"])
    if cfg.rope == "rope":
        cos, sin = rope_cos_sin(positions, hd, cfg.rope_theta)
        q = apply_rotary(q, cos, sin)
        k = apply_rotary(k, cos, sin)
    elif cfg.rope == "mrope":
        # positions may be (B, S) text-only -> expand to 3 identical axes
        pos3 = positions if positions.ndim == 3 else \
            jnp.repeat(positions[..., None], 3, axis=-1)
        cos, sin = mrope_cos_sin(pos3, hd, cfg.rope_theta)
        q = apply_rotary(q, cos, sin)
        k = apply_rotary(k, cos, sin)
    pos1 = positions[..., 0] if positions.ndim == 3 else positions

    if cache is None:
        out = attention(q, k, v, pos1, pos1, causal=True, window=window)
        new_cache = None
    elif block_tables is not None:
        ck, cv = cache["k"], cache["v"]          # (NB, block, KV, hd)
        kv_pos = cache["pos"]                    # (NB, block)
        blk = ck.shape[1]
        nbs = block_tables.shape[1]
        cl = jnp.asarray(cache_len, jnp.int32)
        cl = jnp.broadcast_to(cl, (b,))
        n_new = jnp.ones((b,), jnp.int32) if new_counts is None \
            else jnp.asarray(new_counts, jnp.int32)
        # scatter the new tokens through the table; invalid lanes (j >=
        # n_new) land in trash block 0 whose content is never read
        j = jnp.arange(s, dtype=jnp.int32)[None]         # (1, s)
        wpos = cl[:, None] + j                           # (B, s)
        valid = j < n_new[:, None]
        bidx = jnp.take_along_axis(
            block_tables, jnp.clip(wpos // blk, 0, nbs - 1), axis=1)
        bidx = jnp.where(valid, bidx, 0)
        off = jnp.where(valid, wpos % blk, 0)
        ck = ck.at[bidx, off].set(k.astype(ck.dtype))
        cv = cv.at[bidx, off].set(v.astype(cv.dtype))
        kv_pos = kv_pos.at[bidx, off].set(pos1.astype(jnp.int32))
        n_valid = jnp.minimum(cl + n_new, nbs * blk)
        from repro.kernels import ops as KOPS
        resolved = KOPS.resolve_impl(impl)
        if resolved != "ref" and s == 1:
            kernel = _rows_on_mesh(partial(
                KOPS.decode_attention_paged_impl, window=window,
                impl=resolved), mesh, replicated=(1, 2, 3))
            out = kernel(q[:, 0], ck, cv, kv_pos, block_tables, n_valid,
                         pos1[:, 0])[:, None]
        else:
            # gather each row's dense view: block i of the table holds
            # positions [i*blk, (i+1)*blk), so the view is position-
            # ordered and masks exactly like the contiguous ring
            gk = ck[block_tables].reshape(b, nbs * blk, kvh, hd)
            gv = cv[block_tables].reshape(b, nbs * blk, kvh, hd)
            gpos = kv_pos[block_tables].reshape(b, nbs * blk)
            out = attention(q, gk, gv, pos1, gpos, causal=True,
                            window=window, kv_len=n_valid)
        new_cache = {"k": ck, "v": cv, "pos": kv_pos}
    else:
        ck, cv = cache["k"], cache["v"]
        smax = ck.shape[1]
        kv_pos = cache["pos"]
        cl = jnp.asarray(cache_len, jnp.int32)
        if cl.ndim == 0:
            slot = jnp.mod(cl, smax)
            ck = jax.lax.dynamic_update_slice(ck, k.astype(ck.dtype),
                                              (0, slot, 0, 0))
            cv = jax.lax.dynamic_update_slice(cv, v.astype(cv.dtype),
                                              (0, slot, 0, 0))
            # absolute positions held in the ring: slot i holds position
            # i + smax*floor((cache_len-i-1)/smax + 1) ... simpler: track them
            kv_pos = jax.lax.dynamic_update_slice(
                kv_pos, pos1.astype(jnp.int32), (0, slot))
        else:
            # per-row lengths: scatter each row's new entries at its own
            # ring offset
            rows = jnp.arange(b, dtype=jnp.int32)[:, None]
            idx = jnp.mod(cl[:, None] + jnp.arange(s, dtype=jnp.int32)[None],
                          smax)                                    # (B, s)
            ck = ck.at[rows, idx].set(k.astype(ck.dtype))
            cv = cv.at[rows, idx].set(v.astype(cv.dtype))
            kv_pos = kv_pos.at[rows, idx].set(pos1.astype(jnp.int32))
        n_valid = jnp.minimum(cl + s, smax)
        # kernels.ops is imported lazily so consumers of the jnp-only
        # paths never pull in pallas-tpu
        from repro.kernels import ops as KOPS
        resolved = KOPS.resolve_impl(impl)
        if resolved != "ref" and s == 1:
            kernel = _rows_on_mesh(partial(
                KOPS.decode_attention_impl, window=window, impl=resolved),
                mesh)
            out = kernel(q[:, 0], ck, cv, kv_pos, n_valid,
                         pos1[:, 0])[:, None]
        else:
            out = attention(q, ck, cv, pos1, kv_pos, causal=True,
                            window=window, kv_len=n_valid)
        new_cache = {"k": ck, "v": cv, "pos": kv_pos}
    out = out.reshape(b, s, h * hd) @ p["wo"]
    return out.astype(x.dtype), new_cache


def _rows_on_mesh(kernel, mesh, replicated=()):
    """`kernel` as a per-row program on the serving `mesh`: a Pallas
    kernel cannot be partitioned by the compiler, so on a mesh it runs
    under shard_map with every operand's leading (row) axis split over
    ('data', 'ep') — the EP layer's token sharding — except the operands
    at positions `replicated` (the shared paged pool), which each rank
    holds whole."""
    if mesh is None:
        return kernel
    rows = P(("data", "ep"))

    def mapped(*args):
        specs = tuple(P() if i in replicated else rows
                      for i in range(len(args)))
        return jax.shard_map(kernel, mesh=mesh, in_specs=specs,
                             out_specs=rows, check_vma=False)(*args)
    return mapped


def init_attn_cache(cfg, batch: int, max_len: int, dtype=jnp.bfloat16):
    kvh, hd = cfg.num_kv_heads, cfg.resolved_head_dim
    return {
        "k": jnp.zeros((batch, max_len, kvh, hd), dtype),
        "v": jnp.zeros((batch, max_len, kvh, hd), dtype),
        "pos": jnp.full((batch, max_len), -(10 ** 9), jnp.int32),
    }


def init_paged_attn_cache(cfg, num_blocks: int, block: int,
                          dtype=jnp.bfloat16):
    """Global paged KV pool for one attention sublayer: `num_blocks`
    blocks of `block` tokens, shared by every slot via block tables
    (block 0 is the serving layer's reserved trash target). Positions
    init to the same -1e9 sentinel as the contiguous ring so unwritten
    lanes are causally masked identically."""
    kvh, hd = cfg.num_kv_heads, cfg.resolved_head_dim
    return {
        "k": jnp.zeros((num_blocks, block, kvh, hd), dtype),
        "v": jnp.zeros((num_blocks, block, kvh, hd), dtype),
        "pos": jnp.full((num_blocks, block), -(10 ** 9), jnp.int32),
    }


# ---------------------------------------------------------------- ffn


def init_ffn(key, d: int, f: int, act: str, dtype):
    ks = jax.random.split(key, 3)
    sd, sf = 1.0 / math.sqrt(d), 1.0 / math.sqrt(f)
    p = {"w_up": jax.random.normal(ks[1], (d, f), dtype) * sd,
         "w_down": jax.random.normal(ks[2], (f, d), dtype) * sf}
    if act == "swiglu":
        p["w_gate"] = jax.random.normal(ks[0], (d, f), dtype) * sd
    return p


def ffn(p, x, act: str):
    if act == "swiglu":
        h = jax.nn.silu(x @ p["w_gate"]) * (x @ p["w_up"])
    else:
        h = jax.nn.gelu(x @ p["w_up"])
    return h @ p["w_down"]
