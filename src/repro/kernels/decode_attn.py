"""Pallas TPU decode-attention kernel: one new query per sequence against
a (possibly ring-buffered) KV cache — the serving hot spot of every
decode_32k / long_500k shape.

Grid (B, KV, S//bs) with the cache-sequence axis innermost ("arbitrary"):
each step streams one (bs, hd) K/V tile of one KV head HBM->VMEM and
maintains the online-softmax running (m, l, acc) of all G = H // KV
query heads that share it in VMEM scratch — the flash-decoding
recurrence. GQA therefore reads each KV tile once per query-head group
and never materialises repeated KV.

TPU tiling: the last two dims of every block must be divisible by
(8, 128) or equal the array's own. The wrappers lay the operands out so
that they are: q as (B, KV, G, hd) with a (G, hd) block, K/V as
(B|NB, KV, S|blk, hd) with a (bs|blk, hd) block, and the key positions
as (B|NB, 1, S|blk) with a (1, bs|blk) block (bs is a multiple of 128
or the whole cache).

Masking: slots >= kv_len are invalid (unwritten cache), and with
window > 0 positions <= q_pos - window are masked (sliding window).

Each kernel carries a fixed ``name`` and ``metadata={"kernel": name}``
(``decode_attn``, ``decode_attn_paged``): the custom call's
``kernel_metadata`` in a profiler trace's op text.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -0.7 * float(jnp.finfo(jnp.float32).max)


def _kernel(scalar_ref, q_ref, k_ref, v_ref, pos_ref, o_ref,
            m_ref, l_ref, acc_ref, *, ns: int, window: int):
    b = pl.program_id(0)
    si = pl.program_id(2)
    bs = k_ref.shape[0]

    @pl.when(si == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    kv_len = scalar_ref[b, 0]
    q_pos = scalar_ref[b, 1]
    q = q_ref[...].astype(jnp.float32)          # (G, hd)
    k = k_ref[...].astype(jnp.float32)          # (bs, hd)
    # rows past the cache end (a partial last tile) hold unspecified
    # values; zero them so 0-weight lanes cannot turn p @ v into NaN
    row = si * bs + jax.lax.broadcasted_iota(jnp.int32, (bs, 1), 0)
    v = jnp.where(row < kv_len, v_ref[...].astype(jnp.float32), 0.0)

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)  # (G, bs)
    slot = si * bs + jax.lax.broadcasted_iota(jnp.int32, (1, bs), 1)
    kpos = pos_ref[...]                         # (1, bs)
    mask = (slot < kv_len) & (kpos <= q_pos)
    if window:
        mask = mask & (kpos > q_pos - window)
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)
    corr = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * corr + jnp.sum(p, -1, keepdims=True)
    acc_ref[...] = acc_ref[...] * corr + jnp.dot(
        p, v, preferred_element_type=jnp.float32)
    m_ref[...] = m_new

    @pl.when(si == ns - 1)
    def _out():
        o_ref[...] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)) \
            .astype(o_ref.dtype)


def _scalars(kv_len, q_pos, b):
    return jnp.stack([jnp.broadcast_to(kv_len, (b,)).astype(jnp.int32),
                      jnp.broadcast_to(q_pos, (b,)).astype(jnp.int32)],
                     axis=1)


def _scratch(g, hd):
    return [pltpu.VMEM((g, 1), jnp.float32),
            pltpu.VMEM((g, 1), jnp.float32),
            pltpu.VMEM((g, hd), jnp.float32)]


def decode_attention(q, k, v, kv_pos, kv_len, q_pos, *, window: int = 0,
                     bs: int = 512, interpret: bool = False):
    """q: (B, H, hd); k/v: (B, S, KV, hd); kv_pos: (B, S) absolute
    positions of cache slots; kv_len/q_pos: (B,). Returns (B, H, hd)."""
    b, h, hd = q.shape
    s, kv = k.shape[1], k.shape[2]
    g = h // kv
    bs = min(bs, s)
    ns = pl.cdiv(s, bs)
    q = (q * (1.0 / math.sqrt(hd))).astype(q.dtype).reshape(b, kv, g, hd)
    kernel = functools.partial(_kernel, ns=ns, window=window)
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b, kv, ns),
            in_specs=[
                pl.BlockSpec((None, None, g, hd),
                             lambda b, j, si, sc: (b, j, 0, 0)),
                pl.BlockSpec((None, None, bs, hd),
                             lambda b, j, si, sc: (b, j, si, 0)),
                pl.BlockSpec((None, None, bs, hd),
                             lambda b, j, si, sc: (b, j, si, 0)),
                pl.BlockSpec((None, 1, bs),
                             lambda b, j, si, sc: (b, 0, si)),
            ],
            out_specs=pl.BlockSpec((None, None, g, hd),
                                   lambda b, j, si, sc: (b, j, 0, 0)),
            scratch_shapes=_scratch(g, hd),
        ),
        out_shape=jax.ShapeDtypeStruct((b, kv, g, hd), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="decode_attn",
        metadata={"kernel": "decode_attn"},
    )(_scalars(kv_len, q_pos, b), q, k.transpose(0, 2, 1, 3),
      v.transpose(0, 2, 1, 3), kv_pos.reshape(b, 1, s))
    return out.reshape(b, h, hd)


def _paged_kernel(tab_ref, scalar_ref, q_ref, k_ref, v_ref, pos_ref,
                  o_ref, m_ref, l_ref, acc_ref, *, ns: int, window: int):
    # the block tables are consumed entirely by the BlockSpec index maps
    # (they pick WHICH pool block streams in at each grid step); inside
    # the body the recurrence is the contiguous kernel's, with the block
    # axis as the innermost "arbitrary" grid dim
    del tab_ref
    _kernel(scalar_ref, q_ref, k_ref, v_ref, pos_ref, o_ref,
            m_ref, l_ref, acc_ref, ns=ns, window=window)


def decode_attention_paged(q, k, v, kv_pos, block_tables, kv_len, q_pos, *,
                           window: int = 0, interpret: bool = False):
    """Paged-pool flash decode: q (B, H, hd); k/v are the GLOBAL block
    pool (NB, blk, KV, hd) with kv_pos (NB, blk); block_tables (B, nbs)
    int32 maps each row's logical block i to a pool block id. The tables
    ride the scalar-prefetch lane so the K/V BlockSpec index maps can
    gather pool blocks directly — no (B, nbs*blk) materialisation.
    kv_len/q_pos: (B,). Returns (B, H, hd)."""
    b, h, hd = q.shape
    nb, blk, kv = k.shape[0], k.shape[1], k.shape[2]
    g = h // kv
    nbs = block_tables.shape[1]
    q = (q * (1.0 / math.sqrt(hd))).astype(q.dtype).reshape(b, kv, g, hd)
    tables = jnp.asarray(block_tables, jnp.int32)
    kernel = functools.partial(_paged_kernel, ns=nbs, window=window)
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b, kv, nbs),
            in_specs=[
                pl.BlockSpec((None, None, g, hd),
                             lambda b, j, si, tab, sc: (b, j, 0, 0)),
                pl.BlockSpec((None, None, blk, hd),
                             lambda b, j, si, tab, sc:
                             (tab[b, si], j, 0, 0)),
                pl.BlockSpec((None, None, blk, hd),
                             lambda b, j, si, tab, sc:
                             (tab[b, si], j, 0, 0)),
                pl.BlockSpec((None, 1, blk),
                             lambda b, j, si, tab, sc: (tab[b, si], 0, 0)),
            ],
            out_specs=pl.BlockSpec((None, None, g, hd),
                                   lambda b, j, si, tab, sc: (b, j, 0, 0)),
            scratch_shapes=_scratch(g, hd),
        ),
        out_shape=jax.ShapeDtypeStruct((b, kv, g, hd), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="decode_attn_paged",
        metadata={"kernel": "decode_attn_paged"},
    )(tables, _scalars(kv_len, q_pos, b), q, k.transpose(0, 2, 1, 3),
      v.transpose(0, 2, 1, 3), kv_pos.reshape(nb, 1, blk))
    return out.reshape(b, h, hd)


def decode_attention_paged_ref(q, k, v, kv_pos, block_tables, kv_len,
                               q_pos, *, window: int = 0):
    """Pure-jnp oracle for the paged kernel: gather each row's block
    chain from the pool, then run the contiguous oracle."""
    b = q.shape[0]
    blk, kv, hd = k.shape[1], k.shape[2], k.shape[3]
    nbs = block_tables.shape[1]
    gk = k[block_tables].reshape(b, nbs * blk, kv, hd)
    gv = v[block_tables].reshape(b, nbs * blk, kv, hd)
    gpos = kv_pos[block_tables].reshape(b, nbs * blk)
    return decode_attention_ref(q, gk, gv, gpos, kv_len, q_pos,
                                window=window)


def decode_attention_ref(q, k, v, kv_pos, kv_len, q_pos, *,
                         window: int = 0):
    """Pure-jnp oracle (mirrors models.layers.attention semantics)."""
    b, h, hd = q.shape
    s, kv = k.shape[1], k.shape[2]
    groups = h // kv
    kk = jnp.broadcast_to(k[:, :, :, None, :],
                          (b, s, kv, groups, hd)).reshape(b, s, h, hd)
    vv = jnp.broadcast_to(v[:, :, :, None, :],
                          (b, s, kv, groups, hd)).reshape(b, s, h, hd)
    scores = jnp.einsum("bhd,bshd->bhs", q.astype(jnp.float32),
                        kk.astype(jnp.float32)) / math.sqrt(hd)
    slot = jnp.arange(s)[None, None, :]
    mask = (slot < kv_len[:, None, None]) \
        & (kv_pos[:, None, :] <= q_pos[:, None, None])
    if window:
        mask = mask & (kv_pos[:, None, :] > q_pos[:, None, None] - window)
    scores = jnp.where(mask, scores, NEG_INF)
    p = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("bhs,bshd->bhd", p,
                      vv.astype(jnp.float32)).astype(q.dtype)
