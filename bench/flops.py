"""Operations and bytes of the work a step needs, counted from what was
routed and attended, never from capacity, slots or tiles: the same work
whatever implements it.

Each function returns (flops, bytes). Weights and activations are
counted in the type the configuration serves them in: bfloat16
activations and attention weights, int8 expert weights with float32
per-row scales.
"""
from __future__ import annotations

import numpy as np

BF16 = 2
F32 = 4


def gmm_work(loads, d: int, f: int) -> tuple:
    """The routed expert FFN of one MoE layer in one step. `loads` (E,)
    holds the assignments routed to each expert. FLOPs: gate, up and
    down projections of every assignment. Bytes: each expert with an
    assignment has its int8 weights and scales read once; each
    assignment reads its input row and writes its output row, and the
    (assignments, F) hidden activation is written and read once."""
    loads = np.asarray(loads, np.int64)
    a = int(loads.sum())
    flops = 2 * a * d * f * 3
    weights = int((loads > 0).sum()) * (3 * d * f + (2 * d + f) * F32)
    acts = a * (2 * d + 2 * f) * BF16
    return float(flops), float(weights + acts)


def decode_attn_work(ctx, heads: int, kv_heads: int, hd: int) -> tuple:
    """Paged decode attention of one step: each row's query attends its
    live context `ctx[i]` keys. FLOPs: QK^T and PV. Bytes: the K, V and
    position of every live key, the query and the output."""
    ctx = np.asarray(ctx, np.int64)
    flops = 4 * int(ctx.sum()) * heads * hd
    kv = int(ctx.sum()) * (2 * kv_heads * hd * BF16 + F32)
    qo = len(ctx) * 2 * heads * hd * BF16
    return float(flops), float(kv + qo)


def _layer_flops(m: dict) -> float:
    """Per-token FLOPs of one layer outside attention's score and PV."""
    d, h, kv, hd = m["d"], m["h"], m["kv"], m["hd"]
    return (2 * d * (2 * h * hd + 2 * kv * hd)        # q, o, k, v
            + 2 * d * m["e"]                           # router
            + 2 * m["k"] * 3 * d * m["f"])             # routed experts


def step_flops(m: dict, prefill, decode_ctx) -> float:
    """Model FLOPs of one engine step through every layer. `prefill`
    holds prompt chunks as (start position, tokens, ends the prompt);
    `decode_ctx` the keys each decode row attends. A token at position p
    attends p + 1 keys; the head counts for every token whose logits are
    sampled (a prompt's last token and each decode row)."""
    attn = 4 * m["h"] * m["hd"]
    keys = 0
    tokens = 0
    sampled = 0
    for start, n, last in prefill:
        tokens += n
        keys += n * (start + 1) + n * (n - 1) // 2
        sampled += int(last)
    tokens += len(decode_ctx)
    keys += int(np.sum(decode_ctx)) if len(decode_ctx) else 0
    sampled += len(decode_ctx)
    return float(m["layers"] * (tokens * _layer_flops(m) + attn * keys)
                 + sampled * 2 * m["d"] * m["vocab"])


def roofline_s(flops: float, nbytes: float, peaks: dict) -> tuple:
    """(least time, bound) of work on one chip."""
    tc = flops / peaks["bf16_flops_per_s"]
    tm = nbytes / peaks["hbm_bytes_per_s"]
    return (tc, "compute") if tc >= tm else (tm, "memory")
