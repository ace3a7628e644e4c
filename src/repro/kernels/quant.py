"""Int8 expert-weight quantization for serverless slot banks.

Format (the ``cfg.moe.slot_dtype = "int8"`` storage layout):

  * symmetric, per-expert-ROW scales — for a bank leaf of shape
    (..., R, C) every row r (the contraction index of the grouped
    matmul) gets one fp32 scale ``s = max(|w[..., r, :]|) / 127`` and
    is stored as ``round(w / s)`` in int8. Dequantisation is exact to
    fp32 rounding: ``w ≈ q.astype(f32) * s[..., None]``.
  * a quantized bank dict carries each original key ``k`` as the int8
    values plus ``k + "_scale"`` as the (…, R) fp32 scale vector —
    w_gate / w_up (E, D, F) scale over D, w_down (E, F, D) scale
    over F, so the scale always sits on the matmul contraction axis
    and the dequantizing kernels apply it inside the tile loop
    (``w_tile * s_tile[:, None]``) without the fp32 weights ever
    existing in HBM.

Byte footprint per swiglu expert: ``3*D*F`` int8 values plus
``(2*D + F)`` fp32 scales ≈ 0.25x of the fp32 bank — the number
``repro.core.costmodel.param_bytes`` derives analytically so the cost
model and the executing runtime agree on every transferred byte.

This module is jnp-only (no pallas import): quantization runs once at
bank materialisation on any backend; only the DEQUANTIZING matmuls have
Pallas lowerings (repro.kernels.moe_gmm).
"""
from __future__ import annotations

import jax.numpy as jnp

SCALE_SUFFIX = "_scale"


def is_quantized(bank: dict) -> bool:
    """True when `bank` carries int8 values + per-row scale vectors."""
    return any(k.endswith(SCALE_SUFFIX) for k in bank)


def quantize_rows(w):
    """(..., R, C) float -> (int8 values (..., R, C), f32 scales (..., R)).

    Symmetric per-row: s_r = max(|w[..., r, :]|)/127 (1.0 for all-zero
    rows so padding rows stay exactly zero), q = round(w / s) in
    [-127, 127].

    Leading axes are quantized one (R, C) matrix at a time: the f32
    working copies then stay one matrix large (a whole Mixtral layer
    bank at once needs several 2 GB copies), and every value is the
    same as the whole-array computation's."""
    if w.ndim > 2:
        qs, ss = zip(*(quantize_rows(m) for m in w))
        return jnp.stack(qs), jnp.stack(ss)
    w = jnp.asarray(w, jnp.float32)
    amax = jnp.max(jnp.abs(w), axis=-1)
    scale = jnp.where(amax > 0, amax / 127.0, 1.0).astype(jnp.float32)
    q = jnp.round(w / scale[..., None])
    q = jnp.clip(q, -127, 127).astype(jnp.int8)
    return q, scale


def dequantize_rows(q, scale):
    """Inverse of ``quantize_rows`` (up to int8 rounding)."""
    return q.astype(jnp.float32) * scale[..., None]


def quantize_expert_bank(bank: dict) -> dict:
    """Quantize every leaf of an expert weight bank: each key ``k``
    (..., R, C) becomes int8 values under ``k`` plus fp32 per-row scales
    under ``k + '_scale'``. Idempotence guard: a bank that already
    carries scale keys is returned unchanged."""
    if is_quantized(bank):
        return bank
    out = {}
    for k, w in bank.items():
        q, s = quantize_rows(w)
        out[k] = q
        out[k + SCALE_SUFFIX] = s
    return out


def dequantize_expert_bank(bank: dict) -> dict:
    """Quantized bank dict -> plain fp32 bank (scale keys folded in)."""
    if not is_quantized(bank):
        return bank
    return {k: dequantize_rows(w, bank[k + SCALE_SUFFIX])
            for k, w in bank.items() if not k.endswith(SCALE_SUFFIX)}


def weight_keys(bank: dict) -> list:
    """The value keys of a (possibly quantized) bank, scale keys
    excluded, in a stable order."""
    return sorted(k for k in bank if not k.endswith(SCALE_SUFFIX))
