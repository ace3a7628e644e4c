"""Plain float32 reference forward of the benchmark's configurations.

Straight `jax.numpy` at `Precision.HIGHEST`: no cache, no kernels, no
batching, one sequence at a time over its whole length, layer by layer.
It imports nothing of the program. The weights come from
`bench/weights.py` and the seed; where the configuration serves its
experts from int8 slot banks (`serving.slot_dtype` int8 with the expert
runtime on), expert weights are those int8 values, quantised here with
the stated rule: one symmetric scale per row of the contraction axis,
`max|w_row| / 127`, values `round(w / scale)` clipped to [-127, 127].

The architecture follows the published descriptions: pre-norm (RMSNorm)
decoder layer, rotary embeddings (half-split rotation, base `rope_theta`),
grouped-query causal attention, top-k routing with the softmax taken
over the k selected router logits, SwiGLU experts, final norm and
untied head. Departures of the served program from the published models
are stated in each configuration file and `PERF.md`.

`lower=True` computes the control: every matmul operand that the
configuration holds in bfloat16 is rounded to float8 e4m3 (one scale per
tensor), and int8 expert weights to int4 (per-row scale
`max|w_row| / 7`) -- the next precision below each stated one.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from bench.weights import dims, make_weights

HI = jax.lax.Precision.HIGHEST
BUCKET = 1024          # sequences are padded to a multiple of this
QUERY_BLOCK = 512      # attention is computed in blocks of queries


def quantize_rows(w, levels: int):
    """(..., R, C) -> symmetric per-row values and scales, as float32."""
    w = w.astype(jnp.float32)
    amax = jnp.max(jnp.abs(w), axis=-1, keepdims=True)
    scale = jnp.where(amax > 0, amax / levels, 1.0)
    q = jnp.clip(jnp.round(w / scale), -levels, levels)
    return q, scale


def _fp8(x):
    """Round to float8 e4m3 under one per-tensor scale."""
    amax = jnp.max(jnp.abs(x))
    s = jnp.where(amax > 0, amax / 448.0, 1.0)
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _mm(a, b, lower: bool):
    a = a.astype(jnp.float32)
    b = b.astype(jnp.float32)
    if lower:
        a, b = _fp8(a), _fp8(b)
    return jnp.matmul(a, b, precision=HI)


def _norm(x, p, eps: float):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * p["scale"].astype(jnp.float32)


def _rope(x, pos, theta: float):
    """x (L, heads, hd), half-split rotation."""
    half = x.shape[-1] // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = pos.astype(jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _attention(q, k, v, m):
    """Causal GQA. q (L, H, hd), k/v (L, KV, hd) -> (L, H*hd)."""
    n, h, hd = q.shape
    g = h // m["kv"]
    k = jnp.repeat(k, g, axis=1)
    v = jnp.repeat(v, g, axis=1)
    kpos = jnp.arange(n)

    def block(i):
        qb = jax.lax.dynamic_slice_in_dim(q, i * QUERY_BLOCK, QUERY_BLOCK)
        s = jnp.einsum("qhd,khd->hqk", qb, k, precision=HI) / np.sqrt(hd)
        qpos = i * QUERY_BLOCK + jnp.arange(QUERY_BLOCK)
        s = jnp.where(kpos[None, None, :] <= qpos[None, :, None], s,
                      -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("hqk,khd->qhd", p, v, precision=HI)

    out = jax.lax.map(block, jnp.arange(n // QUERY_BLOCK))
    return out.reshape(n, h * hd)


def _experts(h, ex, gate, m, lower: bool):
    """sum_e gate[:, e] * FFN_e(h) over every expert (dense). Int8
    experts take their int8 values (int4 in the control); bfloat16
    experts their own values (float8 in the control)."""
    levels = 7 if lower else 127

    def weight(w):
        if m["int8"]:
            return jnp.multiply(*quantize_rows(w, levels))
        w = w.astype(jnp.float32)
        return _fp8(w) if lower else w

    def one(acc, xs):
        wg, wu, wd, g_e = xs
        wg, wu, wd = weight(wg), weight(wu), weight(wd)
        a = h.astype(jnp.float32)
        if lower:
            a = _fp8(a)
        y = jax.nn.silu(jnp.matmul(a, wg, precision=HI)) \
            * jnp.matmul(a, wu, precision=HI)
        if lower:
            y = _fp8(y)
        y = jnp.matmul(y, wd, precision=HI)
        return acc + g_e[:, None] * y, None

    acc = jnp.zeros(h.shape, jnp.float32)
    acc, _ = jax.lax.scan(one, acc, (ex["w_gate"], ex["w_up"],
                                     ex["w_down"], gate.T))
    return acc


@partial(jax.jit, static_argnums=(0, 3))
def _forward(spec: tuple, w, tokens, lower: bool):
    m = dict(spec)
    eps, theta = m["eps"], m["theta"]
    n = tokens.shape[0]
    pos = jnp.arange(n)
    x = w["embed"][tokens].astype(jnp.float32)
    lay = w["layers"][0]
    for i in range(m["layers"]):
        p = jax.tree.map(lambda a: a[i], lay)
        a = p["attn"]
        hn = _norm(x, p["norm1"], eps)
        q, k, v = (_mm(hn, a[n_], lower) for n_ in ("wq", "wk", "wv"))
        q = _rope(q.reshape(n, m["h"], m["hd"]), pos, theta)
        k = _rope(k.reshape(n, m["kv"], m["hd"]), pos, theta)
        v = v.reshape(n, m["kv"], m["hd"])
        x = x + _mm(_attention(q, k, v, m), a["wo"], lower)
        hn = _norm(x, p["norm2"], eps)
        rl = _mm(hn, p["moe"]["router"]["w_gate"], lower)
        top_v, top_i = jax.lax.top_k(rl, m["k"])
        top_w = jax.nn.softmax(top_v, axis=-1)
        gate = jnp.zeros((n, m["e"]), jnp.float32).at[
            jnp.arange(n)[:, None], top_i].add(top_w)
        x = x + _experts(hn, p["moe"]["experts"], gate, m, lower)
    hn = _norm(x, w["final_norm"], eps)
    return _mm(hn, w["head"], lower)[:, :m["vocab"]]


def spec_of(c: dict) -> tuple:
    m = dims(c)
    m["eps"] = float(c["rms_norm_eps"])
    m["theta"] = float(c["rope_theta"])
    s = c["serving"]
    m["int8"] = s["slot_dtype"] == "int8" and \
        s.get("expert_runtime", "on") == "on"
    return tuple(sorted(m.items()))


class Reference:
    """The reference model of one configuration and seed."""

    def __init__(self, c: dict, seed: int):
        self.spec = spec_of(c)
        self.w = make_weights(c, seed)

    def logits(self, tokens, lower: bool = False):
        """(len(tokens), vocab) float32 logits on the device, position i
        predicting token i + 1."""
        n = len(tokens)
        pad = -(-n // BUCKET) * BUCKET
        t = np.zeros(pad, np.int32)
        t[:n] = tokens
        return _forward(self.spec, self.w, jnp.asarray(t), lower)[:n]


@jax.jit
def _gaps(logits, picked):
    best = jnp.max(logits, axis=-1)
    got = jnp.take_along_axis(logits, picked[:, None], axis=-1)[:, 0]
    return best - got


def served_gaps(ref: Reference, prompt, served, lower_pick: bool = False):
    """Gap, under the float32 reference, between the best logit and the
    logit of each served token (teacher-forced on prompt + served).
    With `lower_pick` the token compared at each position is the one the
    lower-precision control puts first, not the served one."""
    prompt = np.asarray(prompt, np.int32)
    served = np.asarray(served, np.int32)
    seq = np.concatenate([prompt, served[:-1]])
    lg = ref.logits(seq)[len(prompt) - 1:]
    picked = jnp.asarray(served)
    if lower_pick:
        picked = jnp.argmax(ref.logits(seq, lower=True)[len(prompt) - 1:],
                            axis=-1)
    return np.asarray(_gaps(lg, picked))


@partial(jax.jit, static_argnums=(0,))
def _top1(spec: tuple, w):
    m = dict(spec)
    p = jax.tree.map(lambda a: a[0], w["layers"][0])
    x = w["embed"][:m["vocab"]].astype(jnp.float32)
    hn = _norm(x, p["norm2"], m["eps"])
    return jnp.argmax(_mm(hn, p["moe"]["router"]["w_gate"], False), -1)


def token_top1(c: dict, w) -> np.ndarray:
    """Each vocabulary id's top-1 expert under the layer-0 router applied
    to its normed embedding (the seed of the expert-affine topics)."""
    return np.asarray(_top1(spec_of(c), w))
