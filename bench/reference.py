"""Plain float32 reference forward of the benchmark's configurations.

Straight `jax.numpy` at `Precision.HIGHEST`: no cache, no kernels, no
batching, one sequence at a time over its whole length, layer by layer.
It imports nothing of the program. The weights come from
`bench/weights.py` and the seed; where the configuration serves its
experts from int8 slot banks (`serving.slot_dtype` int8 with the expert
runtime on), expert weights are those int8 values, quantised here with
the stated rule: one symmetric scale per row of the contraction axis,
`max|w_row| / 127`, values `round(w / scale)` clipped to [-127, 127].

The architecture follows the published descriptions: pre-norm decoder
layer, rotary embeddings (half-split rotation, base `rope_theta`),
grouped-query causal attention, a router over the experts, SwiGLU
experts, final norm and untied head. The file's layout keys
(`bench.weights.LAYOUT`) choose among:

- `norm`: RMSNorm `x * rsqrt(mean(x^2) + eps) * w`, or LayerNorm
  `(x - mean) * rsqrt(var + eps) * w + b`; eps is `rms_norm_eps`.
- `attention_bias`: biases on q, k, v and the output projection;
  `lm_head_bias`: a bias on the logits.
- `router`: `softmax_topk`, the softmax over the k selected router
  logits; or `sparsemixer`, PhiMoE's top-2 SparseMixer in its inference
  form (`_sparsemixer`), gates not renormalised.
- `rope_scaling`: LongRoPE, `inv_freq_i = 1 / (short_factor_i *
  theta^(2i/hd))`, cos and sin both times `short_mscale`. One departure
  from the published model: past `original_max_position_embeddings` it
  switches to `long_factor` and `long_mscale` and re-rotates; the
  reference refuses such a sequence instead (the benchmark's traffic
  stays under it).

Departures of the served program from the published models are stated
in each configuration file and `PERF.md`.

Beside the logits, the forward returns each position's router margin:
over the MoE layers, the smallest distance in router logits from a
boundary where the router's choice would change (`_margin_topk`,
`_sparsemixer`). It is exact for one layer; with more, a flip in an
earlier layer moves the later layers' logits, which the margin of those
layers does not see. `router="bf16"` rounds the router logits to
bfloat16 before routing, as the program computes them: the witness that
router flips explain a gap.

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


def _norm(x, p, m):
    w = p["scale"].astype(jnp.float32)
    if m["norm"] == "rmsnorm":
        return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True)
                                 + m["eps"]) * w
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + m["eps"]) * w \
        + p["bias"].astype(jnp.float32)


def _rope(x, pos, theta: float, scaling=None):
    """x (L, heads, hd), half-split rotation; `scaling` a LongRoPE
    `rope_scaling` (frozen, as `bench.weights.dims` gives it) or None."""
    half = x.shape[-1] // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    if scaling is not None:
        rs = dict(scaling)
        freqs = freqs / jnp.asarray(rs["short_factor"], jnp.float32)
    ang = pos.astype(jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    if scaling is not None:
        cos, sin = cos * rs["short_mscale"], sin * rs["short_mscale"]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _bias(y, p, name):
    return y + p[name].astype(jnp.float32) if name in p else y


def _margin_topk(s, k: int):
    """Distance of softmax-top-k routing from a change of the selected
    set: s_(k) - s_(k+1) (infinite where every expert is selected)."""
    if s.shape[-1] <= k:
        return jnp.full(s.shape[:-1], jnp.inf, jnp.float32)
    v = jax.lax.top_k(s, k + 1)[0]
    return v[..., k - 1] - v[..., k]


def _sparsemixer(s, eps: float):
    """PhiMoE's SparseMixer top-2 in its inference form. s (n, E) router
    logits in float32 -> gates (n, E) and margin (n,).

    Top 1: i1 = argmax s, m1 = max s; expert j is masked where
    (m1 - s_j) / max(|s_j|, m1) > 2 eps; g1 = exp(s_i1) over the sum of
    exp(s_j) over unmasked j. Top 2: the same on s with s_i1 = -inf, the
    mask tested with the original s_j against m2. Gates (g1, g2) are not
    renormalised. The margin is the least of s_(1) - s_(2), s_(2) - s_(3)
    and, for each mask test and each j it decides (not the test's own
    argmax, nor i1 in the second), |(m - s_j) - 2 eps max(|s_j|, m)|."""
    n, e = s.shape
    rows = jnp.arange(n)

    def top(scores):
        i = jnp.argmax(scores, -1)
        m = jnp.max(scores, -1, keepdims=True)
        over = (m - s) - 2 * eps * jnp.maximum(jnp.abs(s), m)
        g = jax.nn.softmax(jnp.where(over > 0, -jnp.inf, scores), -1)
        return i, g[rows, i], jnp.abs(over)

    i1, g1, d1 = top(s)
    first = jax.nn.one_hot(i1, e, dtype=bool)
    i2, g2, d2 = top(jnp.where(first, -jnp.inf, s))
    second = jax.nn.one_hot(i2, e, dtype=bool)
    gate = jnp.zeros((n, e), jnp.float32).at[rows, i1].add(g1) \
        .at[rows, i2].add(g2)
    v = jax.lax.top_k(s, min(3, e))[0]
    ties = [v[:, 0] - v[:, 1]] + ([v[:, 1] - v[:, 2]] if e > 2 else [])
    tests = [jnp.min(jnp.where(first, jnp.inf, d1), -1),
             jnp.min(jnp.where(first | second, jnp.inf, d2), -1)]
    return gate, jnp.min(jnp.stack(ties + tests), 0)


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


def _route(rl, m):
    """Router logits (n, E) -> gates (n, E) and margin (n,)."""
    n = rl.shape[0]
    if m["router"] == "sparsemixer":
        return _sparsemixer(rl, m["jitter"])
    top_v, top_i = jax.lax.top_k(rl, m["k"])
    top_w = jax.nn.softmax(top_v, axis=-1)
    gate = jnp.zeros((n, m["e"]), jnp.float32).at[
        jnp.arange(n)[:, None], top_i].add(top_w)
    return gate, _margin_topk(rl, m["k"])


@partial(jax.jit, static_argnums=(0, 3, 4))
def _forward(spec: tuple, w, tokens, lower: bool, router: str):
    m = dict(spec)
    theta, rs = m["theta"], m["rope_scaling"]
    n = tokens.shape[0]
    pos = jnp.arange(n)
    x = w["embed"][tokens].astype(jnp.float32)
    lay = w["layers"][0]
    margin = jnp.full((n,), jnp.inf, jnp.float32)
    for i in range(m["layers"]):
        p = jax.tree.map(lambda a: a[i], lay)
        a = p["attn"]
        hn = _norm(x, p["norm1"], m)
        q, k, v = (_bias(_mm(hn, a["w" + n_], lower), a, "b" + n_)
                   for n_ in "qkv")
        q = _rope(q.reshape(n, m["h"], m["hd"]), pos, theta, rs)
        k = _rope(k.reshape(n, m["kv"], m["hd"]), pos, theta, rs)
        v = v.reshape(n, m["kv"], m["hd"])
        x = x + _bias(_mm(_attention(q, k, v, m), a["wo"], lower), a, "bo")
        hn = _norm(x, p["norm2"], m)
        rl = _mm(hn, p["moe"]["router"]["w_gate"], lower)
        if router == "bf16":
            rl = rl.astype(jnp.bfloat16).astype(jnp.float32)
        gate, mg = _route(rl, m)
        margin = jnp.minimum(margin, mg)
        x = x + _experts(hn, p["moe"]["experts"], gate, m, lower)
    hn = _norm(x, w["final_norm"], m)
    logits = _bias(_mm(hn, w["head"], lower), w, "head_bias")
    return logits[:, :m["vocab"]], margin


def spec_of(c: dict) -> tuple:
    m = dims(c)
    m["eps"] = float(c["rms_norm_eps"])
    m["theta"] = float(c["rope_theta"])
    s = c["serving"]
    m["int8"] = s["slot_dtype"] == "int8" and \
        s.get("expert_runtime", "on") == "on"
    return tuple(sorted(m.items()))


ROUTER_LOGITS = ("f32", "bf16")


class Reference:
    """The reference model of one configuration and seed."""

    def __init__(self, c: dict, seed: int):
        self.spec = spec_of(c)
        self.w = make_weights(c, seed)

    def forward(self, tokens, lower: bool = False, router: str = "f32"):
        """(len(tokens), vocab) float32 logits on the device, position i
        predicting token i + 1, and (len(tokens),) router margins."""
        if router not in ROUTER_LOGITS:
            raise ValueError(f"router {router!r}: one of {ROUTER_LOGITS}")
        n = len(tokens)
        rs = dict(self.spec)["rope_scaling"]
        if rs is not None and n > dict(rs)["original_max_position_embeddings"]:
            raise ValueError(f"{n} tokens: past LongRoPE's "
                             "original_max_position_embeddings")
        pad = -(-n // BUCKET) * BUCKET
        t = np.zeros(pad, np.int32)
        t[:n] = tokens
        logits, margin = _forward(self.spec, self.w, jnp.asarray(t), lower,
                                  router)
        return logits[:n], margin[:n]

    def logits(self, tokens, lower: bool = False, router: str = "f32"):
        return self.forward(tokens, lower, router)[0]


@jax.jit
def _gaps(logits, picked):
    best = jnp.max(logits, axis=-1)
    got = jnp.take_along_axis(logits, picked[:, None], axis=-1)[:, 0]
    return best - got


def served_gaps(ref: Reference, prompt, served, lower_pick: bool = False,
                router: str = "f32") -> tuple:
    """Gap, under the float32 reference, between the best logit and the
    logit of each served token (teacher-forced on prompt + served), and
    the reference's router margin at each of those positions. With
    `lower_pick` the token compared at each position is the one the
    lower-precision control puts first, not the served one; with
    `router` "bf16", the one the reference with a bfloat16 router puts
    first (the witness)."""
    prompt = np.asarray(prompt, np.int32)
    served = np.asarray(served, np.int32)
    seq = np.concatenate([prompt, served[:-1]])
    at = len(prompt) - 1
    lg, margin = ref.forward(seq)
    picked = jnp.asarray(served)
    if lower_pick or router != "f32":
        picked = jnp.argmax(ref.logits(seq, lower=lower_pick,
                                       router=router)[at:], axis=-1)
    return np.asarray(_gaps(lg[at:], picked)), np.asarray(margin[at:])


@partial(jax.jit, static_argnums=(0,))
def _top1(spec: tuple, w):
    m = dict(spec)
    p = jax.tree.map(lambda a: a[0], w["layers"][0])
    x = w["embed"][:m["vocab"]].astype(jnp.float32)
    hn = _norm(x, p["norm2"], m)
    return jnp.argmax(_mm(hn, p["moe"]["router"]["w_gate"], False), -1)


def token_top1(c: dict, w) -> np.ndarray:
    """Each vocabulary id's top-1 expert under the layer-0 router applied
    to its normed embedding (the seed of the expert-affine topics)."""
    return np.asarray(_top1(spec_of(c), w))
