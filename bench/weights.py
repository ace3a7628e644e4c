"""Seeded weights of a benchmark configuration.

One jitted call makes every leaf on the device, in the type it is served
in, from the run's seed. The program under test receives these arrays;
the plain reference (`bench/reference.py`) makes them again from the
same seed after the program's state has been freed, so it takes nothing
the program made.

Scales keep every activation near unit size: embeddings ~ N(0, 1), so
the residual stream is led by each token's own embedding and the router
sees it (the skew of the expert-affine traffic rests on that);
projections ~ N(0, 1/fan_in); norm weights 1 + N(0, 0.1^2).
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp


def padded_vocab(vocab: int) -> int:
    return -(-vocab // 128) * 128


def key_from_seed(seed: int):
    """A PRNG key from any non-negative seed, wider than 32 bits too."""
    seed = int(seed)
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0x7FFFFFFF)


def dims(c: dict) -> dict:
    """The sizes the weights and the reference need, from a config file.
    The file states its norm; the layout built here (and in the
    reference) is rmsnorm without attention biases, and another is
    refused rather than served as this one."""
    if c["norm"] != "rmsnorm" or c.get("attention_bias", False):
        raise ValueError(f"unsupported layout: norm {c['norm']!r}, "
                         f"attention_bias {c.get('attention_bias')!r}")
    return {
        "d": c["hidden_size"], "f": c["intermediate_size"],
        "h": c["num_attention_heads"], "kv": c["num_key_value_heads"],
        "hd": c["hidden_size"] // c["num_attention_heads"],
        "e": c["num_local_experts"], "k": c["num_experts_per_tok"],
        "layers": c["num_hidden_layers"], "vocab": c["vocab_size"],
        "vpad": padded_vocab(c["vocab_size"]),
    }


def _normal(key, shape, std, dtype):
    return (jax.random.normal(key, shape, jnp.float32) * std).astype(dtype)


@partial(jax.jit, static_argnums=(0,))
def _make(spec: tuple, key):
    m = dict(spec)
    d, f, h, kv, hd, e = m["d"], m["f"], m["h"], m["kv"], m["hd"], m["e"]
    n, dt = m["layers"], jnp.bfloat16
    ks = iter(jax.random.split(key, 32))

    def norm(shape):
        return {"scale": (1.0 + 0.1 * jax.random.normal(next(ks), shape))
                .astype(dt)}

    attn = {"wq": _normal(next(ks), (n, d, h * hd), 1 / math.sqrt(d), dt),
            "wk": _normal(next(ks), (n, d, kv * hd), 1 / math.sqrt(d), dt),
            "wv": _normal(next(ks), (n, d, kv * hd), 1 / math.sqrt(d), dt),
            "wo": _normal(next(ks), (n, h * hd, d), 1 / math.sqrt(h * hd),
                          dt)}
    experts = {
        "w_gate": _normal(next(ks), (n, e, d, f), 1 / math.sqrt(d), dt),
        "w_up": _normal(next(ks), (n, e, d, f), 1 / math.sqrt(d), dt),
        "w_down": _normal(next(ks), (n, e, f, d), 1 / math.sqrt(f), dt)}
    vmask = (jnp.arange(m["vpad"]) < m["vocab"])
    embed = _normal(next(ks), (m["vpad"], d), 1.0, dt) * vmask[:, None]
    head = _normal(next(ks), (d, m["vpad"]), 1 / math.sqrt(d), dt) \
        * vmask[None, :]
    layer = {"norm1": norm((n, d)), "attn": attn, "norm2": norm((n, d)),
             "moe": {"router": {"w_gate": _normal(
                 next(ks), (n, d, e), 1 / math.sqrt(d), dt)},
                 "experts": experts}}
    return {"embed": embed.astype(dt), "layers": [layer],
            "final_norm": norm((d,)), "head": head.astype(dt)}


def make_weights(c: dict, seed: int):
    """The configuration's weights for `seed`, as the program takes them:
    one stacked layer group (the pattern has one sublayer; its leaves
    carry a leading axis of num_hidden_layers)."""
    return _make(tuple(sorted(dims(c).items())), key_from_seed(seed))
