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

A file's layout keys (`LAYOUT`; an absent key means Mixtral's layout)
add leaves only where they ask for them: a LayerNorm bias ~ N(0, 0.1^2)
on each norm; with `attention_bias` the biases `bq`, `bk`, `bv` and
`bo`; with `lm_head_bias` a `head_bias` on the logits, zero beyond the
vocabulary as the head is. Those biases are large enough that leaving
any one of them out moves the served tokens past a check's limit (std
`BIAS_STD`). The extra leaves draw from a key folded off the seed's
key, so the leaves of Mixtral's layout are the same for every layout.

Experts held of those routed: `n_routed_experts` (absent: the file's
`num_local_experts`) is the router's width, the published count;
`num_local_experts` is how many experts this chip holds, the ids
`[first_local_expert, first_local_expert + num_local_experts)`
(`first_local_expert` absent: 0). The router is made at the routed
width and expert leaves for the held experts only. A file that states
`n_routed_experts` draws each expert's leaves from a key folded by its
id, so expert e's leaves are the same whichever share holds it; a file
without the key draws them all at once, as Mixtral's pins have them.
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


# a configuration file's layout keys, at the values of Mixtral's layout
LAYOUT = {"norm": "rmsnorm", "attention_bias": False,
          "lm_head_bias": False, "router": "softmax_topk",
          "rope_scaling": None}
NORMS = ("rmsnorm", "layernorm")
ROUTERS = ("softmax_topk", "sparsemixer")
LONGROPE_KEYS = ("long_factor", "long_mscale",
                 "original_max_position_embeddings", "short_factor",
                 "short_mscale", "type")
BIAS_STD = 0.5


def frozen(v):
    """A JSON value as a hashable one: lists as tuples, objects as
    sorted tuples of (key, value)."""
    if isinstance(v, dict):
        return tuple(sorted((k, frozen(x)) for k, x in v.items()))
    if isinstance(v, list):
        return tuple(frozen(x) for x in v)
    return v


def experts_of(c: dict) -> tuple:
    """(routed, held, first) experts of a file's MoE layers."""
    held = c["num_local_experts"]
    return (c.get("n_routed_experts", held), held,
            c.get("first_local_expert", 0))


def is_share(c: dict) -> bool:
    """Whether the file holds fewer experts than it routes to."""
    routed, held, _ = experts_of(c)
    return held < routed


def _layout(c: dict, hd: int) -> dict:
    """The file's layout keys, checked; a value the weights and the
    reference do not build is refused rather than served as another."""
    lay = {key: c.get(key, dflt) for key, dflt in LAYOUT.items()}

    def refuse(why: str):
        raise ValueError(f"unsupported layout: {why}")
    routed, held, first = experts_of(c)
    if not all(isinstance(v, int) for v in (routed, held, first)) \
            or held < 1 or first < 0 or first + held > routed \
            or routed < c["num_experts_per_tok"]:
        refuse(f"experts {first}..{first + held - 1} held of {routed} "
               "routed")
    if lay["norm"] not in NORMS:
        refuse(f"norm {lay['norm']!r}")
    for key in ("attention_bias", "lm_head_bias"):
        if not isinstance(lay[key], bool):
            refuse(f"{key} {lay[key]!r}")
    if lay["router"] not in ROUTERS:
        refuse(f"router {lay['router']!r}")
    if lay["router"] == "sparsemixer":
        if c["num_experts_per_tok"] != 2 or "router_jitter_noise" not in c:
            refuse("router 'sparsemixer' needs num_experts_per_tok 2 and "
                   "router_jitter_noise")
        lay["jitter"] = float(c["router_jitter_noise"])
    rs = lay["rope_scaling"]
    if rs is not None:
        if not isinstance(rs, dict) or rs.get("type") != "longrope" \
                or tuple(sorted(rs)) != LONGROPE_KEYS \
                or len(rs["short_factor"]) != hd // 2 \
                or len(rs["long_factor"]) != hd // 2:
            refuse(f"rope_scaling {rs!r}")
        lay["rope_scaling"] = frozen(rs)
    return lay


def dims(c: dict) -> dict:
    """The sizes and the layout the weights and the reference need, from
    a config file."""
    hd = c["hidden_size"] // c["num_attention_heads"]
    routed, _, first = experts_of(c)
    return {
        "d": c["hidden_size"], "f": c["intermediate_size"],
        "h": c["num_attention_heads"], "kv": c["num_key_value_heads"],
        "hd": hd, "e": c["num_local_experts"],
        "routed": routed, "first": first,
        "per_expert_keys": "n_routed_experts" in c,
        "k": c["num_experts_per_tok"], "layers": c["num_hidden_layers"],
        "vocab": c["vocab_size"], "vpad": padded_vocab(c["vocab_size"]),
        **_layout(c, hd),
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
    shapes = {"w_gate": ((d, f), d), "w_up": ((d, f), d),
              "w_down": ((f, d), f)}
    if m["per_expert_keys"]:
        for _ in shapes:                  # the other leaves' keys stay
            next(ks)
        experts = _held_experts(key, shapes, m)
    else:
        experts = {name: _normal(next(ks), (n, e, *shape),
                                 1 / math.sqrt(fan), dt)
                   for name, (shape, fan) in shapes.items()}
    vmask = (jnp.arange(m["vpad"]) < m["vocab"])
    embed = _normal(next(ks), (m["vpad"], d), 1.0, dt) * vmask[:, None]
    head = _normal(next(ks), (d, m["vpad"]), 1 / math.sqrt(d), dt) \
        * vmask[None, :]
    layer = {"norm1": norm((n, d)), "attn": attn, "norm2": norm((n, d)),
             "moe": {"router": {"w_gate": _normal(
                 next(ks), (n, d, m["routed"]), 1 / math.sqrt(d), dt)},
                 "experts": experts}}
    w = {"embed": embed.astype(dt), "layers": [layer],
         "final_norm": norm((d,)), "head": head.astype(dt)}

    # the layout's own leaves, from a key of their own
    kx = iter(jax.random.split(jax.random.fold_in(key, 1), 8))
    if m["norm"] == "layernorm":
        for p, shape in ((layer["norm1"], (n, d)), (layer["norm2"], (n, d)),
                         (w["final_norm"], (d,))):
            p["bias"] = _normal(next(kx), shape, 0.1, dt)
    if m["attention_bias"]:
        for name, width in (("bq", h * hd), ("bk", kv * hd),
                            ("bv", kv * hd), ("bo", d)):
            attn[name] = _normal(next(kx), (n, width), BIAS_STD, dt)
    if m["lm_head_bias"]:
        w["head_bias"] = _normal(next(kx), (m["vpad"],), BIAS_STD, dt) \
            * vmask.astype(dt)
    return w


def _held_experts(key, shapes: dict, m: dict) -> dict:
    """Expert leaves (layers, held, ...) of the held experts, expert x's
    from the key folded by x, made one expert at a time."""
    n, dt = m["layers"], jnp.bfloat16
    ids = m["first"] + jnp.arange(m["e"])

    def one(x):
        kx = jax.random.split(
            jax.random.fold_in(jax.random.fold_in(key, 2), x), len(shapes))
        return {name: _normal(k, (n, *shape), 1 / math.sqrt(fan), dt)
                for k, (name, (shape, fan)) in zip(kx, shapes.items())}
    return {name: jnp.moveaxis(w, 0, 1)
            for name, w in jax.lax.map(one, ids).items()}


def make_weights(c: dict, seed: int):
    """The configuration's weights for `seed`, as the program takes them:
    one stacked layer group (the pattern has one sublayer; its leaves
    carry a leading axis of num_hidden_layers)."""
    return _make(tuple(sorted(dims(c).items())), key_from_seed(seed))
