"""The plain reference at a size the CPU runs."""
import hashlib
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench.harness import gap_numbers
from bench.reference import (BUCKET, Reference, _gaps, _margin_topk, _norm,
                             _rope, _sparsemixer, quantize_rows,
                             served_gaps)
from bench.weights import dims, frozen, make_weights

DATA = Path(__file__).parent / "data"
CFG = json.loads((DATA / "tiny.json").read_text())
PHI = json.loads((DATA / "tiny-phimoe.json").read_text())
PINS = json.loads((DATA / "mixtral-layout-pins.json").read_text())


def test_int8_rule_by_hand():
    w = jnp.asarray([[1.0, -2.0, 0.5], [0.0, 0.0, 0.0]])
    q, s = quantize_rows(w, 127)
    assert np.allclose(s[:, 0], [2.0 / 127, 1.0])
    assert np.array_equal(q[0], [64.0, -127.0, 32.0])   # round half even
    assert np.array_equal(q[1], [0.0, 0.0, 0.0])


@pytest.fixture(scope="module")
def ref():
    return Reference(CFG, 7)


def test_causal_and_padding_invariant(ref):
    toks = np.random.default_rng(0).integers(0, CFG["vocab_size"], 40)
    full = np.asarray(ref.logits(toks))
    part = np.asarray(ref.logits(toks[:25]))
    assert full.shape == (40, CFG["vocab_size"])
    assert np.allclose(full[:25], part, atol=1e-5)
    long = np.concatenate([toks, np.zeros(BUCKET + 3 - 40, np.int64)])
    assert np.allclose(np.asarray(ref.logits(long))[:40], full, atol=1e-5)


def test_gaps_of_greedy_tokens_are_zero(ref):
    prompt = np.arange(10) % CFG["vocab_size"]
    seq = list(prompt)
    for _ in range(6):      # greedy decode through the reference itself
        seq.append(int(np.argmax(np.asarray(ref.logits(seq))[-1])))
    served = seq[len(prompt):]
    g, margin = served_gaps(ref, prompt, served)
    assert g.shape == margin.shape == (6,) and np.allclose(g, 0.0)
    assert (margin > 0).all()
    bad = [(t + 1) % CFG["vocab_size"] for t in served]
    assert served_gaps(ref, prompt, bad)[0].min() > 0


@pytest.mark.parametrize("lower_pick, router", [
    (False, "f32"), (True, "f32"), (False, "bf16")],
    ids=["served", "control", "witness"])
def test_served_gaps_read_as_at_the_request_length(ref, lower_pick,
                                                   router):
    """The check cuts the served positions out of the padded programs'
    output on the host; its numbers are those of the same programs read
    at the request's own length, bit for bit."""
    rng = np.random.default_rng(5)
    prompt = rng.integers(0, CFG["vocab_size"], 37)
    served = rng.integers(0, CFG["vocab_size"], 9)
    seq = np.concatenate([prompt, served[:-1]])
    at = len(prompt) - 1
    lg, margin = ref.forward(seq)
    picked = jnp.asarray(served)
    if lower_pick or router != "f32":
        picked = jnp.argmax(ref.logits(seq, lower=lower_pick,
                                       router=router)[at:], axis=-1)
    g, mg = served_gaps(ref, prompt, served, lower_pick, router)
    assert np.array_equal(g, np.asarray(_gaps(lg[at:], picked)))
    assert np.array_equal(mg, np.asarray(margin[at:]))


def test_control_is_lower_precision(ref):
    toks = np.arange(30) % CFG["vocab_size"]
    hi = np.asarray(ref.logits(toks))
    lo = np.asarray(ref.logits(toks, lower=True))
    err = np.abs(hi - lo).max()
    assert 1e-3 < err < 0.5 * np.abs(hi).max()


# ------------------------------------------------ Mixtral's layout held


def test_mixtral_layout_weights_unchanged():
    h = hashlib.sha256()
    for leaf in jax.tree.leaves(make_weights(CFG, 7)):
        h.update(np.asarray(leaf).tobytes())
    assert h.hexdigest() == PINS["tiny_weights_sha256"]


def test_mixtral_layout_logits_unchanged(ref):
    toks = np.random.default_rng(0).integers(0, CFG["vocab_size"], 40)
    got = np.asarray(ref.logits(toks))[::8, ::64]
    assert np.allclose(got, PINS["tiny_logits"], rtol=0, atol=1e-6)


# ------------------------------------------------ the layout keys by hand


REST = np.zeros(14)


@pytest.mark.parametrize("logits, gates", [
    ([3.0, 2.99, 1.0, *REST], (0.50250, 1.0)),      # 1 / (1 + e^-0.01)
    ([-1.0, -1.01, -3.0, *(REST - 5)], (0.50250, 1.0)),
    ([8.0, 6.0, *np.arange(4.0, -24.0, -2.0)], (1.0, 1.0))],
    ids=["near_tie", "near_tie_negative", "far_apart"])
def test_sparsemixer_by_hand(logits, gates):
    gate, margin = _sparsemixer(jnp.asarray([logits], jnp.float32), 0.01)
    gate = np.asarray(gate)[0]
    assert np.flatnonzero(gate).tolist() == [0, 1]
    assert np.allclose(gate[:2], gates, rtol=0, atol=1e-6)
    # nearest boundary: the top two's own distance, or the first mask
    # test of expert 1: |(m1 - s_1) - 0.02 max(|s_1|, m1)|
    s = np.asarray(logits)
    near = min(s[0] - s[1], abs((s[0] - s[1]) - 0.02 * max(abs(s[1]), s[0])))
    assert np.isclose(float(margin[0]), near, rtol=0, atol=1e-6)


def test_softmax_topk_margin_is_kth_less_next():
    s = jnp.asarray([[3.0, 1.0, 2.0, 0.5], [0.0, 0.1, -4.0, 0.3]])
    assert np.allclose(_margin_topk(s, 2), [1.0, 0.1])
    assert np.isinf(_margin_topk(s, 4)).all()


def _longrope(factors, mscale):
    return frozen({"type": "longrope", "short_factor": factors,
                   "long_factor": factors, "short_mscale": mscale,
                   "long_mscale": mscale,
                   "original_max_position_embeddings": 4096})


def test_longrope_unit_factors_is_rope_and_mscale_scales_scores():
    rng = np.random.default_rng(1)
    q = jnp.asarray(rng.normal(size=(50, 4, 32)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(50, 4, 32)), jnp.float32)
    pos = jnp.arange(50)
    ones = [1.0] * 16
    assert np.array_equal(_rope(q, pos, 1e4, _longrope(ones, 1.0)),
                          _rope(q, pos, 1e4))

    def scores(rs):
        return np.einsum("qhd,khd->hqk", _rope(q, pos, 1e4, rs),
                         _rope(k, pos, 1e4, rs))
    c = 1.3
    assert np.allclose(scores(_longrope(ones, c)), c * c * scores(None),
                       rtol=1e-5, atol=1e-4)
    # the factors divide each frequency
    f = [2.0] * 16
    rs = _longrope(f, 1.0)
    assert np.allclose(_rope(q, 2 * pos, 1e4, rs), _rope(q, pos, 1e4),
                       atol=1e-4)


def test_longrope_refuses_past_original_positions():
    c = json.loads(json.dumps(PHI))
    c["rope_scaling"]["original_max_position_embeddings"] = 64
    ref = Reference(c, 3)
    assert ref.logits(np.arange(64)).shape == (64, PHI["vocab_size"])
    with pytest.raises(ValueError, match="original_max_position"):
        ref.logits(np.arange(65))


def test_layernorm_matches_its_formula():
    rng = np.random.default_rng(2)
    x = rng.normal(size=(5, 16)) * 3 + 1
    w, b = rng.normal(size=16), rng.normal(size=16)
    got = _norm(jnp.asarray(x, jnp.float32),
                {"scale": jnp.asarray(w), "bias": jnp.asarray(b)},
                {"norm": "layernorm", "eps": 1e-5})
    want = (x - x.mean(-1, keepdims=True)) \
        / np.sqrt(x.var(-1, keepdims=True) + 1e-5) * w + b
    assert np.allclose(got, want, atol=1e-5)


@pytest.fixture(scope="module")
def phi():
    return Reference(PHI, 11)


def test_phi_layout_at_tiny_size(phi):
    from bench import traffic as TR
    from bench.reference import token_top1
    attn = phi.w["layers"][0]["attn"]
    assert {"bq", "bk", "bv", "bo"} <= set(attn)
    assert "bias" in phi.w["final_norm"]
    hb = np.asarray(phi.w["head_bias"], np.float32)
    assert hb.shape == (512,) and not hb[PHI["vocab_size"]:].any()
    toks = np.arange(100) % PHI["vocab_size"]
    lg, margin = phi.forward(toks)
    assert lg.shape == (100, PHI["vocab_size"])
    assert np.isfinite(np.asarray(lg)).all()
    assert (np.asarray(margin) >= 0).all()
    topics = TR.topic_sets(token_top1(PHI, phi.w), 16)
    assert sum(map(len, topics)) == PHI["vocab_size"] and len(topics) > 8


def test_phi_widths_file_passes_dims():
    m = dims(json.loads((DATA / "phimoe-widths.json").read_text()))
    assert (m["d"], m["f"], m["e"], m["k"], m["hd"], m["vpad"]) \
        == (4096, 6400, 8, 2, 128, 32128)
    assert (m["routed"], m["first"], m["layers"]) == (16, 0, 4)
    assert (m["norm"], m["router"], m["jitter"]) \
        == ("layernorm", "sparsemixer", 0.01)


def test_bf16_router_witness(phi):
    toks = np.random.default_rng(4).integers(0, PHI["vocab_size"], 200)
    exact = np.asarray(phi.logits(toks))
    witness = np.asarray(phi.logits(toks, router="bf16"))
    assert 0 < np.abs(witness - exact).max() < np.abs(exact).max()
    with pytest.raises(ValueError, match="router"):
        phi.logits(toks, router="f16")


LEAVES = [("layers", 0, "norm1", "bias"), ("layers", 0, "norm2", "bias"),
          ("final_norm", "bias"), ("layers", 0, "attn", "bq"),
          ("layers", 0, "attn", "bk"), ("layers", 0, "attn", "bv"),
          ("layers", 0, "attn", "bo"), ("head_bias",)]


@pytest.mark.parametrize("leaf", LEAVES,
                         ids=["/".join(map(str, p)) for p in LEAVES])
def test_each_new_leaf_is_needed(phi, leaf):
    """The reference without one leaf, put in the program's place, fails
    the tiny file's check: the tokens it puts first read past the
    limits under the whole reference."""
    toks = np.random.default_rng(5).integers(0, PHI["vocab_size"], 300)
    lg, margin = phi.forward(toks)
    w = jax.tree.map(lambda a: a, phi.w)
    node = w
    for k in leaf[:-1]:
        node = node[k]
    node[leaf[-1]] = jnp.zeros_like(node[leaf[-1]])
    less = Reference.__new__(Reference)
    less.spec, less.w = phi.spec, w
    picked = jnp.argmax(less.logits(toks), axis=-1)
    got = gap_numbers(np.asarray(_gaps(lg, picked)), np.asarray(margin),
                      PHI.get("router_margin", 0.0))
    assert all(got[k] > lim for k, lim in PHI["check"].items())


def test_router_margin_readings_at_tiny_size():
    from bench.router_margin import readings
    mix = json.loads((DATA / "tiny-mix.json").read_text())
    out = readings(PHI, mix, 2**31 + 5, 3)
    assert out["requests"] == 3 and out["own_gap_max"] == 0.0
    shares = list(out["clear_share"].values())
    assert shares[0] == 1.0 and shares == sorted(shares, reverse=True)
    assert out["witness"]["mean_gap"]["0.0"] >= 0.0
