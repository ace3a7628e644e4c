"""The plain reference at a size the CPU runs."""
import json
from pathlib import Path

import jax.numpy as jnp
import numpy as np
import pytest

from bench.reference import BUCKET, Reference, quantize_rows, served_gaps

CFG = json.loads((Path(__file__).parent / "data" / "tiny.json").read_text())


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
    g = served_gaps(ref, prompt, served)
    assert g.shape == (6,) and np.allclose(g, 0.0)
    bad = [(t + 1) % CFG["vocab_size"] for t in served]
    assert served_gaps(ref, prompt, bad).min() > 0


def test_control_is_lower_precision(ref):
    toks = np.arange(30) % CFG["vocab_size"]
    hi = np.asarray(ref.logits(toks))
    lo = np.asarray(ref.logits(toks, lower=True))
    err = np.abs(hi - lo).max()
    assert 1e-3 < err < 0.5 * np.abs(hi).max()
