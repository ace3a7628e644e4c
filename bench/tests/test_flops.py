"""The operation and byte counts against hand counts."""
import numpy as np
import pytest

from bench.flops import (decode_attn_work, gmm_work, roofline_s,
                         step_flops)

M = {"d": 4, "f": 8, "h": 4, "kv": 2, "hd": 2, "e": 4, "k": 2,
     "layers": 1, "vocab": 10}


def test_gmm_work_by_hand():
    flops, nbytes = gmm_work([3, 0, 1, 0], d=4, f=8)
    # 4 assignments x 3 projections x 2 x D x F
    assert flops == 4 * 3 * 2 * 4 * 8
    # 2 experts hold assignments: int8 weights 3*D*F + f32 scales
    # (2D + F) each; 4 rows x (D in + D out + F out + F in) in bf16
    assert nbytes == 2 * (96 + 16 * 4) + 4 * (4 + 4 + 8 + 8) * 2


def test_gmm_work_counts_experts_not_replicas():
    # the same routed work has the same cost however it is placed
    assert gmm_work([2, 2], 4, 8) == gmm_work(np.array([2, 2]), 4, 8)
    assert gmm_work([0, 0], 4, 8) == (0.0, 0.0)


def test_decode_attn_work_by_hand():
    flops, nbytes = decode_attn_work([5, 3], heads=4, kv_heads=2, hd=8)
    assert flops == 2 * 2 * 8 * 4 * 8          # QK^T and PV over 8 keys
    # each key: K and V (2 kv heads x 8) in bf16 plus an int32 position
    assert nbytes == 8 * (2 * 2 * 8 * 2 + 4) + 2 * 2 * 4 * 8 * 2


def _token(m, pos, head):
    d, h, kv, hd = m["d"], m["h"], m["kv"], m["hd"]
    per = (2 * d * h * hd * 2 + 2 * d * kv * hd * 2 + 4 * (pos + 1) * h * hd
           + 2 * d * m["e"] + 2 * m["k"] * 3 * d * m["f"])
    return m["layers"] * per + (2 * d * m["vocab"] if head else 0)


@pytest.mark.parametrize("layers", [1, 3])
def test_step_flops_token_by_token(layers):
    m = dict(M, layers=layers)
    prefill = [(0, 3, False), (5, 2, True)]
    decode = [7, 1]
    want = (_token(m, 0, False) + _token(m, 1, False) + _token(m, 2, False)
            + _token(m, 5, False) + _token(m, 6, True)
            + _token(m, 6, True) + _token(m, 0, True))
    assert step_flops(m, prefill, decode) == want


def test_roofline_names_its_bound():
    peaks = {"bf16_flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}
    assert roofline_s(1000.0, 50.0, peaks) == (10.0, "compute")
    assert roofline_s(100.0, 50.0, peaks) == (5.0, "memory")
