"""One chip's share of each expert layer, at a size the CPU runs: the
router at its routed width, the held experts' part of the result, and
what the harness and the counts make of a share."""
import dataclasses
import gc
import json
import time
import weakref

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import harness as H
from bench.flops import gmm_work, held_loads, step_flops
from bench.reference import Reference, _moe, spec_of
from bench.tests.tiny_root import CELL, DATA, make_root
from bench.weights import dims, make_weights

PHI = json.loads((DATA / "tiny-phimoe.json").read_text())
TINY = json.loads((DATA / "tiny.json").read_text())   # Mixtral's layout
SEED = 2**31 + 3117
UNCUT = dict(PHI, n_routed_experts=16)          # 16 routed, 16 held


def share(first: int, held: int = 8, **kw) -> dict:
    return dict(PHI, n_routed_experts=16, num_local_experts=held,
                first_local_expert=first, **kw)


def _layer0(w):
    return jax.tree.map(lambda a: a[0], w["layers"][0])


@pytest.mark.parametrize("first", [0, 4, 8])
def test_expert_leaves_follow_their_id(first):
    """Expert x's leaves are the same whichever share holds it, and every
    leaf outside the experts is the uncut file's."""
    whole = make_weights(UNCUT, SEED)
    part = make_weights(share(first), SEED)
    ex_w = whole["layers"][0]["moe"].pop("experts")
    ex_p = part["layers"][0]["moe"].pop("experts")
    for name in ("w_gate", "w_up", "w_down"):
        assert ex_p[name].shape[1] == 8
        assert np.array_equal(ex_p[name], ex_w[name][:, first:first + 8])
    for a, b in zip(jax.tree.leaves(whole), jax.tree.leaves(part)):
        assert np.array_equal(a, b)


def _moe_out(c: dict, hn):
    spec = spec_of(c)
    return _moe(hn, _layer0(make_weights(c, SEED))["moe"], dict(spec),
                False, "f32")


@pytest.fixture(scope="module")
def normed():
    return jnp.asarray(np.random.default_rng(8).normal(
        size=(64, PHI["hidden_size"])), jnp.float32)


def test_shares_add_up_to_the_uncut_layer(normed):
    y, _ = _moe_out(UNCUT, normed)
    y0, _ = _moe_out(share(0), normed)
    y8, _ = _moe_out(share(8), normed)
    # The uncut layer adds its 16 gated expert outputs in one running
    # float32 sum, the shares in two sums of 8 joined by one addition:
    # the same terms grouped otherwise. Top-2 routing leaves two nonzero
    # terms a row, so the groupings differ only in how the compiler
    # rounds the gate's product into the running sum: a float32 ulp
    # (2^-24) of the row's terms at most, at each of the 16 additions.
    # 16 x 2^-24 of the largest output gives that room (the CPU reads
    # about a thirtieth of it); a missing or doubled expert is off by a
    # whole term.
    tol = 16 * 2.0**-24 * float(jnp.abs(y).max())
    assert float(jnp.abs(y0 + y8 - y).max()) <= tol
    assert float(jnp.abs(y0 - y).max()) > 100 * tol


def test_each_share_has_the_uncut_router_margin(normed):
    _, mg = _moe_out(UNCUT, normed)
    for first in (0, 8):
        assert np.array_equal(_moe_out(share(first), normed)[1], mg)
    # through a whole forward at one layer too: the router sees the same
    # input whichever share the chip holds
    toks = np.random.default_rng(9).integers(0, PHI["vocab_size"], 80)
    want = np.asarray(Reference(UNCUT, SEED).forward(toks)[1])
    for first in (0, 8):
        got = np.asarray(Reference(share(first), SEED).forward(toks)[1])
        assert np.array_equal(got, want)


def test_uncut_count_stated_draws_the_same_model():
    """A file stating n_routed_experts equal to num_local_experts is the
    whole layer: the same router, the same counts, the same program."""
    m, u = dims(PHI), dims(UNCUT)
    assert (m["routed"], m["e"], u["routed"], u["e"]) == (16, 16, 16, 16)
    a, b = make_weights(PHI, SEED), make_weights(UNCUT, SEED)
    assert np.array_equal(a["layers"][0]["moe"]["router"]["w_gate"],
                          b["layers"][0]["moe"]["router"]["w_gate"])
    assert H.program_config(dict(TINY, n_routed_experts=4)) \
        == H.program_config(TINY)


@pytest.mark.parametrize("change", [
    dict(first_local_expert=9), dict(num_local_experts=0),
    dict(first_local_expert=-1), dict(n_routed_experts=1,
                                      num_local_experts=1)],
    ids=["past_routed", "none_held", "negative_first", "fewer_than_k"])
def test_share_that_does_not_fit_is_refused(change):
    c = dict(share(8), **change)
    with pytest.raises(ValueError, match="unsupported layout: experts"):
        dims(c)
    with pytest.raises(ValueError, match="unsupported layout"):
        make_weights(c, SEED)


SHARE_KEYS = ("num_local_experts", "first_local_expert")
TINY_SHARE = dict(TINY, n_routed_experts=8, first_local_expert=4)


def test_share_refused_while_the_program_lacks_its_fields():
    from repro.configs.base import MoESpec
    declared = {f.name for f in dataclasses.fields(MoESpec)}
    lacking = [k for k in SHARE_KEYS if k not in declared]
    if not lacking:
        cfg = H.program_config(TINY_SHARE)
        assert (cfg.moe.num_local_experts, cfg.moe.first_local_expert) \
            == (4, 4)
        return
    with pytest.raises(ValueError, match="unsupported layout") as err:
        H.program_config(TINY_SHARE)
    assert all(f"no {k} " in str(err.value) for k in lacking)


def test_share_passed_on_once_the_program_declares_it(monkeypatch):
    from repro.configs import base
    plain = {f.name for f in dataclasses.fields(base.MoESpec)}
    extra = [(k, int, dataclasses.field(default=-1)) for k in SHARE_KEYS
             if k not in plain]
    held = dataclasses.make_dataclass("HeldMoESpec", extra,
                                      bases=(base.MoESpec,), frozen=True)
    monkeypatch.setattr(base, "MoESpec", held)
    cfg = H.program_config(TINY_SHARE)
    assert cfg.moe.num_experts == 8             # the router's width
    assert (cfg.moe.num_local_experts, cfg.moe.first_local_expert) \
        == (4, 4)
    # a whole layer passes neither
    cfg = H.program_config(TINY)
    assert cfg.moe.num_experts == 4
    assert all(getattr(cfg.moe, k) == -1 for k, *_ in extra)


# ------------------------------------------------------------- counts


M = {"d": 4, "f": 8, "h": 4, "kv": 2, "hd": 2, "k": 2, "layers": 2,
     "vocab": 10, "e": 2, "routed": 4, "first": 2}


def test_share_flops_by_hand():
    loads = np.array([[1, 2, 3, 4], [0, 5, 0, 1]])    # (layers, routed)
    assert held_loads(M, loads).tolist() == [[3, 4], [0, 1]]
    assert held_loads(M, loads[:, 2:]).tolist() == [[3, 4], [0, 1]]
    with pytest.raises(ValueError, match="routes 4 and holds 2"):
        held_loads(M, loads[:, :3])
    d, f, h, kv, hd = 4, 8, 4, 2, 2
    # one decode row at context 3: per layer q, o, k, v, the router at
    # its routed width 4 and attention over 3 keys; the 8 held
    # assignments (3 + 4 + 0 + 1) of both layers once each; the head
    per_layer = 2 * d * (2 * h * hd + 2 * kv * hd) + 2 * d * 4 \
        + 4 * h * hd * 3
    want = 2 * per_layer + 8 * 2 * 3 * d * f + 2 * d * 10
    assert step_flops(M, [], [3], loads) == want
    # without loads a share counts no expert work
    assert step_flops(M, [], [3]) == want - 8 * 2 * 3 * d * f


MIXTRAL = dims(json.loads(
    (H.BENCH / "configs" / "mixtral-8x7b.rt1.json").read_text()))


@pytest.mark.parametrize("prefill, decode, want", [
    ([(0, 64, False), (704, 64, True), (1200, 17, True)],
     [769, 1031, 2100, 33], 120281006080.0),
    ([], [769, 1031], 2130968576.0)], ids=["mixed", "decode"])
def test_mixtral_step_flops_pinned(prefill, decode, want):
    """Read at the parent of the share's counts; loads change nothing
    for a whole layer."""
    assert step_flops(MIXTRAL, prefill, decode) == want
    assert step_flops(MIXTRAL, prefill, decode,
                      np.ones((1, 8), np.int64)) == want


@pytest.mark.parametrize("loads, want", [
    ([37, 0, 120, 5, 0, 0, 9, 61], (81738596352.0, 898359296.0)),
    ([3, 1, 0, 0, 2, 5, 1, 4], (5637144576.0, 1058684928.0))],
    ids=["skewed", "light"])
def test_mixtral_gmm_work_pinned(loads, want):
    held = held_loads(MIXTRAL, np.asarray([loads]))[0]
    assert gmm_work(held, MIXTRAL["d"], MIXTRAL["f"]) == want


# ------------------------------------------------------ runs of a cell


def _run(root, **kw):
    return H.run_cell(CELL, SEED, 2.0, False, time.perf_counter(),
                      require_chip=False, root=root, **kw)


@pytest.mark.parametrize("slots, total", [(None, 12), (2, 8)],
                         ids=["default", "two"])
def test_slots_per_device_sets_the_runtime_slots(tmp_path, monkeypatch,
                                                 slots, total):
    """Absent, the controller's default (3 a device over 4 devices for 4
    experts); stated, the runtime follows it."""
    root = make_root(tmp_path)
    if slots is not None:
        path = root / "bench" / "configs" / "tiny.json"
        c = json.loads(path.read_text())
        c["serving"]["slots_per_device"] = slots
        path.write_text(json.dumps(c))
    seen = []
    rewrite = H.rewrite_all_slots

    def spy(engine):
        seen.append(engine.result().runtime.total_slots)
        return rewrite(engine)
    monkeypatch.setattr(H, "rewrite_all_slots", spy)
    res = _run(root)
    assert res["correct"], res["checks"]
    assert seen == [total]


def test_run_keeps_no_copy_of_the_weights(tmp_path, monkeypatch):
    """Once the engine is built, nothing of the harness holds the made
    weights: an engine that serves its own copy leaves them unheld
    through the warm-up and the window."""
    import bench.weights
    import repro.serving.engine as E
    made = []
    make = bench.weights.make_weights

    def tracked(c, seed):
        w = make(c, seed)
        made.extend(weakref.ref(x) for x in jax.tree.leaves(w))
        return w

    class OwnCopy(E.ServingEngine):
        def __init__(self, cfg, params, **kw):
            super().__init__(cfg, jax.tree.map(jnp.copy, params), **kw)

    held = []
    serve = H.serve_open

    def probe(*a, **kw):
        gc.collect()
        held.append(sum(r() is not None for r in made))
        return serve(*a, **kw)
    monkeypatch.setattr(bench.weights, "make_weights", tracked)
    monkeypatch.setattr(E, "ServingEngine", OwnCopy)
    monkeypatch.setattr(H, "serve_open", probe)
    res = _run(make_root(tmp_path))
    assert res["correct"], res["checks"]
    assert made and held == [0, 0]      # warm-up stretch, then the window


def test_router_margin_readings_of_a_share_without_decode():
    from bench.router_margin import readings
    mix = json.loads((DATA / "tiny-mix.json").read_text())
    out = readings(share(8, num_hidden_layers=2), mix, SEED, 3,
                   decode=False)
    assert out["requests"] == 3 and out["own_gap_max"] is None
    shares = list(out["clear_share"].values())
    assert shares[0] == 1.0 and shares == sorted(shares, reverse=True)
    assert out["seconds"]["weights"] > 0
