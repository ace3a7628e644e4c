"""Pallas kernel allclose sweeps vs the pure-jnp oracle (deliverable c):
shapes x dtypes x group-size patterns, interpret mode on CPU."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import moe_gmm, ops, quant, ref
from repro.obs import Telemetry

KEY = jax.random.PRNGKey(42)


def _mk(e, c, d, f, dtype):
    ks = jax.random.split(KEY, 4)
    x = jax.random.normal(ks[0], (e, c, d), dtype)
    wg = (jax.random.normal(ks[1], (e, d, f), dtype) * 0.1).astype(dtype)
    wu = (jax.random.normal(ks[2], (e, d, f), dtype) * 0.1).astype(dtype)
    wd = (jax.random.normal(ks[3], (e, f, d), dtype) * 0.1).astype(dtype)
    return x, wg, wu, wd


SHAPES = [(2, 16, 32, 64), (4, 64, 128, 256), (3, 100, 96, 160),
          (1, 256, 64, 64)]


@pytest.mark.parametrize("shape", SHAPES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_gmm_matches_ref(shape, dtype):
    e, c, d, f = shape
    x, wg, _, _ = _mk(e, c, d, f, dtype)
    gs = jnp.asarray(np.random.default_rng(0).integers(0, c + 1, e),
                     jnp.int32)
    out = moe_gmm.gmm(x, wg, gs, interpret=True)
    expect = ref.gmm_ref(x, wg, gs)
    atol = 1e-4 if dtype == jnp.float32 else 5e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(expect, np.float32), atol=atol)


@pytest.mark.parametrize("shape", SHAPES[:3])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_fused_ffn_matches_ref(shape, dtype):
    e, c, d, f = shape
    x, wg, wu, wd = _mk(e, c, d, f, dtype)
    gs = jnp.asarray([c, max(0, c - 7), c // 2][:e] + [1] * max(0, e - 3),
                     jnp.int32)[:e]
    out = ops.expert_ffn(x, wg, wu, wd, gs, impl="pallas_interpret")
    expect = ref.expert_ffn_ref(x, wg, wu, wd, gs)
    atol = 2e-4 if dtype == jnp.float32 else 8e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(expect, np.float32), atol=atol)


def test_non_mxu_aligned_shapes_interpret():
    """fused_gate_up and gmm on shapes far off the 8x128 MXU lanes
    (C=7, D=96, F=40) in interpret mode: allclose to the oracle and
    masked rows exactly zero."""
    e, c, d, f = 3, 7, 96, 40
    x, wg, wu, wd = _mk(e, c, d, f, jnp.float32)
    gs = jnp.asarray([7, 3, 0], jnp.int32)
    mask = np.arange(c)[None] >= np.asarray(gs)[:, None]

    h = moe_gmm.fused_gate_up(x, wg, wu, gs, interpret=True)
    h_ref = jax.nn.silu(jnp.einsum("ecd,edf->ecf", x, wg)) \
        * jnp.einsum("ecd,edf->ecf", x, wu)
    h_ref = jnp.where(jnp.asarray(~mask)[..., None], h_ref, 0.0)
    np.testing.assert_allclose(np.asarray(h), np.asarray(h_ref), atol=2e-4)
    assert np.all(np.asarray(h)[mask] == 0)

    y = moe_gmm.gmm(x, wg, gs, interpret=True)
    np.testing.assert_allclose(np.asarray(y),
                               np.asarray(ref.gmm_ref(x, wg, gs)),
                               atol=1e-4)
    assert np.all(np.asarray(y)[mask] == 0)


# --------------------------------------------------- one schedule, four kernels

# name -> (slots, C, D, F, tile overrides); "decode" is one row tile,
# "mixed" three 128-row tiles over a padded C; both several D and F tiles
GEOMETRY = {
    "decode": (4, 16, 256, 384, dict(bf=128, bd=128)),
    "mixed": (4, 320, 256, 384, dict(bf=128, bd=128)),
    "small": (4, 32, 64, 64, {}),
    "narrow": (2, 16, 96, 40, {}),
    "sweep16": (2, 64, 128, 128, dict(bc=16, bf=32, bd=32)),
    "sweep64": (2, 64, 128, 128, dict(bc=64, bf=128, bd=128)),
    "sweep32": (2, 64, 128, 128, dict(bc=32, bf=64, bd=64)),
}

# name -> group sizes from (slots, C, row tile), cycled over the slots
PATTERNS = {
    "zero": lambda c, bc: [0],
    "one": lambda c, bc: [1, 0],
    "partial": lambda c, bc: [min(bc // 2 + 3, c), 0, 5, bc - 1],
    "tile": lambda c, bc: [bc, 0, min(2 * bc, c), 1],
    "full": lambda c, bc: [c],
    "ep_extent": lambda c, bc: [min(c // 2 + 5, c), 0, 3, c],
    "mixed_1_0_c_3": lambda c, bc: [1, 0, c, 3],
    "sweep_50_64": lambda c, bc: [50, 64],
}

FAMILIES = ("gmm", "ffn", "gmm_quant", "ffn_quant")

# the f32 tolerances of the former per-kernel tests: a gmm against its
# oracle to 1e-4, the two-kernel FFN to 2e-4
TOLERANCE = {"gmm": dict(atol=1e-4), "ffn": dict(atol=2e-4),
             "gmm_quant": dict(atol=5e-2, rtol=2e-2),
             "ffn_quant": dict(atol=5e-2, rtol=2e-2)}

CASES = (
    [(fam, geo, pat) for fam in FAMILIES for geo in ("decode", "mixed")
     for pat in ("zero", "one", "partial", "tile", "full", "ep_extent")]
    # the cases of the former test_group_size_zero_and_full ...
    + [("ffn", "small", pat) for pat in ("zero", "full", "mixed_1_0_c_3")]
    # ... of test_all_zero_group_sizes_interpret ...
    + [(fam, "narrow", "zero") for fam in ("gmm", "ffn")]
    # ... and of test_block_shape_sweep (different tilings agree)
    + [("gmm", geo, "sweep_50_64")
       for geo in ("sweep16", "sweep64", "sweep32")])


def _row_tile(geo):
    e, c, d, f, tiles = GEOMETRY[geo]
    return moe_gmm.plan(e, c, d, f, 2, bc=tiles.get("bc"),
                        bk=tiles.get("bd"), bn=tiles.get("bf")).bc


@pytest.mark.parametrize("fam,geo,pat", CASES,
                         ids=["-".join(c) for c in CASES])
def test_grouped_kernels_match_ref(fam, geo, pat):
    """Every kernel of the one slot-major schedule against its jnp
    oracle, in interpret mode: rows below group_sizes[e] match, and rows
    at or past it, filled with NaN on the way in, come out exactly
    zero. "ep_extent" is an occupied extent with zero rows inside it."""
    e, c, d, f, tiles = GEOMETRY[geo]
    bc = _row_tile(geo)
    sizes = PATTERNS[pat](c, bc)
    gs = jnp.asarray([sizes[i % len(sizes)] for i in range(e)], jnp.int32)
    quantized = fam.endswith("_quant")
    x, wg, wu, wd = _mk(e, c, d, f, jnp.float32)
    if pat == "ep_extent":
        x = x.at[0, 1:4].set(0.0)
    if quantized:
        x = x.astype(jnp.bfloat16)
    pad = np.arange(c)[None, :] >= np.asarray(gs)[:, None]
    x_nan = jnp.where(jnp.asarray(pad)[..., None], jnp.nan, x)
    down = dict(bc=tiles.get("bc"), bf=tiles.get("bf"), bd=tiles.get("bd"))
    if fam == "gmm":
        out = moe_gmm.gmm(x_nan, wg, gs, interpret=True, **tiles)
        expect = ref.gmm_ref(x, wg, gs)
    elif fam == "ffn":
        h = moe_gmm.fused_gate_up(x_nan, wg, wu, gs, interpret=True, **tiles)
        out = moe_gmm.gmm(h, wd, gs, interpret=True, **down)
        expect = ref.expert_ffn_ref(x, wg, wu, wd, gs)
    elif fam == "gmm_quant":
        q, s = quant.quantize_rows(wg)
        out = moe_gmm.gmm_quant(x_nan, q, s, gs, interpret=True, **tiles)
        expect = ref.gmm_ref_quant(x, q, s, gs)
    else:
        (gq, gsc), (uq, usc), (dq, dsc) = map(quant.quantize_rows,
                                               (wg, wu, wd))
        h = moe_gmm.fused_gate_up_quant(x_nan, gq, gsc, uq, usc, gs,
                                        interpret=True, **tiles)
        out = moe_gmm.gmm_quant(h, dq, dsc, gs, interpret=True, **down)
        expect = ref.expert_ffn_ref_quant(x, gq, gsc, uq, usc, dq, dsc, gs)
    out = np.asarray(out, np.float32)
    assert out.shape == (e, c, d if fam.startswith("ffn") else f)
    assert np.all(out[pad] == 0)
    tol = TOLERANCE[fam]
    np.testing.assert_allclose(out, np.asarray(expect, np.float32), **tol)


# the cell's shapes: 20 int8 slots of Mixtral-8x7B, decode and mixed C
CELL_SLOTS, CELL_D, CELL_F = 20, 4096, 14336
CELL_LOADS = {
    "decode": [4, 3, 2, 2, 1, 1, 1, 1, 1] + [0] * 11,
    "mixed": [40, 0, 26, 18, 12, 10, 0, 8, 7, 6, 5, 4, 3, 2, 1] + [0] * 5,
    "empty": [0] * 20,
    "full": None,                       # every row of every slot
}
MAX_GRID_STEPS = 2240                   # per call, at the cell's shapes


def _walk(gs, c, k, n, n_w):
    """Walk the grid of one call in order: the weight blocks the
    pipeline fetches (a fetch wherever the index map's block changes)
    with the slot of the step that fetches it, and the x row tiles the
    kernel's loop copies."""
    p = moe_gmm.plan(CELL_SLOTS, c, k, n, n_w)
    src, last = (np.asarray(a) for a in
                 moe_gmm.weight_source(jnp.asarray(gs, jnp.int32)))
    gs = np.asarray(gs, np.int32)
    _, nn, nk = p.grid
    fetched, prev, x_tiles, steps = [], None, {}, 0
    for e in range(CELL_SLOTS):
        for ni in range(nn):
            for ki in range(nk):
                blk = tuple(int(v) for v in moe_gmm.weight_block(
                    e, ni, ki, gs, src, last, nn=nn, nk=nk))
                if blk != prev:
                    fetched.append((e, blk))
                    prev = blk
                t = int(moe_gmm.row_tiles(gs[e], p.bc))
                x_tiles[e] = x_tiles.get(e, 0) + t
                steps += 1
    return p, fetched, x_tiles, steps


@pytest.mark.parametrize("c", (16, 1024))
@pytest.mark.parametrize("call", ("gate_up", "down"))
def test_schedule_reads_each_weight_tile_once(c, call):
    """The slot-major schedule at the cell's shapes: each weight block of
    a slot with rows is fetched exactly once a call, no step of an empty
    slot fetches one, no row tile at or past a slot's group size is
    copied, and a call takes at most MAX_GRID_STEPS grid steps."""
    k, n, n_w = ((CELL_D, CELL_F, 2) if call == "gate_up"
                 else (CELL_F, CELL_D, 1))
    for name, loads in CELL_LOADS.items():
        gs = [c] * CELL_SLOTS if loads is None else loads
        p, fetched, x_tiles, steps = _walk(gs, c, k, n, n_w)
        _, nn, nk = p.grid
        assert steps == p.grid_steps <= MAX_GRID_STEPS, name
        active = [e for e in range(CELL_SLOTS) if gs[e] > 0]
        blocks = [blk for _, blk in fetched]
        if not active:      # the pipeline's prologue fetches one block
            assert len(fetched) == 1, name
            continue
        assert len(blocks) == len(set(blocks)), name
        assert set(blocks) == {(e, kb, nb) for e in active
                               for kb in range(nk) for nb in range(nn)}
        # an empty slot's steps fetch no block of their own: at most the
        # first block of the next slot with rows, which that slot's first
        # step then finds in place (counted once above)
        for step_slot, (slot, kb, nb) in fetched:
            if gs[step_slot] == 0:
                nxt = min(a for a in active if a > step_slot)
                assert (slot, kb, nb) == (nxt, 0, 0), name
        for e in range(CELL_SLOTS):
            per_step = -(-gs[e] // p.bc)
            assert x_tiles[e] == nn * nk * per_step, (name, e)
            # the last tile copied starts below the group size
            assert per_step == 0 or (per_step - 1) * p.bc < gs[e]


def test_schedule_gauge_recorded_at_trace():
    """Tracing a kernel records its static schedule, grid steps per
    call by kernel and capacity, in every Telemetry, one made after the
    trace too."""
    e, c, d, f = 2, 16, 96, 40
    x, wg, wu, _ = _mk(e, c, d, f, jnp.float32)
    gs = jnp.asarray([3, 0], jnp.int32)
    before = Telemetry()
    moe_gmm.fused_gate_up(x, wg, wu, gs, interpret=True)
    after = Telemetry()
    p = moe_gmm.plan(e, c, d, f, 2)
    for tel in (before, after):
        got = tel.registry.as_dict()
        key = '{kernel="moe_ffn",capacity="16"}'
        assert got["kernel_grid_steps" + key] == p.grid_steps
