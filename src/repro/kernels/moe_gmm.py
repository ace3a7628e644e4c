"""Pallas TPU kernels for the MoE expert hot spot (DESIGN.md §3).

Layout: capacity-padded expert batches — x: (E, C, D) — exactly what the
EP all-to-all dispatch delivers to each device. Slot ``e`` holds
``group_sizes[e]`` rows at the front of its C; rows at or past it are
padding and come back as zeros. Four kernels share one schedule:

  * ``gmm``        — grouped matmul (E, C, D) x (E, D, F) -> (E, C, F)
  * ``fused_gate_up`` — silu(x@Wg) * (x@Wu) in one pass, the first half
                     of the swiglu expert MLP
  * ``gmm_quant`` / ``fused_gate_up_quant`` — the same over int8 slot
                     banks with per-row fp32 scales (repro.kernels.quant):
                     the int8 tile is converted to f32 and scaled in VMEM
                     right before its dot, so HBM holds the int8 bank only

Schedule (slot-major). The grid is ``(E, N/bn, K/bk)`` for an
(E, C, K) x (E, K, N) product, K innermost. Each step's weight tile
(and scale slice) comes in through the BlockSpec pipeline, so every
weight tile of a slot is read from HBM once per call and applied to all
of the slot's row tiles in that step. An int8 tile is dequantised once a
step, into f32 VMEM scratch that each row tile's dot reads; a float
tile is read by each dot from its pipeline buffer. The rows of x stay in HBM
(``pltpu.HBM``); the step copies the slot's row tiles ``0 ..
ceil(group_sizes[e]/bc) - 1`` itself into a two-buffer VMEM ring, the
first tile of the next step already started while this step computes.
A row tile at or past ``group_sizes[e]`` is never copied. The weight
index map of an empty slot repeats the block of the step beside it (the
first of the next slot with rows, else the last of the one before), so
an empty slot moves no weight bytes either. A tile's dot takes only
its filled rows, rounded up to 16, 32, 64 or the whole tile: with f32
operands the dot's cost grows with its rows, and a slot of a mixed step
holds a few rows of its 128-row tile. Partial products
accumulate over K in f32 VMEM scratch covering the slot's C rows; the
last K step writes the whole (C, bn) output block: the active tiles,
masked at ``group_sizes[e]``, and zeros elsewhere (the combine einsum
reads every row, so padding is written, never left unset).

Tiles follow the shapes (``plan``): one row tile of C rows up to 256
(rounded up to 16, the bf16 tiling a copy must align to), else 128-row
tiles, C padded up to a whole number of tiles; the widest bn in
{1024, 512, 256, 128} dividing N whose f32 accumulators stay within
4 MiB; then the deepest bk in {4096 .. 128} dividing K with at most
512 Ki weight elements a tile (0.5 MiB int8, 1 MiB bf16). A dimension
no candidate divides is taken whole. At Mixtral's widths and C = 1024,
gate/up runs 20 x 28 x 4 = 2240 grid steps (bk 1024, bn 512) and down
20 x 4 x 28 = 2240 (bk 512, bn 1024), against 143,360 each before.
VMEM per step, gate/up at C = 1024 with int8 weights: weight tiles
2 x 2 x 0.5 MiB, scale rows 2 x 2 x 32 KiB, x ring 2 x 128 x 1024 x 2 B
= 0.5 MiB, accumulators 2 x 1024 x 512 x 4 B = 4 MiB, output block
2 x 1024 x 512 x 2 B = 2 MiB, and the dequantised f32 tiles
2 x 2 MiB: about 13 MiB, under the 64 MiB limit the calls set (a v5e
core has 128 MiB).

Scales travel in row form (E, 1, K), a (1, bk) block per step, and are
turned into a (bk, 1) column in VMEM: the column form (E, K, 1) is laid
out in HBM with its unit minor dimension padded to 128 lanes.

Each kernel carries a fixed ``name`` and ``metadata={"kernel": name}``
(``moe_gmm``, ``moe_gmm_quant``, ``moe_ffn``, ``moe_ffn_quant``): the
custom call's ``kernel_metadata`` in a profiler trace's op text. Each
trace of a kernel records its static schedule in the ``obs`` gauge
``kernel_grid_steps`` {kernel, capacity}.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.obs.telemetry import record_kernel_schedule

_ONE_TILE_ROWS = 256        # C up to this is a single row tile
_ROW_TILE = 128             # rows per tile above it
_ROW_ALIGN = 16             # a row tile's multiple: bf16's HBM tiling
_N_TILES = (1024, 512, 256, 128)
_K_TILES = (4096, 2048, 1024, 512, 256, 128)
_ACC_BYTES = 4 * 2**20      # f32 accumulators of one step
_W_TILE_ELEMS = 512 * 1024  # weight elements of one tile
_VMEM_LIMIT = 64 * 2**20


class Plan(NamedTuple):
    """The tiling of one call: row tile ``bc`` over ``rows`` (C rounded
    up to a whole number of tiles), contraction tile ``bk``, output
    tile ``bn``, and the grid ``(slots, N/bn, K/bk)``."""
    bc: int
    bk: int
    bn: int
    rows: int
    grid: tuple

    @property
    def grid_steps(self) -> int:
        e, nn, nk = self.grid
        return e * nn * nk


def _pick(dim: int, candidates, fits) -> int:
    """The first candidate that divides `dim` and fits, else the
    smallest that divides, else `dim` whole."""
    divides = [b for b in candidates if dim % b == 0]
    for b in divides:
        if fits(b):
            return b
    return divides[-1] if divides else dim


def plan(slots: int, c: int, k: int, n: int, n_weights: int, *,
         bc: int | None = None, bk: int | None = None,
         bn: int | None = None) -> Plan:
    """Tiles for (slots, c, k) x `n_weights` x (slots, k, n), chosen
    from the shapes; a tile given here is taken as it is (bk and bn
    must divide k and n)."""
    if bc is None:
        bc = (pl.cdiv(c, _ROW_ALIGN) * _ROW_ALIGN if c <= _ONE_TILE_ROWS
              else _ROW_TILE)
    rows = pl.cdiv(c, bc) * bc
    if bn is None:
        bn = _pick(n, _N_TILES, lambda b: n_weights * rows * b * 4
                   <= _ACC_BYTES)
    if bk is None:
        bk = _pick(k, _K_TILES, lambda b: b * bn <= _W_TILE_ELEMS)
    if k % bk or n % bn:
        raise ValueError(f"tiles bk={bk}, bn={bn} must divide K={k}, "
                         f"N={n}")
    return Plan(bc, bk, bn, rows, (slots, n // bn, k // bk))


def row_tiles(group_size, bc: int):
    """Row tiles of a slot holding `group_size` rows: the tiles a step
    copies and computes."""
    return (group_size + bc - 1) // bc


def weight_source(group_sizes):
    """Per slot, (slot whose weight tiles its steps map to, whether to
    the last tile of that slot rather than the first): a slot with rows
    maps to itself; an empty one to the first tile of the next slot with
    rows, else to the last tile of the one before, so the pipeline sees
    the same block index again and copies nothing."""
    e = group_sizes.shape[0]
    idx = jnp.arange(e, dtype=jnp.int32)
    act = group_sizes > 0
    nxt = jax.lax.cummin(jnp.where(act, idx, e), reverse=True)
    prv = jax.lax.cummax(jnp.where(act, idx, -1))
    src = jnp.where(act, idx, jnp.where(nxt < e, nxt, jnp.maximum(prv, 0)))
    last = ~act & (nxt == e) & (prv >= 0)
    return src.astype(jnp.int32), last.astype(jnp.int32)


def weight_block(e, ni, ki, gs_ref, src_ref, last_ref, *, nn: int,
                 nk: int):
    """(slot, K block, N block) of the weight tile grid step
    (e, ni, ki) reads; arithmetic only, so it runs on scalar refs inside
    the kernel's index maps and on host arrays in a test."""
    act = jnp.minimum(gs_ref[e], 1)
    k_idle = last_ref[e] * (nk - 1)
    n_idle = last_ref[e] * (nn - 1)
    return (src_ref[e], act * ki + (1 - act) * k_idle,
            act * ni + (1 - act) * n_idle)


def _dot_rows(bc: int) -> list:
    """Row counts a tile's dot may take: 16, 32, 64 below the tile, and
    the tile."""
    return [r for r in (16, 32, 64) if r < bc] + [bc]


def _column(s_row):
    """(1, bk) scale row -> (bk, 1) column, to scale the rows of a
    (bk, bn) weight tile."""
    bk = s_row.shape[-1]
    return jnp.transpose(jnp.broadcast_to(s_row, (8, bk)))[:, :1]


def _kernel(gs_ref, src_ref, last_ref, x_hbm, *refs, n_w: int,
            quant: bool, swiglu: bool, p: Plan):
    n_in = n_w * (2 if quant else 1)
    w_refs, o_ref = refs[:n_in], refs[n_in]
    acc_refs = refs[n_in + 1:n_in + 1 + n_w]
    n_dq = n_w if quant else 0
    dq_refs = refs[n_in + 1 + n_w:n_in + 1 + n_w + n_dq]
    xbuf, sem, ring = refs[n_in + 1 + n_w + n_dq:]
    e, ni, ki = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    slots, nn, nk = p.grid
    bc, bk = p.bc, p.bk
    one_tile = p.rows == bc
    gs = gs_ref[e]
    nt = row_tiles(gs, bc)

    def x_copy(slot, t, kb, buf):
        # a single tile copies the whole row extent, which need not be
        # aligned to the HBM tiling as a slice of it must
        rows = slice(None) if one_tile else pl.ds(t * bc, bc)
        return pltpu.make_async_copy(
            x_hbm.at[slot, rows, pl.ds(kb * bk, bk)],
            xbuf.at[buf], sem.at[buf])

    # the next grid step, whose first row tile this step prefetches
    k_end, n_end = ki == nk - 1, ni == nn - 1
    e_next = jnp.where(k_end & n_end, e + 1, e)
    k_next = jnp.where(k_end, 0, ki + 1)
    next_active = (e_next < slots) & (
        gs_ref[jnp.minimum(e_next, slots - 1)] > 0)

    @pl.when((e == 0) & (ni == 0) & (ki == 0))
    def _prologue():
        ring[0] = 0

        @pl.when(gs > 0)
        def _():
            x_copy(e, 0, ki, 0).start()

    head = ring[0]

    def tiles(body):
        if one_tile:
            pl.when(nt > 0)(lambda: body(0))
        else:
            jax.lax.fori_loop(0, nt, lambda t, c: (body(t), c)[1], 0)

    def rows_of(t, size=bc):
        return (slice(0, size) if one_tile
                else pl.ds(pl.multiple_of(t * bc, bc), size))

    def compute(t):
        buf = (head + t) % 2
        x_copy(e, t, ki, buf).wait()
        if not one_tile:
            @pl.when(t + 1 < nt)
            def _():
                x_copy(e, t + 1, ki, 1 - buf).start()

        @pl.when((t + 1 == nt) & next_active)
        def _():
            x_copy(e_next, 0, k_next, 1 - buf).start()

        # the dot takes the tile's filled rows only, rounded up to a size
        # of `_dot_rows`: its cost grows with the rows it takes
        filled = jnp.minimum(gs - t * bc, bc)
        lo = 0
        for size in _dot_rows(bc):
            @pl.when((filled > lo) & (filled <= size))
            def _(size=size):
                xb = xbuf[buf, :size, :]
                if quant:
                    xb = xb.astype(jnp.float32)
                rows = rows_of(t, size)
                for w, acc in zip(weights, acc_refs):
                    d = jnp.dot(xb, w[...],
                                preferred_element_type=jnp.float32)

                    @pl.when(ki == 0)
                    def _():
                        acc[rows] = d

                    @pl.when(ki > 0)
                    def _():
                        acc[rows] += d
            lo = size

    if quant:
        # dequantise each weight tile once a step, for all its row tiles
        @pl.when(nt > 0)
        def _():
            for i in range(n_w):
                dq_refs[i][...] = (w_refs[2 * i][...].astype(jnp.float32)
                                   * _column(w_refs[2 * i + 1][...]))
        weights = dq_refs
    else:
        weights = w_refs

    tiles(compute)

    @pl.when((nt == 0) & next_active)
    def _():
        x_copy(e_next, 0, k_next, head).start()

    ring[0] = (head + nt) % 2

    @pl.when(ki == nk - 1)
    def _emit():
        o_ref[...] = jnp.zeros(o_ref.shape, o_ref.dtype)

        def write(t):
            rows = rows_of(t)
            if swiglu:
                h = jax.nn.silu(acc_refs[0][rows]) * acc_refs[1][rows]
            else:
                h = acc_refs[0][rows]
            r = t * bc + jax.lax.broadcasted_iota(jnp.int32, h.shape, 0)
            o_ref[rows] = jnp.where(r < gs, h, 0.0).astype(o_ref.dtype)

        tiles(write)


def _grouped(x, weights, scales, group_sizes, *, swiglu: bool, name: str,
             tiles: tuple, interpret: bool):
    """The one schedule behind the four public kernels: `weights` are
    one or two (E, K, N) banks, `scales` their (E, K) per-row scales
    (int8 banks) or None; `tiles` (bc, bf, bd) overrides of the planned
    row, output and contraction tiles, None to plan them."""
    e, c, k = x.shape
    n = weights[0].shape[-1]
    n_w = len(weights)
    quant = scales is not None
    bc, bf, bd = tiles
    p = plan(e, c, k, n, n_w, bc=bc, bk=bd, bn=bf)
    _, nn, nk = p.grid
    record_kernel_schedule(name, c, p.grid_steps)
    if p.rows != c:
        x = jnp.pad(x, ((0, 0), (0, p.rows - c), (0, 0)))
    src, last = weight_source(group_sizes)
    wmap = functools.partial(weight_block, nn=nn, nk=nk)

    def smap(*a):
        slot, kb, _ = wmap(*a)
        return slot, 0, kb

    w_spec = pl.BlockSpec((None, p.bk, p.bn), wmap)
    s_spec = pl.BlockSpec((None, 1, p.bk), smap)
    in_specs = [pl.BlockSpec(memory_space=pltpu.HBM)]
    operands = [x]
    for i, w in enumerate(weights):
        in_specs.append(w_spec)
        operands.append(w)
        if quant:
            in_specs.append(s_spec)
            operands.append(scales[i][:, None, :])
    kernel = functools.partial(_kernel, n_w=n_w, quant=quant,
                               swiglu=swiglu, p=p)
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=p.grid,
            in_specs=in_specs,
            out_specs=pl.BlockSpec((None, p.rows, p.bn),
                                   lambda e, ni, ki, *_: (e, 0, ni)),
            scratch_shapes=(
                [pltpu.VMEM((p.rows, p.bn), jnp.float32)] * n_w
                + [pltpu.VMEM((p.bk, p.bn), jnp.float32)] * (
                    n_w if quant else 0)
                + [pltpu.VMEM((2, p.bc, p.bk), x.dtype),
                   pltpu.SemaphoreType.DMA((2,)),
                   pltpu.SMEM((1,), jnp.int32)]),
        ),
        out_shape=jax.ShapeDtypeStruct((e, p.rows, n), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",) * 3,
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
        name=name,
        metadata={"kernel": name},
    )(group_sizes, src, last, *operands)
    return out if p.rows == c else out[:, :c]


def gmm(x, w, group_sizes, *, bc: int | None = None, bf: int | None = None,
        bd: int | None = None, interpret: bool = False):
    """(E, C, D) x (E, D, F) -> (E, C, F) with per-expert row masking.
    `bc`, `bf`, `bd` fix the row, output and contraction tiles (None:
    chosen from the shapes by `plan`); so for the other three."""
    return _grouped(x, [w], None, group_sizes, swiglu=False,
                    name="moe_gmm", tiles=(bc, bf, bd), interpret=interpret)


def gmm_quant(x, wq, scales, group_sizes, *, bc: int | None = None,
              bf: int | None = None, bd: int | None = None,
              interpret: bool = False):
    """(E, C, D) x int8 (E, D, F) with per-row scales (E, D) ->
    (E, C, F): dequantisation happens inside the tile loop, so HBM only
    ever holds the int8 bank + the tiny scale vectors."""
    return _grouped(x, [wq], [scales], group_sizes, swiglu=False,
                    name="moe_gmm_quant", tiles=(bc, bf, bd),
                    interpret=interpret)


def fused_gate_up(x, w_gate, w_up, group_sizes, *, bc: int | None = None,
                  bf: int | None = None, bd: int | None = None,
                  interpret: bool = False):
    """(E, C, D) -> (E, C, F): silu(x@Wg) * (x@Wu), fused."""
    return _grouped(x, [w_gate, w_up], None, group_sizes, swiglu=True,
                    name="moe_ffn", tiles=(bc, bf, bd), interpret=interpret)


def fused_gate_up_quant(x, wg_q, wg_s, wu_q, wu_s, group_sizes, *,
                        bc: int | None = None, bf: int | None = None,
                        bd: int | None = None, interpret: bool = False):
    """(E, C, D) -> (E, C, F): silu(x@Wg) * (x@Wu) over int8 weight
    banks + (E, D) per-row scales, dequantized tile-by-tile in VMEM."""
    return _grouped(x, [wg_q, wu_q], [wg_s, wu_s], group_sizes,
                    swiglu=True, name="moe_ffn_quant", tiles=(bc, bf, bd),
                    interpret=interpret)
