"""Pallas TPU kernels for the MoE expert hot spot (DESIGN.md §3).

Layout: capacity-padded expert batches — x: (E, C, D) — exactly what the
EP all-to-all dispatch delivers to each device. Two kernels:

  * ``gmm``        — grouped matmul (E, C, D) x (E, D, F) -> (E, C, F)
  * ``expert_ffn`` — fused SwiGLU expert MLP: silu(x@Wg) * (x@Wu) in one
                     pass (halves HBM traffic of the activation tensors)

plus the DEQUANTIZING family (``gmm_quant`` / ``fused_gate_up_quant``)
over int8 slot banks with per-row fp32 scales (repro.kernels.quant):
the int8 weight tile is rescaled in VMEM immediately before its dot, so
HBM holds ~0.25x the weight bytes and the fp32 weights never exist
off-chip — the storage format serverless expert slot banks transfer and
bill in under ``cfg.moe.slot_dtype = "int8"``.

TPU adaptation (not a CUDA port): BlockSpec tiles are MXU-aligned
(multiples of 8x128 lanes; default 128x128x512), the D-contraction is the
innermost ("arbitrary") grid axis so partial products accumulate in a
VMEM scratch accumulator in f32, and whole row-tiles beyond an expert's
``group_size`` are skipped with @pl.when — the TPU analogue of
megablocks' skipping of empty CUDA blocks.

Weights stream HBM->VMEM tile-by-tile via BlockSpec index maps; with the
default tiling the VMEM working set is
  x-tile 128x512x2B + w-tile 512x128x2B + acc 128x128x4B  ~= 0.33 MB
per buffer (x2 for double buffering), comfortably inside 16 MB VMEM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _gmm_kernel(gs_ref, x_ref, w_ref, o_ref, acc_ref, *, nd: int):
    """grid = (E, C//bc, F//bf, D//bd); D is innermost."""
    e = pl.program_id(0)
    ci = pl.program_id(1)
    di = pl.program_id(3)
    bc = x_ref.shape[0]

    @pl.when(di == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # skip row-tiles entirely beyond this expert's group size
    row0 = ci * bc
    active = row0 < gs_ref[e]

    @pl.when(active)
    def _mm():
        acc_ref[...] += jnp.dot(x_ref[...], w_ref[...],
                                preferred_element_type=jnp.float32)

    @pl.when(di == nd - 1)
    def _out():
        rows = row0 + jax.lax.broadcasted_iota(jnp.int32, o_ref.shape, 0)
        mask = rows < gs_ref[e]
        o_ref[...] = jnp.where(mask, acc_ref[...],
                               0.0).astype(o_ref.dtype)


def gmm(x, w, group_sizes, *, bc: int = 128, bf: int = 128, bd: int = 512,
        interpret: bool = False):
    """(E, C, D) x (E, D, F) -> (E, C, F) with per-expert row masking."""
    e, c, d = x.shape
    f = w.shape[-1]
    bc, bf, bd = min(bc, c), min(bf, f), min(bd, d)
    grid = (e, pl.cdiv(c, bc), pl.cdiv(f, bf), pl.cdiv(d, bd))
    kernel = functools.partial(_gmm_kernel, nd=grid[3])
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[
                pl.BlockSpec((None, bc, bd),
                             lambda e, ci, fi, di, gs: (e, ci, di)),
                pl.BlockSpec((None, bd, bf),
                             lambda e, ci, fi, di, gs: (e, di, fi)),
            ],
            out_specs=pl.BlockSpec((None, bc, bf),
                                   lambda e, ci, fi, di, gs: (e, ci, fi)),
            scratch_shapes=[pltpu.VMEM((bc, bf), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((e, c, f), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(group_sizes, x, w)


def _scale_column(scales):
    """(E, R) per-row scales -> (E, R, 1). A (bd, 1) block of the column
    form satisfies the TPU tiling rule (last two block dims divisible by
    (8, 128) or equal to the array's), which a (bd,) slice of the (E, R)
    form does not; inside the kernel it broadcasts over the weight
    tile's lanes exactly as ``s[:, None]`` would."""
    return scales[..., None]


def _gmm_q_kernel(gs_ref, x_ref, w_ref, s_ref, o_ref, acc_ref, *, nd: int):
    """Dequantizing grouped matmul: w is an int8 tile, s the fp32
    per-row scales of its contraction slice. The fp32 weight tile exists
    only in VMEM for the duration of one dot — never in HBM."""
    e = pl.program_id(0)
    ci = pl.program_id(1)
    di = pl.program_id(3)
    bc = x_ref.shape[0]

    @pl.when(di == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    row0 = ci * bc
    active = row0 < gs_ref[e]

    @pl.when(active)
    def _mm():
        w = w_ref[...].astype(jnp.float32) * s_ref[...]
        acc_ref[...] += jnp.dot(x_ref[...].astype(jnp.float32), w,
                                preferred_element_type=jnp.float32)

    @pl.when(di == nd - 1)
    def _out():
        rows = row0 + jax.lax.broadcasted_iota(jnp.int32, o_ref.shape, 0)
        mask = rows < gs_ref[e]
        o_ref[...] = jnp.where(mask, acc_ref[...],
                               0.0).astype(o_ref.dtype)


def gmm_quant(x, wq, scales, group_sizes, *, bc: int = 128, bf: int = 128,
              bd: int = 512, interpret: bool = False):
    """(E, C, D) x int8 (E, D, F) with per-row scales (E, D) ->
    (E, C, F): dequantisation happens inside the tile loop, so HBM only
    ever holds the int8 bank + the tiny scale vectors (~0.25x the fp32
    traffic of ``gmm``)."""
    e, c, d = x.shape
    f = wq.shape[-1]
    bc, bf, bd = min(bc, c), min(bf, f), min(bd, d)
    grid = (e, pl.cdiv(c, bc), pl.cdiv(f, bf), pl.cdiv(d, bd))
    kernel = functools.partial(_gmm_q_kernel, nd=grid[3])
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[
                pl.BlockSpec((None, bc, bd),
                             lambda e, ci, fi, di, gs: (e, ci, di)),
                pl.BlockSpec((None, bd, bf),
                             lambda e, ci, fi, di, gs: (e, di, fi)),
                pl.BlockSpec((None, bd, 1),
                             lambda e, ci, fi, di, gs: (e, di, 0)),
            ],
            out_specs=pl.BlockSpec((None, bc, bf),
                                   lambda e, ci, fi, di, gs: (e, ci, fi)),
            scratch_shapes=[pltpu.VMEM((bc, bf), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((e, c, f), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(group_sizes, x, wq, _scale_column(scales))


def _ffn_kernel(gs_ref, x_ref, wg_ref, wu_ref, o_ref, accg_ref, accu_ref,
                *, nd: int):
    """Fused silu(x@Wg) * (x@Wu). grid = (E, C//bc, F//bf, D//bd)."""
    e = pl.program_id(0)
    ci = pl.program_id(1)
    di = pl.program_id(3)
    bc = x_ref.shape[0]

    @pl.when(di == 0)
    def _init():
        accg_ref[...] = jnp.zeros_like(accg_ref)
        accu_ref[...] = jnp.zeros_like(accu_ref)

    row0 = ci * bc
    active = row0 < gs_ref[e]

    @pl.when(active)
    def _mm():
        xb = x_ref[...]
        accg_ref[...] += jnp.dot(xb, wg_ref[...],
                                 preferred_element_type=jnp.float32)
        accu_ref[...] += jnp.dot(xb, wu_ref[...],
                                 preferred_element_type=jnp.float32)

    @pl.when(di == nd - 1)
    def _out():
        rows = row0 + jax.lax.broadcasted_iota(jnp.int32, o_ref.shape, 0)
        mask = rows < gs_ref[e]
        h = jax.nn.silu(accg_ref[...]) * accu_ref[...]
        o_ref[...] = jnp.where(mask, h, 0.0).astype(o_ref.dtype)


def fused_gate_up(x, w_gate, w_up, group_sizes, *, bc: int = 128,
                  bf: int = 128, bd: int = 512, interpret: bool = False):
    """(E, C, D) -> (E, C, F): silu(x@Wg) * (x@Wu), fused."""
    e, c, d = x.shape
    f = w_gate.shape[-1]
    bc, bf, bd = min(bc, c), min(bf, f), min(bd, d)
    grid = (e, pl.cdiv(c, bc), pl.cdiv(f, bf), pl.cdiv(d, bd))
    kernel = functools.partial(_ffn_kernel, nd=grid[3])
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[
                pl.BlockSpec((None, bc, bd),
                             lambda e, ci, fi, di, gs: (e, ci, di)),
                pl.BlockSpec((None, bd, bf),
                             lambda e, ci, fi, di, gs: (e, di, fi)),
                pl.BlockSpec((None, bd, bf),
                             lambda e, ci, fi, di, gs: (e, di, fi)),
            ],
            out_specs=pl.BlockSpec((None, bc, bf),
                                   lambda e, ci, fi, di, gs: (e, ci, fi)),
            scratch_shapes=[pltpu.VMEM((bc, bf), jnp.float32),
                            pltpu.VMEM((bc, bf), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((e, c, f), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(group_sizes, x, w_gate, w_up)


def _ffn_q_kernel(gs_ref, x_ref, wg_ref, wgs_ref, wu_ref, wus_ref, o_ref,
                  accg_ref, accu_ref, *, nd: int):
    """Dequantizing fused silu(x@Wg) * (x@Wu): both int8 weight tiles
    are rescaled in VMEM right before their dot (one scale vector per
    contraction slice, broadcast over the F tile)."""
    e = pl.program_id(0)
    ci = pl.program_id(1)
    di = pl.program_id(3)
    bc = x_ref.shape[0]

    @pl.when(di == 0)
    def _init():
        accg_ref[...] = jnp.zeros_like(accg_ref)
        accu_ref[...] = jnp.zeros_like(accu_ref)

    row0 = ci * bc
    active = row0 < gs_ref[e]

    @pl.when(active)
    def _mm():
        xb = x_ref[...].astype(jnp.float32)
        wg = wg_ref[...].astype(jnp.float32) * wgs_ref[...]
        wu = wu_ref[...].astype(jnp.float32) * wus_ref[...]
        accg_ref[...] += jnp.dot(xb, wg,
                                 preferred_element_type=jnp.float32)
        accu_ref[...] += jnp.dot(xb, wu,
                                 preferred_element_type=jnp.float32)

    @pl.when(di == nd - 1)
    def _out():
        rows = row0 + jax.lax.broadcasted_iota(jnp.int32, o_ref.shape, 0)
        mask = rows < gs_ref[e]
        h = jax.nn.silu(accg_ref[...]) * accu_ref[...]
        o_ref[...] = jnp.where(mask, h, 0.0).astype(o_ref.dtype)


def fused_gate_up_quant(x, wg_q, wg_s, wu_q, wu_s, group_sizes, *,
                        bc: int = 128, bf: int = 128, bd: int = 512,
                        interpret: bool = False):
    """(E, C, D) -> (E, C, F): silu(x@Wg) * (x@Wu) over int8 weight
    banks + (E, D) per-row scales, dequantized tile-by-tile in VMEM."""
    e, c, d = x.shape
    f = wg_q.shape[-1]
    bc, bf, bd = min(bc, c), min(bf, f), min(bd, d)
    grid = (e, pl.cdiv(c, bc), pl.cdiv(f, bf), pl.cdiv(d, bd))
    kernel = functools.partial(_ffn_q_kernel, nd=grid[3])
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[
                pl.BlockSpec((None, bc, bd),
                             lambda e, ci, fi, di, gs: (e, ci, di)),
                pl.BlockSpec((None, bd, bf),
                             lambda e, ci, fi, di, gs: (e, di, fi)),
                pl.BlockSpec((None, bd, 1),
                             lambda e, ci, fi, di, gs: (e, di, 0)),
                pl.BlockSpec((None, bd, bf),
                             lambda e, ci, fi, di, gs: (e, di, fi)),
                pl.BlockSpec((None, bd, 1),
                             lambda e, ci, fi, di, gs: (e, di, 0)),
            ],
            out_specs=pl.BlockSpec((None, bc, bf),
                                   lambda e, ci, fi, di, gs: (e, ci, fi)),
            scratch_shapes=[pltpu.VMEM((bc, bf), jnp.float32),
                            pltpu.VMEM((bc, bf), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((e, c, f), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(group_sizes, x, wg_q, _scale_column(wg_s), wu_q,
      _scale_column(wu_s))
