"""The grouped-GMM kernels alone, at the serving cell's shapes, so a
schedule can be timed against another on the chip.

Quantised family: 20 int8 slots at Mixtral-8x7B's widths (D 4096,
F 14336), `fused_gate_up_quant` (gate/up) and `gmm_quant` (down), at

  * ``decode``: C 16, rows 4,3,2,2,1,1,1,1,1 and 11 empty slots,
  * ``mixed``:  C 1024, rows 40,26,18,12,10,8,7,6,5,4,3,2,1 and 7 empty,
  * ``full``:   C 1024, every row of every slot;

bf16 family: 8 experts, every row (the grouped dispatch's case),
`fused_gate_up` and `gmm` at C 16 and 1024. Weights and inputs are
drawn from ``--seed``; every module given gets the same ones.

Each ``--module LABEL=PATH`` loads a ``moe_gmm.py`` from a file (another
checkout's, say), beside this tree's own (label ``tree``), so two
schedules are timed in one process on one chip. One JSON line per case
and module: the median of ``--iters`` timed calls (each after a warm
call), in ms, the largest difference from the first module's output,
and whether every row at or past a group size reads exactly 0.

    python -m benchmarks.gmm_schedule_bench --module parent=P/moe_gmm.py
    python -m benchmarks.gmm_schedule_bench --small   # CPU, interpret
"""
from __future__ import annotations

import argparse
import importlib.util
import json
import statistics
import time

import jax
import jax.numpy as jnp
import numpy as np

DECODE = [4, 3, 2, 2, 1, 1, 1, 1, 1]
MIXED = [40, 0, 26, 18, 12, 10, 0, 8, 7, 6, 5, 4, 3, 2, 1]


def _load(label: str, path: str):
    spec = importlib.util.spec_from_file_location(f"_gmm_{label}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _sizes(pattern, slots: int, c: int):
    if pattern is None:
        return jnp.full((slots,), c, jnp.int32)
    gs = (pattern + [0] * slots)[:slots]
    return jnp.asarray([min(g, c) for g in gs], jnp.int32)


def _time(fn, iters: int) -> float:
    fn().block_until_ready()
    times = []
    for _ in range(iters):
        t0 = time.perf_counter()
        fn().block_until_ready()
        times.append(time.perf_counter() - t0)
    return statistics.median(times) * 1e3


def _check(out, first, gs):
    out = np.asarray(out, np.float32)
    pad = np.arange(out.shape[1])[None, :] >= np.asarray(gs)[:, None]
    diff = 0.0 if first is None else float(np.max(np.abs(out - first)))
    return out, diff, bool(np.all(out[pad] == 0))


def _run(case: str, call: str, gs, modules, operands, iters: int,
         interpret: bool):
    """Time `call` of each module on `operands` (group sizes last),
    passed as arguments so the jitted program holds no weights."""
    first = None
    for label, mod in modules:
        fn = jax.jit(lambda *a, f=getattr(mod, call): f(
            *a, interpret=interpret))
        ms = _time(lambda: fn(*operands), iters)
        out, diff, zero_ok = _check(fn(*operands), first, gs)
        if first is None:
            first = out
        print(json.dumps({"case": case, "call": call, "module": label,
                          "ms": ms, "max_diff": diff,
                          "zero_ok": zero_ok}), flush=True)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--module", action="append", default=[],
                    metavar="LABEL=PATH")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--iters", type=int, default=10)
    ap.add_argument("--small", action="store_true",
                    help="small widths in interpret mode (CPU)")
    args = ap.parse_args(argv)

    from repro.kernels import moe_gmm
    modules = [("tree", moe_gmm)] + [
        (label, _load(label, path)) for label, path in
        (m.split("=", 1) for m in args.module)]
    if args.small:
        slots, experts, d, f, c_big = 4, 2, 256, 512, 256
    else:
        slots, experts, d, f, c_big = 20, 8, 4096, 14336, 1024
    interpret = args.small
    key = jax.random.PRNGKey(args.seed % 2**31)
    ks = jax.random.split(key, 12)

    def int8(k, shape):
        # four int8 weights per random 32-bit word: no wider temporary
        words = jax.random.bits(k, shape[:-1] + (shape[-1] // 4,))
        return jax.lax.bitcast_convert_type(words, jnp.int8).reshape(shape)

    def scale(k, shape):
        return jax.random.uniform(k, shape, jnp.float32, 0.5e-3, 1.5e-3)

    gq, uq, dq = (int8(ks[i], (slots, a, b)) for i, (a, b) in
                  enumerate(((d, f), (d, f), (f, d))))
    gsc, usc, dsc = (scale(ks[3 + i], (slots, a)) for i, a in
                     enumerate((d, d, f)))
    for case, c, pattern in (("decode", 16, DECODE),
                             ("mixed", c_big, MIXED),
                             ("full", c_big, None)):
        gs = _sizes(pattern, slots, c)
        x = jax.random.normal(ks[6], (slots, c, d), jnp.bfloat16)
        h = jax.random.normal(ks[7], (slots, c, f), jnp.bfloat16)
        _run(case, "fused_gate_up_quant", gs, modules,
             (x, gq, gsc, uq, usc, gs), args.iters, interpret)
        _run(case, "gmm_quant", gs, modules, (h, dq, dsc, gs),
             args.iters, interpret)
    del gq, uq, dq
    wg, wu, wd = (jax.random.normal(ks[8 + i], (experts, a, b),
                                    jnp.bfloat16) * 0.02
                  for i, (a, b) in enumerate(((d, f), (d, f), (f, d))))
    for c in (16, c_big):
        gs = jnp.full((experts,), c, jnp.int32)
        x = jax.random.normal(ks[6], (experts, c, d), jnp.bfloat16)
        h = jax.random.normal(ks[7], (experts, c, f), jnp.bfloat16)
        _run(f"bf16_c{c}", "fused_gate_up", gs, modules,
             (x, wg, wu, gs), args.iters, interpret)
        _run(f"bf16_c{c}", "gmm", gs, modules, (h, wd, gs), args.iters,
             interpret)


if __name__ == "__main__":
    main()
