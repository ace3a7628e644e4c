"""Chip smoke test: serve Mixtral-8x7B at its published widths on one TPU
through the engine's request-level API (``ServingEngine.start / submit /
run``), and check what comes out.

    python chip_smoke.py             # one chip: dispatch and runtime phases
    python chip_smoke.py --chips 4   # ep=4 expert-parallel serving vs ep=1

Weights are random from a fixed seed. Depth is cut so that one v5e
chip's 16 GiB holds the phase: 2 layers in bf16 on the dispatch plane,
1 layer (Mixtral's whole layer period) with int8 expert slot banks on
the serverless runtime plane (a bf16 runtime layer would need ~23 GB:
padded bank + two slot banks + the model's own experts). Widths are the
published ones: d_model 4096, 32 heads / 8 KV heads of 128, 8 experts
of d_ff 14336, top-2, vocab 32000.

Each phase serves 8 requests (prompts of 64-256 tokens, 4 of them
sharing a 64-token prefix, 32 greedy new tokens each) on a paged KV pool
with chunked prefill and the radix prefix cache, under the MoEless
controller as the session control plane. It runs the same traffic
twice: the first session compiles, the second is the steady run. It
checks that every request finishes, every token id is in the vocabulary,
every sampled-from logit is finite, the prefix cache hit, the runtime
moved expert weights, and that each Pallas kernel agrees with its jnp
oracle at the phase's shapes.

The last line of standard output is one JSON object,
``{"ok": true, "device": {...}}``. Without a TPU, or when any check
fails, the script exits non-zero and prints no such line.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import math
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

ARCH = "mixtral-8x7b"
SEED = 0
SLOTS = 8
N_REQUESTS = 8
PROMPT_LENS = (64, 256)          # inclusive range of prompt lengths
PREFIX = 64                      # shared-prefix length in tokens
SHARED = (0, 5, 6, 7)            # requests that start with the prefix
# wave 2 is submitted once wave 1 has finished and released its prompt
# blocks into the prefix cache, so its shared-prefix requests hit
WAVES = ((0, 1, 2, 3, 4), (5, 6, 7))
MAX_NEW = 32
MAX_LEN = 320                    # 256-token prompt + 32 new, in 16-blocks
KV_BLOCK = 16
PREFILL_CHUNK = 64
CONTROL_DEVICES = 4              # logical devices the controller plans
KERNEL_IMPL = "pallas"
# every kernel check compares max|kernel - oracle| against this share of
# max|oracle|: the kernels round their output to bf16 (2^-8 relative),
# the bf16 oracles round x@W to bf16 before the activation where the
# kernels keep f32, and both sum 4096-14336-long products in different
# orders. 2e-2 is about five bf16 ulps of the output's scale: wide
# enough for rounding, far below what a wrong tile, mask or index gives
# (errors of the order of the output itself).
KERNEL_RTOL = 2e-2
BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def log(msg: str) -> None:
    print(msg, flush=True)


def check(cond, msg: str) -> None:
    if not cond:
        raise SystemExit(f"chip_smoke: FAIL: {msg}")


class CompileMeter:
    """Sums the backend compile seconds JAX reports for this process."""

    def __init__(self):
        self.seconds = 0.0
        self.count = 0

    def __call__(self, event: str, duration: float, **_) -> None:
        if event == BACKEND_COMPILE_EVENT:
            self.seconds += duration
            self.count += 1

    def snapshot(self) -> tuple[float, int]:
        return self.seconds, self.count


def base_config():
    from repro.configs import get_config
    return get_config(ARCH)


def phase_config(phase: str, capacity_factor: float | None = None):
    """The phase's model and serving configuration."""
    from repro.configs import ServingSpec
    cfg = base_config().with_(serving=ServingSpec(
        kv="paged", kv_block=KV_BLOCK, prefill_chunk=PREFILL_CHUNK,
        prefix_cache=True))
    if phase == "dispatch":
        return cfg.with_(num_layers=2)
    moe = dataclasses.replace(cfg.moe, slot_dtype="int8")
    if capacity_factor is not None:
        moe = dataclasses.replace(moe, capacity_factor=capacity_factor)
    return cfg.with_(num_layers=1, moe=moe)


def make_prompts(vocab: int) -> list:
    import numpy as np
    rng = np.random.default_rng(SEED)
    lens = rng.integers(PROMPT_LENS[0], PROMPT_LENS[1] + 1,
                        size=N_REQUESTS)
    prefix = rng.integers(0, vocab, size=PREFIX, dtype=np.int32)
    prompts = []
    for i, n in enumerate(lens):
        p = rng.integers(0, vocab, size=int(n), dtype=np.int32)
        if i in SHARED:
            p[:PREFIX] = prefix
        prompts.append(p)
    return prompts


def serve_session(engine, cfg, prompts, telemetry):
    """One serving session through the request-level API. Returns
    (handles, result, wall seconds from first submit to the last token
    fetched to the host, session start-up seconds)."""
    from repro.core.control import MoElessController
    from repro.serving.scheduler import GenRequest
    ctl = MoElessController(cfg, num_devices=CONTROL_DEVICES,
                            telemetry=telemetry)
    t0 = time.perf_counter()
    engine.start(num_slots=SLOTS, control=ctl)
    setup_s = time.perf_counter() - t0
    handles = []
    t0 = time.perf_counter()
    for wave in WAVES:
        handles += [engine.submit(GenRequest(
            rid=i, arrival=math.nan, prompt=prompts[i],
            max_new_tokens=MAX_NEW)) for i in wave]
        res = engine.run()
    wall_s = time.perf_counter() - t0
    engine.close()
    return handles, res, wall_s, setup_s


def check_served(name, cfg, handles, res) -> list:
    vocab = cfg.vocab_size
    tokens = [list(h.tokens) for h in handles]
    check(all(h.status == "finished" for h in handles),
          f"{name}: not every request finished: "
          f"{[h.status for h in handles]}")
    check(all(len(t) == MAX_NEW for t in tokens),
          f"{name}: token counts {[len(t) for t in tokens]} != {MAX_NEW}")
    check(all(0 <= x < vocab for t in tokens for x in t),
          f"{name}: a token id is outside [0, {vocab})")
    check(res.nonfinite_logits == 0,
          f"{name}: {res.nonfinite_logits} non-finite logits")
    return tokens


def rel_err(out, ref) -> float:
    import numpy as np
    out = np.asarray(out, np.float32)
    ref = np.asarray(ref, np.float32)
    check(np.isfinite(out).all(), "kernel output is not finite")
    return float(np.abs(out - ref).max() / max(np.abs(ref).max(), 1e-30))


def kernel_checks(cfg, bank) -> dict:
    """Each Pallas kernel of the phase against its jnp oracle at the
    phase's shapes: the expert FFN over `bank` (the phase's live expert
    weights, (E or slots, D, F)) at decode and prefill-chunk capacity,
    and paged decode attention over a pool of the engine's geometry."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels import ops
    key = jax.random.PRNGKey(SEED + 1)
    errs = {}
    n = bank["w_up"].shape[0]
    d = cfg.d_model
    tokens = SLOTS * PREFILL_CHUNK
    for cap in (math.ceil(cfg.moe.capacity_factor * cfg.moe.top_k * t
                          / cfg.moe.num_experts)
                for t in (SLOTS, tokens)):
        key, kx, kg = jax.random.split(key, 3)
        x = jax.random.normal(kx, (n, cap, d), jnp.bfloat16)
        gs = jax.random.randint(kg, (n,), 0, cap + 1, jnp.int32)
        if "w_up_scale" in bank:
            args = (x, bank["w_gate"], bank["w_gate_scale"], bank["w_up"],
                    bank["w_up_scale"], bank["w_down"],
                    bank["w_down_scale"], gs)
            name, fn = "expert_ffn_quant", ops.expert_ffn_quant
        else:
            args = (x, bank["w_gate"], bank["w_up"], bank["w_down"], gs)
            name, fn = "expert_ffn", ops.expert_ffn
        with jax.default_matmul_precision("highest"):
            ref = fn(*args, impl="ref")
        errs[f"{name}[C={cap}]"] = rel_err(fn(*args, impl=KERNEL_IMPL),
                                           ref)

    h, kv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim
    nbs = -(-MAX_LEN // KV_BLOCK)
    nb = 1 + SLOTS * nbs
    ks = jax.random.split(key, 4)
    q = jax.random.normal(ks[0], (SLOTS, h, hd), jnp.bfloat16)
    k = jax.random.normal(ks[1], (nb, KV_BLOCK, kv, hd), jnp.bfloat16)
    v = jax.random.normal(ks[2], (nb, KV_BLOCK, kv, hd), jnp.bfloat16)
    rng = np.random.default_rng(SEED)
    tables = rng.permutation(np.arange(1, nb)).reshape(SLOTS, nbs)
    pos = np.zeros((nb, KV_BLOCK), np.int32)
    for r in range(SLOTS):
        pos[tables[r]] = np.arange(nbs * KV_BLOCK).reshape(nbs, KV_BLOCK)
    kv_len = jnp.asarray(rng.integers(1, nbs * KV_BLOCK + 1, SLOTS),
                         jnp.int32)
    args = (q, k, v, jnp.asarray(pos), jnp.asarray(tables, jnp.int32),
            kv_len, kv_len - 1)
    with jax.default_matmul_precision("highest"):
        ref = ops.decode_attention_paged(*args, impl="ref")
    errs["decode_attention_paged"] = rel_err(
        ops.decode_attention_paged(*args, impl=KERNEL_IMPL), ref)
    for name, err in errs.items():
        log(f"  kernel {name}: max|pallas-ref|/max|ref| = {err!r} "
            f"(tolerance {KERNEL_RTOL})")
        check(err <= KERNEL_RTOL, f"kernel {name} disagrees with its "
              f"oracle: {err} > {KERNEL_RTOL}")
    return errs


def lowered_step_text(cfg, params, runtime=None) -> str:
    """StableHLO of the engine's single-token paged decode step at the
    session's shapes."""
    from functools import partial

    import jax
    import jax.numpy as jnp

    from repro.models import transformer as T
    nbs = -(-MAX_LEN // KV_BLOCK)
    cache = jax.eval_shape(partial(T.init_paged_cache, cfg, None,
                                   1 + SLOTS * nbs, KV_BLOCK))
    batch = {"tokens": jax.ShapeDtypeStruct((SLOTS, 1), jnp.int32),
             "active": jax.ShapeDtypeStruct((SLOTS,), jnp.bool_),
             "block_tables": jax.ShapeDtypeStruct((SLOTS, nbs), jnp.int32),
             "new_counts": jax.ShapeDtypeStruct((SLOTS,), jnp.int32)}
    lengths = jax.ShapeDtypeStruct((SLOTS,), jnp.int32)
    if runtime is None:
        step = partial(T.decode_step, cfg, window=0, collect=False)
        return jax.jit(step).lower(params, batch, cache,
                                   lengths).as_text()
    step = partial(T.decode_step, cfg, window=0, collect=False,
                   ep_ctx=runtime.ctx)
    return jax.jit(step).lower(params, batch, cache, lengths,
                               runtime.ep_state()).as_text()


def memory(device) -> dict:
    stats = device.memory_stats() or {}
    return {"peak_bytes_in_use": stats.get("peak_bytes_in_use"),
            "bytes_in_use": stats.get("bytes_in_use")}


def run_phase(phase: str, meter: CompileMeter) -> dict:
    """Serve the phase's traffic twice (cold, then steady) and check it."""
    import jax
    import numpy as np

    from repro.models import model as M
    from repro.obs import Telemetry
    from repro.serving.engine import ServingEngine

    cfg = phase_config(phase)
    runtime = "on" if phase == "runtime" else "off"
    log(f"phase {phase}: {cfg.name} layers={cfg.num_layers} "
        f"d_model={cfg.d_model} experts={cfg.moe.num_experts} "
        f"d_ff={cfg.moe.d_ff} dtype={cfg.dtype} "
        f"slot_dtype={cfg.moe.slot_dtype} expert_runtime={runtime} "
        f"capacity_factor={cfg.moe.capacity_factor}")
    params = M.init_params(cfg, jax.random.PRNGKey(SEED))
    tel = Telemetry()
    engine = ServingEngine(cfg, params, max_len=MAX_LEN,
                           expert_runtime=runtime, telemetry=tel)
    prompts = make_prompts(cfg.vocab_size)

    c0 = meter.snapshot()
    handles, res, cold_s, _ = serve_session(
        engine, cfg, prompts, tel)
    c1 = meter.snapshot()
    cold = check_served(f"{phase}/cold", cfg, handles, res)
    hits0 = tel.registry.as_dict().get("kv_prefix_hits_total", 0.0)
    rt = res.runtime
    if phase == "runtime":
        check(rt.stats.transfers > 0, "runtime moved no expert weights")
        transfers = rt.stats.transfers
        text = lowered_step_text(cfg, engine.params, rt)
        # the kernel check takes up to 4 occupied slots of the live
        # layer-0 bank: per-slot tiles are the phase's, and the oracle's
        # f32 copy of all 20 slots (14 GB) would not fit beside the model
        occupied = np.flatnonzero(
            rt.slot_expert[0] < cfg.moe.num_experts)[:4]
        bank = {k: w[0, occupied]
                for k, w in rt.banks[rt.moe_positions[0]].items()}
    else:
        transfers = 0
        text = lowered_step_text(cfg, engine.params)
        bank = {k: w[0] for k, w in
                params["layers"][0]["moe"]["experts"].items()}
    check("tpu_custom_call" in text,
          f"{phase}: the lowered decode step holds no Pallas kernel")
    del res, rt, handles
    gc.collect()
    errs = kernel_checks(cfg, bank)
    del bank
    gc.collect()

    c2 = meter.snapshot()
    handles, res, steady_s, steady_setup_s = serve_session(
        engine, cfg, prompts, tel)
    c3 = meter.snapshot()
    steady = check_served(f"{phase}/steady", cfg, handles, res)
    check(steady == cold, f"{phase}: the steady session's tokens differ "
          "from the cold session's")
    hits = tel.registry.as_dict().get("kv_prefix_hits_total", 0.0) - hits0
    check(hits >= 1, f"{phase}: no prefix-cache hit")
    generated = res.generated_tokens
    out = {
        "phase": phase,
        "device_kind": jax.devices()[0].device_kind,
        "compile_s_cold_session": c1[0] - c0[0],
        "compiles_cold_session": c1[1] - c0[1],
        "compiles_steady_session": c3[1] - c2[1],
        "cold_session_wall_s": cold_s,
        "steady_wall_s": steady_s,
        "session_setup_s": steady_setup_s,
        "iterations": res.iterations,
        "generated_tokens": generated,
        "kv_prefix_hits": hits,
        "runtime_transfers": transfers,
        "kernel_rel_err": errs,
        **memory(jax.devices()[0]),
    }
    log(json.dumps(out))
    del engine, params, handles, res
    gc.collect()
    return out


def serve_on_mesh(cfg, mesh) -> tuple:
    """One session of the phase traffic on `mesh`; returns (tokens,
    per-rank bytes moved)."""
    import jax

    from repro.models import model as M
    from repro.obs import Telemetry
    from repro.serving.engine import ServingEngine
    params = M.init_params(cfg, jax.random.PRNGKey(SEED))
    tel = Telemetry()
    engine = ServingEngine(cfg, params, max_len=MAX_LEN,
                           expert_runtime="on", mesh=mesh, telemetry=tel)
    del params
    handles, res, wall_s, _ = serve_session(
        engine, cfg, make_prompts(cfg.vocab_size), tel)
    tokens = check_served(f"ep={mesh.shape['ep']}", cfg, handles, res)
    rank_bytes = dict(res.runtime.stats.rank_bytes)
    log(f"ep={mesh.shape['ep']}: session wall {wall_s!r} s (includes "
        f"compilation), {res.iterations} iterations, "
        f"dropped={res.dropped_tokens}, rank_bytes={rank_bytes}")
    del engine, handles, res
    gc.collect()
    return tokens, rank_bytes


def run_four_chips() -> None:
    """The runtime phase at drop-free capacity on a 4-rank EP mesh,
    against the same traffic on a 1-rank mesh: greedy tokens must be
    equal, and the slot banks must spread over all four chips."""
    import jax

    from repro.launch.mesh import make_serving_mesh
    cfg = phase_config("runtime", capacity_factor=8.0)
    log(f"four-chip EP: {cfg.name} layers={cfg.num_layers} "
        f"slot_dtype={cfg.moe.slot_dtype} capacity_factor="
        f"{cfg.moe.capacity_factor}")
    toks4, rank_bytes = serve_on_mesh(cfg, make_serving_mesh(4, ep=4))
    for d in jax.devices()[:4]:
        log(f"  after ep=4: device {d.id} {memory(d)}")
    check(all(b > 0 for b in rank_bytes.values()) and len(rank_bytes) == 4,
          f"slot bytes did not reach all 4 ranks: {rank_bytes}")
    toks1, _ = serve_on_mesh(cfg, make_serving_mesh(1, ep=1))
    check(toks4 == toks1, "greedy tokens differ between ep=4 and ep=1")
    log(f"ep=4 tokens == ep=1 tokens for all {len(toks1)} requests")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the ep=4 vs ep=1 serving path")
    args = ap.parse_args(argv)

    import jax
    from repro.kernels.ops import resolve_impl
    from repro.launch.compile_cache import use_compile_cache

    cache_dir = use_compile_cache()
    meter = CompileMeter()
    jax.monitoring.register_event_duration_secs_listener(meter)
    devices = jax.devices()
    dev = devices[0]
    check(dev.platform == "tpu", f"no TPU: JAX found {dev.platform!r}")
    check(len(devices) >= args.chips,
          f"{args.chips} chips asked for, {len(devices)} present")
    check(resolve_impl("auto") == "pallas",
          "impl='auto' does not resolve to the Pallas kernels")
    log(f"device: {dev.platform} {dev.device_kind} x{len(devices)}; "
        f"compile cache {cache_dir} "
        f"({'warm' if any(Path(cache_dir).glob('*')) else 'cold'})")
    if args.chips == 4:
        run_four_chips()
    else:
        for phase in ("dispatch", "runtime"):
            run_phase(phase, meter)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
