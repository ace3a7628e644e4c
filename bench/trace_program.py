"""Where a cell's step time goes, read from the program's own spans and
device programs (`bench/program_trace.py`), on the chip.

    python3 bench/trace_program.py run --workload <cell> --seed <n> --seconds <s> --trace <0|1> --out <dir>
    python3 bench/trace_program.py small --out <dir>

`run` makes one run of the cell exactly as `bench/run.py` does and
prints its result line, then one more JSON line: the host time of the
window's steps by phase and, with `--trace 1`, the breakdown with each
idle gap named by its innermost span and the device time of each device
program, and each step split into parts (`split_steps`) averaged per
phase and, for mixed steps, per mode. Both lines are also written under
`<dir>`.

`small` records a few serving steps of the test configuration
(`bench/tests/data/tiny.json`) with the expert runtime and the Pallas
kernels, for `bench/tests/data/engine_spans.xplane.pb`.
"""
import argparse
import glob
import json
import shutil
import sys
import time
from pathlib import Path

import numpy as np

SLOW_MIXED_S = 0.229     # between the two modes of mixed steps (223/235)
PARTS = ("engine.admit", "engine.prepare", "engine.dispatch",
         "engine.fetch", "control.step", "control.loads", "control.plan",
         "runtime.apply", "runtime.flush", "engine.commit")


def _window_steps(server, window) -> dict:
    t0, t_end = window
    out = {}
    for phase in ("mixed", "decode"):
        d = np.asarray([s.t1 - s.t0 for s in server.steps
                        if s.phase == phase and t0 <= s.t0 <= t_end])
        if len(d):
            out[phase] = {"n": int(len(d)), "mean_ms": 1e3 * d.mean(),
                          "median_ms": 1e3 * float(np.median(d)),
                          "slow_share": float(np.mean(d >= SLOW_MIXED_S))}
    return out


def split_steps(pt) -> dict:
    """Each step of the window split into parts that sum to its host
    duration: device time of each device program inside the step's
    span (`jit_engine_step`, `jit_bank_update`, the rest) and the
    device-idle time there; beside them the host sections' durations,
    the device time of the step's own `jit_engine_step` execution, and
    the slot-bank flush's `transfers` and `bucket` in this step and in
    the one before. Returns, per phase and mode (mixed steps at or over
    `SLOW_MIXED_S` are slow), the count and the mean of each part."""
    dev = min(pt.modules) if pt.modules else None
    launched = {s: (ms, me) for s, _, _, ms, me in
                pt.dispatched("jit_engine_step", "engine.dispatch", dev)} \
        if dev is not None else {}
    progs = {m: pt.module_events(m, dev) for m in ("jit_engine_step",
                                                   "jit_bank_update")} \
        if dev is not None else {}
    rows, prev = {}, {"transfers": 0, "bucket": 0}
    for s, e, _ in pt.sections("engine.step"):
        inside = [(n, ss, ee, a) for n, ss, ee, a in pt.program
                  if s <= ss and ee <= e]
        disp = [(ss, a) for n, ss, _, a in inside if n == "engine.dispatch"]
        if not disp:
            continue
        phase = disp[0][1].get("phase")
        mode = "slow" if phase == "mixed" and (e - s) * 1e-9 >= SLOW_MIXED_S \
            else "fast"
        ms, me = launched.get(disp[0][0], (np.nan, np.nan))
        idle = 1e3 * pt.idle_inside([(s, e)])
        busy = {m: float(np.sum(np.diff(np.clip(iv, s, e), axis=1))) * 1e-6
                for m, iv in progs.items()}
        flush = [a for n, _, _, a in inside if n == "runtime.flush"]
        now = {"transfers": sum(int(a.get("transfers", 0)) for a in flush),
               "bucket": sum(int(b) for a in flush
                             for b in str(a.get("bucket", 0)).split(","))}
        rec = {"step_ms": (e - s) * 1e-6, "idle_ms": idle,
               **{f"{m}_in_step_ms": v for m, v in busy.items()},
               "other_device_in_step_ms": (e - s) * 1e-6 - idle
               - sum(busy.values()),
               "own_step_device_ms": (me - ms) * 1e-6,
               "transfers": now["transfers"], "bucket": now["bucket"],
               "prev_transfers": prev["transfers"],
               "prev_bucket": prev["bucket"]}
        for part in PARTS:
            rec[part] = sum(ee - ss for n, ss, ee, _ in inside
                            if n == part) * 1e-6
        rows.setdefault(f"{phase}.{mode}", []).append(rec)
        prev = now
    return {k: dict({"n": len(v)}, **{p: float(np.nanmean([r[p] for r in v]))
                                      for p in v[0]})
            for k, v in rows.items()}


def run(a) -> int:
    import bench.harness as H
    import bench.trace_reduce as TR
    from bench.program_trace import ProgramTrace
    out = Path(a.out)
    out.mkdir(parents=True, exist_ok=True)
    keep = {}
    serve_open, load = H.serve_open, TR.Trace.load

    def kept_serve_open(server, sched, seconds, on_close=None):
        window = serve_open(server, sched, seconds, on_close)
        if on_close is not None:
            keep["server"], keep["window"] = server, window
        return window

    def kept_load(trace_dir, num_devices):
        from jax.profiler import ProfileData
        f = sorted(glob.glob(str(Path(trace_dir) / "**" / "*.xplane.pb"),
                             recursive=True))[-1]
        keep["profile"] = ProfileData.from_file(f)
        return load(trace_dir, num_devices)

    H.serve_open = kept_serve_open
    TR.Trace.load = kept_load
    res = H.run_cell(a.workload, a.seed, a.seconds, bool(a.trace),
                     time.perf_counter())
    print(json.dumps(res), flush=True)
    extra = {"seed": a.seed, "trace": a.trace,
             "steps": _window_steps(keep["server"], keep["window"])}
    if a.trace:
        pd = keep["profile"]
        pt = ProgramTrace.over(TR.Trace.from_xspace(pd, 1), pd, 1)
        extra["breakdown"] = pt.breakdown(top=15)
        extra["split"] = split_steps(pt)
    print(json.dumps(extra), flush=True)
    (out / f"seed{a.seed}.trace{a.trace}.json").write_text(
        json.dumps({"result": res, **extra}))
    return 0


def strip(src: Path, dst: Path) -> None:
    """Copy the profile `src` to `dst` with only what the reduction
    reads: the device planes' `XLA Ops` and `XLA Modules` lines (names
    and times; their per-op cost stats go) and the host events of the
    harness's and the program's spans, with their args. The metadata
    plane (the programs' HLO) and the runtime's own host events go."""
    from tensorflow.tsl.profiler.protobuf import xplane_pb2

    from bench.program_trace import MODULES_LINE, PROGRAM_PREFIXES
    from bench.trace_reduce import DEVICE_PREFIX, OPS_LINE, SPAN_PREFIX
    xs = xplane_pb2.XSpace()
    xs.ParseFromString(Path(src).read_bytes())
    planes = []
    for p in xs.planes:
        device = p.name.startswith(DEVICE_PREFIX)
        if not (device or p.name.startswith("/host:CPU")):
            continue
        lines = []
        for line in p.lines:
            if device:
                keep = line.name in (OPS_LINE, MODULES_LINE)
                events = list(line.events) if keep else []
                for e in events:
                    del e.stats[:]
            else:
                events = [e for e in line.events
                          if p.event_metadata[e.metadata_id].name
                          .startswith((SPAN_PREFIX,) + PROGRAM_PREFIXES)]
            if events:
                kept = xplane_pb2.XLine()
                kept.CopyFrom(line)
                del kept.events[:]
                kept.events.extend(events)
                lines.append(kept)
        used = {e.metadata_id for line in lines for e in line.events}
        plane = xplane_pb2.XPlane()
        plane.CopyFrom(p)
        del plane.lines[:]
        plane.lines.extend(lines)
        for k in [k for k in plane.event_metadata if k not in used]:
            del plane.event_metadata[k]
        if device:
            for m in plane.event_metadata.values():
                del m.stats[:]
        planes.append(plane)
    del xs.planes[:]
    xs.planes.extend(planes)
    Path(dst).write_bytes(xs.SerializeToString())


def small(a) -> int:
    """A few steps of the test configuration, traced as the harness
    traces a window."""
    import jax

    from bench.harness import program_config, rewrite_all_slots, setup_jax
    from bench.weights import make_weights
    from repro.core.control import MoElessController
    from repro.obs import Telemetry
    from repro.serving.engine import ServingEngine
    from repro.serving.scheduler import GenRequest
    setup_jax(True, 1)
    root = Path(__file__).resolve().parent
    c = json.loads((root / "tests" / "data" / "tiny.json").read_text())
    cfg = program_config(c)
    params = make_weights(c, 1)
    prompts = np.random.default_rng(1).integers(0, 512, (2, 32),
                                                dtype=np.int32)
    out = Path(a.out)
    out.mkdir(parents=True, exist_ok=True)

    tel = Telemetry()
    eng = ServingEngine(cfg, params, max_len=64, expert_runtime="on",
                        telemetry=tel)

    def session(trace_dir=None, level=2):
        ctl = MoElessController(cfg, num_devices=4, telemetry=tel)
        eng.start(num_slots=2, control=ctl)
        for rid, prompt in enumerate(prompts):
            eng.submit(GenRequest(rid=rid, arrival=float("nan"),
                                  prompt=prompt, max_new_tokens=3))
        rewrite_all_slots(eng)
        jax.effects_barrier()
        if trace_dir is not None:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = level
            jax.profiler.start_trace(str(trace_dir),
                                     profiler_options=opts)
        with jax.profiler.TraceAnnotation("bench.step"):
            rewrite_all_slots(eng)
        while eng.has_work:
            with jax.profiler.TraceAnnotation("bench.step"):
                eng.step()
        if trace_dir is not None:
            jax.profiler.stop_trace()
        eng.close()

    session()                                # compiles every program
    for level in (1, 2):
        d = out / f"small_level{level}"
        shutil.rmtree(d, ignore_errors=True)
        session(d, level)
        f = sorted(glob.glob(str(d / "**" / "*.xplane.pb"),
                             recursive=True))[-1]
        dst = out / f"engine_spans_level{level}.xplane.pb"
        strip(f, dst)
        print(json.dumps({"file": str(dst),
                          "bytes": dst.stat().st_size}), flush=True)
    return 0


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    sub = ap.add_subparsers(dest="cmd", required=True)
    r = sub.add_parser("run")
    r.add_argument("--workload", required=True)
    r.add_argument("--seed", type=int, required=True)
    r.add_argument("--seconds", type=float, required=True)
    r.add_argument("--trace", type=int, choices=(0, 1), default=1)
    r.add_argument("--out", required=True)
    s = sub.add_parser("small")
    s.add_argument("--out", required=True)
    a = ap.parse_args()
    return run(a) if a.cmd == "run" else small(a)


if __name__ == "__main__":
    root = Path(__file__).resolve().parents[1]
    sys.path[:0] = [str(root), str(root / "src")]
    sys.exit(main())
