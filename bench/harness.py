"""One run of one benchmark cell: set up, serve a measured window from the
client's side, check what was served against the plain reference, and
print one result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell (`BENCHMARK.json` `workloads`) names a configuration file
(`bench/configs/<config>.json`) and a traffic mix
(`bench/traffic/<mix>.json`). Per-layer metrics are readers found by
name (`bench/metrics/<metric>.py`, each with `read(run)`), so a new
cell, configuration, mix or metric is a new file.

Entry under test: the request-level serving API
(`ServingEngine.start/submit/step`) with the expert runtime on and the
MoEless controller as the session's control plane. One thread runs the
load generator and the step loop: before each `engine.step()` every
request that has fallen due is submitted; each token is timestamped
when `step()` returns, since a step ends with the token fetch to the
host. Every time is the host's clock (`time.perf_counter`); the
engine's own serving clock is modelled and never read.
"""
from __future__ import annotations

import gc
import importlib.util
import json
import math
import os
import shutil
import sys
import time
import warnings
from dataclasses import dataclass, field
from pathlib import Path

import numpy as np

# plan spills are the runtime's to report; one per step floods the log
warnings.filterwarnings("ignore", message=".*replica overflowed",
                        category=RuntimeWarning)

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
CACHE_DIR = BENCH / ".jax_cache"
TRACE_DIR = BENCH / ".traces"
DRAIN_S = 60.0         # in-flight requests may finish this long after
CHECK_TOKENS = 256     # the correctness sample holds at least this many
CHECK_MAX_REQUESTS = 16
BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
CACHE_HIT = "/jax/compilation_cache/cache_hits"


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


class NoChip(SystemExit):
    pass


# ----------------------------------------------------------- the cell


def load_cell(workload: str, root: Path = ROOT) -> tuple:
    """(benchmark, cell, configuration file dict, mix dict)."""
    bm = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bm["workloads"]}
    if workload not in cells:
        raise SystemExit(f"unknown workload {workload!r}; known: "
                         f"{sorted(cells)}")
    cell = cells[workload]
    conf = {c["name"]: c for c in bm["configs"]}[cell["config"]]
    c = json.loads((root / conf["file"]).read_text())
    from bench.traffic import load_mix
    return bm, cell, c, load_mix(root / "bench", cell["traffic"])


def metrics_for(bm: dict, cell: str, trace: bool) -> list:
    """The metric entries this cell reports in this kind of run."""
    if not trace:
        return [m for m in bm["end_to_end"]
                if cell in m.get("workloads", [cell])]
    return [m for m in bm["per_layer"]
            if cell in m.get("workloads", [cell])]


def load_reader(name: str, bench_dir: Path = BENCH):
    path = bench_dir / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        f"bench_metric_{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def program_config(c: dict):
    """The program's ModelConfig for a configuration file (its layout
    checked by `bench.weights.dims`)."""
    from bench.weights import dims
    dims(c)
    from repro.configs.base import ModelConfig, MoESpec, ServingSpec
    s = c["serving"]
    return ModelConfig(
        name=c["model_type"], family="moe",
        num_layers=c["num_hidden_layers"], d_model=c["hidden_size"],
        num_heads=c["num_attention_heads"],
        num_kv_heads=c["num_key_value_heads"],
        d_ff=c["intermediate_size"], vocab_size=c["vocab_size"],
        head_dim=c["hidden_size"] // c["num_attention_heads"],
        moe=MoESpec(num_experts=c["num_local_experts"],
                    top_k=c["num_experts_per_tok"],
                    d_ff=c["intermediate_size"],
                    capacity_factor=s["capacity_factor"],
                    slot_dtype=s["slot_dtype"]),
        serving=ServingSpec(kv="paged", kv_block=s["kv_block"],
                            prefill_chunk=s["prefill_chunk"],
                            prefix_cache=s["prefix_cache"]),
        rope="rope", rope_theta=float(c["rope_theta"]),
        norm=c["norm"], qkv_bias=False, act="swiglu",
        tie_embeddings=False, dtype="bfloat16")


# ------------------------------------------------------------ records


@dataclass
class StepRec:
    t0: float
    t1: float
    phase: str                       # mixed | decode
    prefill: list = field(default_factory=list)   # (start, n, last)
    decode_ctx: list = field(default_factory=list)  # keys attended
    control_s: float = 0.0
    loads: object = None             # (moe layers, E) routed assignments


@dataclass
class ReqRec:
    rid: int
    due: float                       # host time it fell due
    prompt: np.ndarray
    max_new: int
    handle: object = None
    sent: float = math.nan
    admitted: float = math.nan       # start of the step that admitted it
    times: list = field(default_factory=list)   # host time of each token
    done_prompt: int = -1            # prompt rows processed (-1: queued)
    finished: bool = False
    tokens: list = field(default_factory=list)  # served ids, at the close

    @property
    def plen(self) -> int:
        return len(self.prompt)


class ControlProxy:
    """The session's control plane, timed: each `step` runs under a
    `bench.control` trace span, and its host seconds and the routed
    per-expert loads it was given are kept for the step records."""

    def __init__(self, inner):
        self._inner = inner
        self.calls = []

    def __getattr__(self, name):
        return getattr(self._inner, name)

    def step(self, t, gate_inputs, actual_loads, *a, **kw):
        import jax
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench.control"):
            out = self._inner.step(t, gate_inputs, actual_loads, *a, **kw)
        dt = time.perf_counter() - t0
        self.calls.append((dt, np.asarray(actual_loads)))
        return out


class CompileMeter:
    """Backend compiles and persistent-cache hits of this process."""

    def __init__(self):
        self.compiles = 0
        self.compile_s = 0.0
        self.cache_hits = 0

    def duration(self, event: str, duration: float, **_) -> None:
        if event == BACKEND_COMPILE:
            self.compiles += 1
            self.compile_s += duration

    def event(self, event: str, **_) -> None:
        if event == CACHE_HIT:
            self.cache_hits += 1

    def snapshot(self) -> tuple:
        return self.compiles, self.compile_s, self.cache_hits


# --------------------------------------------------------- the server


class Server:
    """The engine session under test plus the harness's bookkeeping."""

    def __init__(self, engine, tel, ctl, chunk: int):
        self.engine = engine
        self.tel = tel
        self.ctl = ctl
        self.chunk = chunk
        self.steps: list[StepRec] = []
        self.reqs: dict[int, ReqRec] = {}
        self.inflight: dict[int, ReqRec] = {}
        self._counts = self._phase_counts()

    def _phase_counts(self) -> dict:
        d = self.tel.registry.as_dict()
        return {p: d.get(f'engine_steps_total{{phase="{p}"}}', 0.0)
                for p in ("mixed", "decode")}

    def submit(self, r: ReqRec) -> None:
        import jax
        from repro.serving.scheduler import GenRequest
        with jax.profiler.TraceAnnotation("bench.submit"):
            r.sent = time.perf_counter()
            r.handle = self.engine.submit(GenRequest(
                rid=r.rid, arrival=math.nan, prompt=r.prompt,
                max_new_tokens=r.max_new))
        if r.handle.status == "rejected":
            raise RuntimeError(f"request {r.rid} rejected at admission")
        self.reqs[r.rid] = r
        self.inflight[r.rid] = r

    @property
    def busy(self) -> bool:
        return self.engine.has_work

    def step(self) -> StepRec:
        import jax
        ncalls = len(self.ctl.calls)
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench.step"):
            events = self.engine.step()
        t1 = time.perf_counter()
        counts = self._phase_counts()
        phase = "mixed" if counts["mixed"] > self._counts["mixed"] \
            else "decode"
        self._counts = counts
        rec = StepRec(t0, t1, phase)
        calls = self.ctl.calls[ncalls:]
        rec.control_s = sum(c[0] for c in calls)
        if calls:
            rec.loads = sum(c[1] for c in calls)
        # rows this step processed, mirrored from the request states
        for r in list(self.inflight.values()):
            st = r.handle.status
            if st == "queued":
                continue
            if r.done_prompt < 0:       # admitted by this step
                r.admitted = t0
                r.done_prompt = int(r.handle.req.prefix_hit_len)
            if r.done_prompt < r.plen:
                n = min(self.chunk, r.plen - r.done_prompt)
                rec.prefill.append((r.done_prompt, n,
                                    r.done_prompt + n == r.plen))
                r.done_prompt += n
            else:
                rec.decode_ctx.append(r.plen + len(r.times))
        for ev in events:
            r = self.reqs[ev.rid]
            r.times.append(t1)
            if ev.done:
                r.finished = True
                self.inflight.pop(ev.rid, None)
        self.steps.append(rec)
        return rec

    def drain(self, deadline: float) -> None:
        while self.busy and time.perf_counter() < deadline:
            self.step()


# ------------------------------------------------------------- phases


def rewrite_all_slots(engine) -> int:
    """Apply to the expert runtime one plan that puts every expert's
    replicas on devices other than the ones it holds, as many as the
    slots take: the largest slot rewrite the runtime makes, whose bank
    update program a window otherwise meets only when its plan swings
    far. The plans that follow rewrite the slots again from the
    program's own control plane. Returns the slots written."""
    from repro.core.control import MOELESS_EXEC_TIME, PlanEvent
    from repro.core.plan import LayerPlan
    res = engine.result()
    rt = res.runtime
    if rt is None:
        return 0
    e, g = rt.num_experts, rt.num_devices
    r = max(1, min(rt.total_slots // e, g - 1))
    plan = LayerPlan(e, g, replicas=[r] * e,
                     placement=[[(x + 1 + i) % g for i in range(r)]
                                for x in range(e)])
    ev = PlanEvent(plan=plan, served=plan, lead_time=math.inf,
                   exec_time=MOELESS_EXEC_TIME, serverless=True)
    return rt.apply(res.clock_s, [ev] * rt.n_layers,
                    phase="bootstrap").transfers


def warm_up(server: Server, burst: list, stretch: list, mix: dict,
            seconds: float, meter: "CompileMeter") -> None:
    """Rewrite every slot once (`rewrite_all_slots`), serve `burst` (all
    due at once) to the end, then `seconds` of the cell's own loop over
    `stretch` (another seed's requests of the same mix, at its rate or
    clients) and its drain: every step shape and bank-update program
    the window uses compiles here."""
    c0 = meter.snapshot()
    written = rewrite_all_slots(server.engine)
    now = time.perf_counter()
    for r in burst:
        server.submit(ReqRec(r.rid, now, r.prompt, r.max_new))
    server.drain(math.inf)
    c1 = meter.snapshot()
    if seconds > 0:
        if mix["loop"] == "open":
            serve_open(server, stretch, seconds)
        else:
            serve_closed(server, stretch, mix["clients"], seconds)
    c2 = meter.snapshot()
    log(f"warm-up: {written} slots rewritten and a burst of "
        f"{len(burst)} requests {c1[0] - c0[0]} "
        f"backend compiles, {seconds:g} s of the cell's loop "
        f"{c2[0] - c1[0]} more")


def serve_open(server: Server, sched: list, seconds: float,
               on_close=None) -> tuple:
    """Open loop: each request is sent when it falls due. Returns (window
    start, window end). `on_close` runs when the window closes and
    returns the seconds it took (writing a trace), which the drain's
    deadline does not count."""
    import jax
    t0 = time.perf_counter()
    t_end = t0 + seconds
    recs = [ReqRec(r.rid, t0 + r.due, r.prompt, r.max_new) for r in sched]
    i = 0
    closed = False
    deadline = t_end + DRAIN_S
    while True:
        now = time.perf_counter()
        while i < len(recs) and recs[i].due <= now:
            server.submit(recs[i])
            i += 1
        if not closed and now >= t_end:
            closed = True
            if on_close:
                deadline += on_close()
        if i >= len(recs) and not server.busy:
            break
        if now > deadline:
            break
        if not server.busy:
            with jax.profiler.TraceAnnotation("bench.wait"):
                time.sleep(max(0.0, recs[i].due - time.perf_counter()))
            continue
        server.step()
    if not closed and on_close:
        on_close()
    return t0, t_end


def serve_closed(server: Server, pool: list, clients: int,
                 seconds: float, on_close=None) -> tuple:
    """Closed loop: `clients` clients, each sending its next request from
    the pool as soon as its last one finishes, until the window closes."""
    t0 = time.perf_counter()
    t_end = t0 + seconds
    nxt = 0

    def send():
        nonlocal nxt
        r = pool[nxt % len(pool)]
        now = time.perf_counter()
        server.submit(ReqRec(r.rid + len(pool) * (nxt // len(pool)), now,
                             r.prompt, r.max_new))
        nxt += 1

    for _ in range(clients):
        send()
    closed = False
    deadline = t_end + DRAIN_S
    while True:
        now = time.perf_counter()
        if not closed and now >= t_end:
            closed = True
            if on_close:
                deadline += on_close()
        if closed and (not server.busy or now > deadline):
            break
        server.step()
        if not closed:
            for _ in range(clients - len(server.inflight)):
                send()
    return t0, t_end


# ------------------------------------------------------------ metrics


def p95(values) -> float:
    return float(np.percentile(np.asarray(values, np.float64), 95))


def end_to_end(reqs: list, t0: float, t_end: float, peak: int,
               setup_s: float) -> dict:
    ttft = [r.times[0] - r.due for r in reqs if r.times]
    gaps = [b - a for r in reqs for a, b in zip(r.times, r.times[1:])]
    toks = sum(1 for r in reqs for t in r.times if t0 <= t <= t_end)
    return {
        "ttft_p95_s": (p95(ttft), "s"),
        "itl_p95_ms": (1e3 * p95(gaps), "ms"),
        "output_tokens_per_s": (toks / (t_end - t0), "tokens/s"),
        "peak_hbm_gb": (peak / 1e9, "GB"),
        "setup_s": (setup_s, "s"),
    }


def device_peak(devices) -> int:
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devices)


# -------------------------------------------------------- correctness


def check_sample(reqs: list, seed: int) -> list:
    """Finished requests to compare: the longest (prompt + output), then
    others drawn from the seed until the sample holds CHECK_TOKENS served
    tokens or CHECK_MAX_REQUESTS requests."""
    done = [r for r in reqs if r.finished]
    if not done:
        return []
    done.sort(key=lambda r: r.rid)
    longest = max(done, key=lambda r: (r.plen + len(r.times), r.rid))
    rest = [r for r in done if r is not longest]
    order = np.random.default_rng(int(seed)).permutation(len(rest))
    out, served = [longest], len(longest.times)
    for i in order:
        if served >= CHECK_TOKENS or len(out) >= CHECK_MAX_REQUESTS:
            break
        out.append(rest[i])
        served += len(rest[i].times)
    return out


def compare(c: dict, seed: int, sample: list,
            lower_pick: bool = False) -> np.ndarray:
    """Gap, under the float32 reference, between the best logit and the
    logit of each served token of the sample, all tokens in one array.
    With `lower_pick`, of the token the lower-precision control puts
    first at each of those positions instead (the control's reading)."""
    from bench.reference import Reference, served_gaps
    ref = Reference(c, seed)
    return np.concatenate([served_gaps(ref, r.prompt, r.tokens,
                                       lower_pick=lower_pick)
                           for r in sample])


def gap_numbers(gaps: np.ndarray) -> dict:
    """The numbers a configuration's `check` may hold a limit for: the
    widest gap, and the mean gap over the served tokens."""
    if gaps.size == 0:
        return {"max_logit_gap": math.inf, "mean_logit_gap": math.inf}
    return {"max_logit_gap": float(gaps.max()),
            "mean_logit_gap": float(gaps.mean())}


# ---------------------------------------------------------------- run


@dataclass
class Run:
    """What a per-layer reader sees of one traced run."""
    cell: dict
    config: dict
    steps: list                      # StepRec inside the traced window
    reqs: list                       # ReqRec due in the window
    window: tuple                    # (t0, t_end) on the host clock
    registry: tuple                  # obs snapshots at open and close
    trace: object                    # bench.trace_reduce.Trace or None
    peaks: dict


def setup_jax(require_chip: bool, chips: int):
    import jax
    devices = jax.devices()
    if require_chip:
        if devices[0].platform != "tpu":
            raise NoChip(f"no TPU: JAX found {devices[0].platform!r}")
        if len(devices) < chips:
            raise NoChip(f"{chips} chips asked for, {len(devices)} found")
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return devices[:chips]


def peaks_of(kind: str) -> dict:
    table = json.loads((BENCH / "peaks.json").read_text())["devices"]
    if kind not in table:
        raise SystemExit(f"no peaks for device kind {kind!r} in "
                         "bench/peaks.json")
    return table[kind]


def run_cell(workload: str, seed: int, seconds: float, trace: bool,
             t_proc0: float, require_chip: bool = True,
             root: Path = ROOT, control: bool = False,
             rate: float | None = None) -> dict:
    """One run of `workload`. `control` also judges the lower-precision
    control on the run's check sample, by the same checks and limits
    (`control_correct`; bench/control.py; benchmark runs never do).
    `rate` overrides an open-loop mix's rate (bench/sweep.py).
    `require_chip=False` and another `root` serve the tests."""
    bm, cell, c, mix = load_cell(workload, root)
    if rate is not None:
        mix = dict(mix, rate_per_s=rate)
    devices = setup_jax(require_chip, cell["chips"])
    import jax

    from bench import traffic as TR
    from bench.reference import token_top1
    from bench.weights import make_weights
    from repro.core.control import MoElessController
    from repro.launch.mesh import make_serving_mesh
    from repro.obs import Telemetry
    from repro.serving.engine import ServingEngine

    meter = CompileMeter()
    jax.monitoring.register_event_duration_secs_listener(meter.duration)
    jax.monitoring.register_event_listener(meter.event)
    dev = devices[0]
    # without the chip (tests only) the v5e row stands in, so the
    # readers' plumbing runs; such numbers are never reported
    peaks = peaks_of(dev.device_kind if require_chip else "TPU v5 lite")
    s = c["serving"]
    cfg = program_config(c)
    params = make_weights(c, seed)
    topics = None
    if mix["ids"]["kind"] == "expert_topics":
        topics = TR.topic_sets(token_top1(c, params), cfg.moe.num_experts)
    gen = TR.Generator(mix, seed, cfg.vocab_size, topics)
    wgen = TR.Generator(mix, seed + 1, cfg.vocab_size, topics)
    burst = wgen.requests(mix["warmup_requests"],
                          max_new=mix["warmup_max_new"], rid0=10**9)
    stretch = wgen.window(mix["warmup_seconds"], rid0=2 * 10**9)
    sched = gen.window(seconds)
    mesh = None
    if cell["chips"] > 1 or s["ep"] > 1:
        mesh = make_serving_mesh(cell["chips"], ep=s["ep"])
    tel = Telemetry()
    engine = ServingEngine(cfg, params,
                           max_len=TR.max_len(mix, s["kv_block"]),
                           expert_runtime=s.get("expert_runtime", "on"),
                           mesh=mesh, telemetry=tel)
    ctl = ControlProxy(MoElessController(
        cfg, num_devices=s["control_devices"], telemetry=tel))
    engine.start(num_slots=s["num_slots"], control=ctl)
    server = Server(engine, tel, ctl, s["prefill_chunk"])
    warm_up(server, burst, stretch, mix, mix["warmup_seconds"], meter)
    server.steps.clear()
    ctl.calls.clear()
    n_warm = len(server.reqs)
    server.reqs.clear()
    dropped0 = float(ctl.dropped_tokens)

    tracer = {}
    if trace:
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0

    def close_window() -> float:
        t = time.perf_counter()
        tracer["steps"] = len(server.steps)
        tracer["reg1"] = tel.registry.as_dict()
        tracer["compiles"] = meter.snapshot()
        if trace:
            jax.profiler.stop_trace()
        return time.perf_counter() - t

    c0 = meter.snapshot()
    reg0 = tel.registry.as_dict()
    setup_s = time.perf_counter() - t_proc0
    jax.config.update("jax_log_compiles", True)   # names any compile
    if trace:
        jax.profiler.start_trace(str(TRACE_DIR), profiler_options=opts)
    if mix["loop"] == "open":
        t0, t_end = serve_open(server, sched, seconds, close_window)
    else:
        t0, t_end = serve_closed(server, sched, mix["clients"], seconds,
                                 close_window)
    jax.config.update("jax_log_compiles", False)
    c1, c2 = tracer["compiles"], meter.snapshot()
    log(f"warm-up served {n_warm} requests; window: "
        f"{c1[0] - c0[0]} backend compiles ({c1[1] - c0[1]:.3f} s), "
        f"{c1[2] - c0[2]} persistent-cache hits; drain after it: "
        f"{c2[0] - c1[0]} backend compiles ({c2[1] - c1[1]:.3f} s), "
        f"{c2[2] - c1[2]} persistent-cache hits")
    reqs = sorted(server.reqs.values(), key=lambda r: r.rid)
    late = [r.sent - r.due for r in reqs]
    log(f"generator lateness: max {max(late):.6f} s, p95 {p95(late):.6f} s"
        f" over {len(reqs)} requests")
    peak = device_peak(devices)
    e2e = end_to_end(reqs, t0, t_end, peak, setup_s)
    for r in reqs:       # a handle holds the engine: keep only tokens
        r.tokens, r.handle = list(r.handle.tokens), None
    unfinished = sum(1 for r in reqs if not r.finished)
    wrong_len = sum(1 for r in reqs if r.finished
                    and len(r.tokens) != r.max_new)
    dropped = float(ctl.dropped_tokens) - dropped0
    nonfinite = engine.result().nonfinite_logits
    win_steps = server.steps[:tracer["steps"]]
    loads = [st.loads for st in win_steps if st.loads is not None]
    if loads:
        mx = np.array([l.max(-1) / np.maximum(l.mean(-1), 1e-9)
                       for l in loads])
        log(f"expert load max/mean per step: median {np.median(mx):.3f}, "
            f"p95 {np.percentile(mx, 95):.3f} over {len(loads)} steps")

    out = {}
    if trace:
        from bench.trace_reduce import Trace
        tr = Trace.load(TRACE_DIR, len(devices))
        run = Run(cell, c, win_steps, reqs, (t0, t_end),
                  (reg0, tracer["reg1"]), tr, peaks)
        for m in metrics_for(bm, cell["name"], True):
            v = load_reader(m["name"], root / "bench")(run)
            if v is not None:
                out[m["name"]] = {"value": float(v), "unit": m["unit"]}
        busy_s, window_s = tr.busy_s(), tr.window_s()
        breakdown = tr.breakdown()
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
    else:
        for m in metrics_for(bm, cell["name"], False):
            v, unit = e2e[m["name"]]
            out[m["name"]] = {"value": float(v), "unit": unit}

    # free the program's state, then check against the reference
    sample = check_sample(reqs, seed)
    engine.close()
    del engine, server, ctl, params, tel
    gc.collect()
    gaps = gap_numbers(compare(c, seed, sample) if sample
                       else np.zeros(0))
    control_gaps = gap_numbers(compare(c, seed, sample, lower_pick=True)
                               if sample else np.zeros(0)) \
        if control else None
    log(f"widest gap {gaps['max_logit_gap']!r}, mean gap "
        f"{gaps['mean_logit_gap']!r} over the sample's served tokens")
    exact = {
        "unfinished_requests": (unfinished, 0),
        "wrong_token_counts": (wrong_len, 0),
        "dropped_assignments": (dropped, 0),
        "nonfinite_logits": (nonfinite, 0),
    }

    def judged(g: dict) -> tuple:
        checks = {k: (g[k], float(lim)) for k, lim in c["check"].items()}
        checks.update(exact)
        return checks, all(v <= lim for v, lim in checks.values())

    checks, correct = judged(gaps)
    log(f"compared {len(sample)} requests, "
        f"{sum(len(r.times) for r in sample)} served tokens")
    if control:
        _, control_correct = judged(control_gaps)
        for k, lim in c["check"].items():
            log(f"control {k}: {control_gaps[k]!r} (limit {float(lim)!r})")
        log(f"control correct: {control_correct}")
    for k, (v, lim) in checks.items():
        log(f"check {k}: {v!r} (limit {lim!r})")
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": peak}
    if rate is not None:
        waits = [r.admitted - r.due for r in reqs
                 if not math.isnan(r.admitted)]
        k = max(1, len(waits) // 5)
        busy = sum(min(st.t1, t_end) - st.t0 for st in win_steps
                   if st.t0 < t_end)
        result_sweep = {"queue_wait_first_fifth_s": float(np.mean(waits[:k])),
                        "queue_wait_last_fifth_s": float(np.mean(waits[-k:])),
                        "step_busy_share": busy / (t_end - t0)}
    result = {"correct": bool(correct), "attempted": len(reqs),
              "failed": unfinished, "metrics": out, "device": device}
    if trace:
        device["busy_s"] = busy_s
        device["window_s"] = window_s
        result["breakdown"] = breakdown
    if control:
        result["program_gaps"] = gaps
        result["control_gaps"] = control_gaps
        result["control_correct"] = bool(control_correct)
    if rate is not None:
        result["sweep"] = dict(result_sweep, rate_per_s=rate,
                               **{k: v[0] for k, v in e2e.items()})
    result["checks"] = {k: {"value": v, "limit": lim}
                        for k, (v, lim) in checks.items()}
    return result


def main(argv=None, t_proc0: float | None = None) -> int:
    import argparse
    t_proc0 = time.perf_counter() if t_proc0 is None else t_proc0
    ap = argparse.ArgumentParser(description="one run of one cell")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    a = ap.parse_args(argv)
    try:
        res = run_cell(a.workload, a.seed, a.seconds, bool(a.trace),
                       t_proc0)
    except NoChip as e:
        log(f"bench: {e}")
        return 2
    print(json.dumps(res), flush=True)
    return 0
