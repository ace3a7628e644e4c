"""Telemetry layer: metrics registry semantics, Prometheus text
exposition (golden file), Chrome trace-event tracing, NOOP overhead
contract, and the instrumented serving scenario end-to-end."""
import json
import math

import numpy as np
import pytest

from repro.obs import (NOOP, MetricsRegistry, NullTelemetry, Telemetry,
                       Tracer)
from repro.obs.registry import TIME_BUCKETS

GOLDEN = __file__.rsplit("/", 1)[0] + "/goldens/metrics_exposition.txt"


# ---------------------------------------------------------------- registry


def test_counter_semantics():
    r = MetricsRegistry()
    c = r.counter("c_total", "help")
    c.inc()
    c.inc(2.5)
    assert r.as_dict()["c_total"] == 3.5
    with pytest.raises(ValueError):
        c.inc(-1)


def test_gauge_semantics():
    r = MetricsRegistry()
    g = r.gauge("g", "help")
    g.set(4.0)
    g.inc()
    assert r.as_dict()["g"] == 5.0
    g.labels().dec(2.0)
    assert r.as_dict()["g"] == 3.0


def test_histogram_buckets_cumulative():
    r = MetricsRegistry()
    h = r.histogram("h_seconds", "help", buckets=(0.1, 1.0))
    for v in (0.05, 0.5, 2.0):
        h.observe(v)
    d = r.as_dict()
    assert d['h_seconds_bucket{le="0.1"}'] == 1      # cumulative
    assert d['h_seconds_bucket{le="1"}'] == 2
    assert d['h_seconds_bucket{le="+Inf"}'] == 3
    assert d["h_seconds_count"] == 3
    assert d["h_seconds_sum"] == pytest.approx(2.55)


def test_labeled_families_and_schema_enforcement():
    r = MetricsRegistry()
    c = r.counter("req_total", "help", labels=("outcome",))
    c.labels(outcome="ok").inc(2)
    c.labels(outcome="err").inc()
    d = r.as_dict()
    assert d['req_total{outcome="ok"}'] == 2
    assert d['req_total{outcome="err"}'] == 1
    with pytest.raises(ValueError):                  # wrong label name
        c.labels(reason="ok")
    with pytest.raises(ValueError):                  # label-less access
        c.inc()


def test_label_cardinality_cap():
    r = MetricsRegistry()
    c = r.counter("c_total", "", labels=("rid",), max_series=8)
    for i in range(8):
        c.labels(rid=i).inc()
    with pytest.raises(ValueError, match="cardinality"):
        c.labels(rid=999).inc()
    # existing series stay usable after the cap fires
    c.labels(rid=0).inc()


def test_reregistration():
    r = MetricsRegistry()
    a = r.counter("x_total", "", labels=("k",))
    assert r.counter("x_total", "", labels=("k",)) is a   # idempotent
    with pytest.raises(ValueError, match="re-registered"):
        r.gauge("x_total")                                # kind mismatch
    with pytest.raises(ValueError, match="re-registered"):
        r.counter("x_total", "", labels=("other",))       # label mismatch


def test_prometheus_exposition_golden_file():
    """The exposition is byte-stable for a fixed recording sequence —
    the contract the gateway-smoke parser and dashboards rely on."""
    r = MetricsRegistry()
    c = r.counter("demo_requests_total", "requests served",
                  labels=("outcome",))
    c.labels(outcome="ok").inc(3)
    c.labels(outcome="error").inc()
    r.gauge("demo_queue_depth", "requests waiting").set(2)
    h = r.histogram("demo_latency_seconds", "request latency",
                    buckets=(0.1, 1.0))
    for v in (0.0625, 0.5, 2.0):                  # dyadic: exact sums
        h.observe(v)
    with open(GOLDEN) as f:
        assert r.render_prometheus() == f.read()


def test_exposition_escaping_and_inf():
    r = MetricsRegistry()
    r.counter("c_total", "", labels=("v",)).labels(v='a"b\\c\nd').inc()
    r.gauge("g").set(math.inf)
    text = r.render_prometheus()
    assert 'c_total{v="a\\"b\\\\c\\nd"} 1' in text
    assert "g +Inf" in text


def test_as_dict_matches_exposition_values():
    tel = Telemetry()
    tel.sched_admitted.inc(5)
    tel.engine_step_seconds.labels(phase="decode").observe(0.25)
    d = tel.registry.as_dict()
    assert d["scheduler_admitted_total"] == 5
    assert d['engine_step_seconds_count{phase="decode"}'] == 1
    for line in tel.registry.render_prometheus().splitlines():
        if line.startswith("#") or not line:
            continue
        series, value = line.rsplit(" ", 1)
        assert d[series] == pytest.approx(
            float(value.replace("+Inf", "inf")))


# ------------------------------------------------------------------ tracer


def test_tracer_roundtrip(tmp_path):
    tr = Tracer(process_name="test-proc")
    tr.span("engine", "decode_step", 0.0, 0.5, args={"occupancy": 3})
    tr.span("engine/req0", "queue", 0.0, 0.1)
    tr.span("engine/req0", "prefill", 0.1, 0.2)
    tr.instant("engine/req0", "finish", 0.7)
    tr.counter("engine", "tokens", 0.5, generated=12)
    path = tmp_path / "trace.json"
    n = tr.write(str(path))
    obj = json.loads(path.read_text())               # valid JSON
    assert len(obj["traceEvents"]) == n
    evs = obj["traceEvents"]
    meta = [e for e in evs if e["ph"] == "M"]
    names = {e["args"]["name"] for e in meta}
    assert {"test-proc", "engine", "engine/req0"} <= names
    # one stable tid per track; ts in microseconds
    by_name = {e["name"]: e for e in evs if e["ph"] != "M"}
    assert by_name["queue"]["tid"] == by_name["finish"]["tid"]
    assert by_name["queue"]["tid"] != by_name["decode_step"]["tid"]
    assert by_name["decode_step"]["dur"] == pytest.approx(0.5e6)
    assert by_name["finish"]["ts"] == pytest.approx(0.7e6)


def _assert_monotonic_per_track(obj):
    last: dict[int, float] = {}
    for e in obj["traceEvents"]:
        if e["ph"] == "M":
            continue
        assert e["ts"] >= last.get(e["tid"], -math.inf), e
        last[e["tid"]] = e["ts"]


def test_tracer_negative_duration_clamped():
    tr = Tracer()
    tr.span("t", "s", 1.0, 0.5)          # caller bug: t1 < t0
    ev = tr.to_obj()["traceEvents"][-1]
    assert ev["dur"] == 0.0


# ----------------------------------------------------------- NOOP contract


def test_noop_telemetry_swallows_everything():
    assert NOOP.enabled is False and NOOP.tracing is False
    assert NOOP.registry is None and NOOP.tracer is None
    NOOP.sched_admitted.inc()
    NOOP.anything.labels(a=1, b=2).observe(3.0)      # any chain no-ops
    NOOP.span("t", "s", 0.0, 1.0)
    NOOP.instant("t", "i", 0.0)
    assert isinstance(NOOP, NullTelemetry)


def test_telemetry_taxonomy_registers_cleanly():
    tel = Telemetry()
    assert tel.enabled and not tel.tracing
    text = tel.registry.render_prometheus()
    for fam in ("scheduler_admitted_total", "engine_steps_total",
                "runtime_replica_starts_total", "control_iterations_total",
                "router_requests_total"):
        assert f"# TYPE {fam} counter" in text
    assert "# TYPE engine_step_seconds histogram" in text
    # two handles share one registry without re-registration conflicts
    Telemetry(registry=tel.registry)


# ----------------------------------------------------- percentile_summary


def test_percentile_summary_count_and_mean():
    from repro.serving.scheduler import RequestMetrics, percentile_summary
    rs = [RequestMetrics(rid=i, arrival=0.0, in_tokens=8, out_tokens=4,
                         ttft=0.1 * (i + 1), tpot=0.05,
                         e2e=1.0 * (i + 1)) for i in range(4)]
    s = percentile_summary(rs)
    assert s["e2e"]["count"] == 4
    assert s["e2e"]["mean"] == pytest.approx(np.mean([1.0, 2.0, 3.0, 4.0]))
    assert s["ttft"]["count"] == 4
    empty = percentile_summary([])
    for m in ("ttft", "tpot", "e2e"):
        assert empty[m] == {"count": 0, "mean": 0.0, "p50": 0.0,
                            "p95": 0.0, "p99": 0.0}
    # single-token requests are excluded from TPOT but counted elsewhere
    one = percentile_summary([RequestMetrics(
        rid=0, arrival=0.0, in_tokens=8, out_tokens=1, ttft=0.1,
        tpot=0.0, e2e=0.1)])
    assert one["tpot"]["count"] == 0 and one["e2e"]["count"] == 1


# ------------------------------------------------------- scale-event ring


def test_autoscaler_ring_bounded_total_monotonic():
    from repro.serving.gateway.driver import ReplicaMeters
    from repro.serving.gateway.router import (SCALE_EVENT_RING,
                                              Autoscaler, AutoscalerConfig)

    sc = Autoscaler(AutoscalerConfig(
        min_replicas=1, max_replicas=1000, queue_delay_up_s=1e-9,
        sustain=1, cooldown_s=0.0), resident_gb=1.0)

    def hot(n, t):
        return [ReplicaMeters(
            replica_id=i, healthy=True, draining=False, pending=2,
            running=1, free_slots=0, outstanding_tokens=8,
            queue_delay_s=9.0, completed=0, cancelled=0, clock_s=t,
            gb_s=0.0, idle=False) for i in range(n)]

    n = 1
    for k in range(100):                 # 100 up decisions > ring size
        want, _ = sc.observe(float(k), hot(n, float(k)))
        assert want == n + 1
        n = want
    assert sc.events_total == 100
    assert len(sc.events) == SCALE_EVENT_RING
    # the ring keeps the NEWEST events
    assert sc.events[-1].t == 99.0
    assert sc.events[0].t == float(100 - SCALE_EVENT_RING)


# ------------------------------------------- control-plane L1 error gauge


class _FixedErrorModel:
    """Stub PredictorErrorModel: prediction = actual + known offset."""

    def __init__(self, offset):
        self.offset = np.asarray(offset, np.float64)

    def predict(self, rng, actual, layer, distance):
        return np.asarray(actual, np.float64) + self.offset


def test_control_plane_l1_error_hand_computed():
    from repro.configs import get_config
    from repro.core.control import ControlPlane

    cfg = get_config("mixtral-8x7b", smoke=True)
    E = cfg.moe.num_experts
    offset = np.arange(E, dtype=np.float64)          # |pred-act| = offset
    tel = Telemetry()
    cp = ControlPlane(cfg, "megatron-lm", num_devices=4,
                      error_model=_FixedErrorModel(offset), telemetry=tel,
                      straggler_factor=1.5)
    acts = np.tile(np.linspace(4.0, 8.0, E), (cp.n_layers, 1))
    cp.step(0.0, None, acts, phase="decode")
    d = tel.registry.as_dict()
    for l in range(cp.n_layers):
        assert d[f'control_pred_load_l1_error{{layer="{l}"}}'] == \
            pytest.approx(float(offset.sum()))
        assert d[f'control_load_max{{layer="{l}"}}'] == pytest.approx(8.0)
        assert d[f'control_load_mean{{layer="{l}"}}'] == \
            pytest.approx(6.0)
        assert d[f'control_imbalance_factor{{layer="{l}"}}'] == \
            pytest.approx(8.0 / 6.0)
    assert d['control_iterations_total{phase="decode"}'] == 1
    # one host-timed control step; the modelled per-layer latency family
    # is gone from the registry
    assert d["control_step_seconds_count"] == 1
    assert not any(k.startswith("control_layer_latency") for k in d)
    assert "control_stragglers_total" not in d       # 8/6 < 1.5


def test_control_plane_straggler_flagged():
    from repro.configs import get_config
    from repro.core.control import ControlPlane

    cfg = get_config("mixtral-8x7b", smoke=True)
    E = cfg.moe.num_experts
    tel = Telemetry(tracer=Tracer())
    cp = ControlPlane(cfg, "megatron-lm", num_devices=4,
                      error_model=_FixedErrorModel(np.zeros(E)),
                      telemetry=tel, straggler_factor=2.0,
                      track="lane/control")
    acts = np.ones((cp.n_layers, E))
    acts[:, 0] = 100.0                               # one hot expert
    cp.step(1.0, None, acts)
    d = tel.registry.as_dict()
    assert d["control_stragglers_total"] == cp.n_layers
    evs = tel.tracer.to_obj()["traceEvents"]
    stragglers = [e for e in evs if e["name"] == "straggler"]
    assert len(stragglers) == cp.n_layers
    assert stragglers[0]["ph"] == "i"
    assert stragglers[0]["args"]["layer"] == 0


# --------------------------------------- instrumented serving end-to-end


@pytest.fixture(scope="module")
def smoke_model():
    import jax

    from repro.configs import get_config
    from repro.models import model as M

    cfg = get_config("mixtral-8x7b", smoke=True)
    return cfg, M.init_params(cfg, jax.random.PRNGKey(0))


def _mk_reqs(cfg, n, gen=4, prompt_len=8):
    from repro.serving.scheduler import GenRequest
    rng = np.random.default_rng(0)
    return [GenRequest(
        rid=i, arrival=float("nan"),
        prompt=rng.integers(0, cfg.vocab_size, size=prompt_len,
                            dtype=np.int32),
        max_new_tokens=gen) for i in range(n)]


def test_instrumented_serve_identical_to_noop(smoke_model):
    """Telemetry is observation-only: request metrics on the MODELED
    serving clock from an instrumented serve match the NOOP default
    bit-for-bit (the control plane pins the clock to modeled latency;
    without one the clock advances by non-deterministic wall time)."""
    from repro.core.control import ControlPlane
    from repro.serving.engine import ServingEngine

    cfg, params = smoke_model

    def run(tel):
        eng = ServingEngine(cfg, params, max_len=16, telemetry=tel)
        reqs = _mk_reqs(cfg, 3)
        for r in reqs:
            r.arrival = 0.0
        res = eng.serve(reqs, num_slots=2,
                        control=ControlPlane(cfg, "megatron-lm",
                                             num_devices=4,
                                             telemetry=tel))
        return [(r.rid, r.out_tokens, r.ttft, r.e2e)
                for r in res.records]

    assert run(None) == run(Telemetry(tracer=Tracer()))


def test_gateway_scenario_trace_and_metrics(smoke_model, tmp_path):
    """One unthreaded router scenario produces the full observable
    surface: queue/prefill/decode spans + finish instants per request,
    a ScaleEvent instant, populated registry families, and a trace that
    round-trips through JSON with per-track monotonic timestamps."""
    from repro.serving.engine import ServingEngine
    from repro.serving.gateway import (AutoscalerConfig, Backpressure,
                                       EngineDriver, Router)

    cfg, params = smoke_model
    tracer = Tracer(process_name="test-gateway")
    tel = Telemetry(tracer=tracer)

    def factory(i):
        eng = ServingEngine(cfg, params, max_len=16, telemetry=tel,
                            name=f"replica{i}")
        return EngineDriver(eng, replica_id=i, num_slots=1, max_pending=2)

    router = Router(factory, threaded=False, telemetry=tel,
                    scaler=AutoscalerConfig(
                        min_replicas=1, max_replicas=2,
                        queue_delay_up_s=1e-9, sustain=1, cooldown_s=0.0))
    scale_events = []
    for req in _mk_reqs(cfg, 5):
        try:
            router.submit(req)
        except Backpressure:
            pass
        router.step_all()
        scale_events += router.autoscale(router.clock())
    for _ in range(10_000):
        if not any(d.engine.has_work for d in router.replicas.values()
                   if d.healthy):
            break
        router.step_all()
        scale_events += router.autoscale(router.clock())
    router.refresh_telemetry()
    d = tel.registry.as_dict()
    router.stop()

    assert any(e.action == "up" for e in scale_events)
    assert d['router_scale_events_total{action="up"}'] >= 1
    assert d["router_replicas"] == 2
    assert d["scheduler_admitted_total"] >= 2
    assert d['engine_steps_total{phase="decode"}'] >= 1
    assert d["scheduler_queue_delay_seconds_count"] == \
        d["scheduler_admitted_total"]
    assert d['replica_healthy{replica="0"}'] == 1

    path = tmp_path / "gw.json"
    tracer.write(str(path))
    obj = json.loads(path.read_text())
    _assert_monotonic_per_track(obj)
    names = [e["name"] for e in obj["traceEvents"] if e["ph"] != "M"]
    for want in ("queue", "prefill", "decode", "decode_step", "finish"):
        assert want in names, (want, sorted(set(names)))
    scale = [e for e in obj["traceEvents"]
             if e["name"].startswith("ScaleEvent:")]
    assert scale and scale[0]["ph"] == "i"
    assert scale[0]["args"]["n_after"] == 2
    # every admitted request got its own queue->prefill->decode->finish
    finishes = [e for e in obj["traceEvents"] if e["name"] == "finish"]
    assert len(finishes) == d["scheduler_admitted_total"]


# ------------------------------------------------- host-clock sections


def test_noop_section_is_the_shared_noop_context():
    a = NOOP.section("engine.step", step_num=1)
    b = NOOP.section("control.plan")
    assert a is b
    with a as s:
        assert s is a
    assert a.seconds == 0.0


def test_sections_nest_on_the_host_clock_with_parent_and_rid():
    import time
    tel = Telemetry(tracer=Tracer())
    t_before = time.perf_counter()
    with tel.section("engine.step", step_num=0) as outer:
        with tel.section("engine.dispatch", phase="mixed", rows=2,
                         prefill_rows=1):
            pass
        with tel.section("engine.commit", rid=7):
            pass
    t_after = time.perf_counter()
    evs = tel.tracer.to_obj()["traceEvents"]
    spans = [e for e in evs if e["ph"] == "X"]
    # appended as they open: start order, parent before its children
    assert [e["name"] for e in spans] == \
        ["engine.step", "engine.dispatch", "engine.commit"]
    by = {e["name"]: e for e in spans}
    step = by["engine.step"]
    assert t_before * 1e6 <= step["ts"]
    assert step["ts"] + step["dur"] <= t_after * 1e6 + 1e-3
    assert outer.seconds == pytest.approx(step["dur"] * 1e-6, abs=1e-9)
    assert "parent" not in step.get("args", {})
    assert step["args"]["step_num"] == 0
    for name in ("engine.dispatch", "engine.commit"):
        c = by[name]
        assert c["args"]["parent"] == "engine.step"
        assert c["tid"] == step["tid"]
        assert step["ts"] <= c["ts"]
        assert c["ts"] + c["dur"] <= step["ts"] + step["dur"] + 1e-3
    assert by["engine.dispatch"]["args"]["phase"] == "mixed"
    assert by["engine.commit"]["args"]["rid"] == 7
    track = [e for e in evs if e["ph"] == "M"
             and e["tid"] == step["tid"]][0]["args"]["name"]
    assert track.startswith("host/")


def test_sections_keep_one_parent_stack_per_thread():
    import threading
    tel = Telemetry(tracer=Tracer())

    def worker():
        with tel.section("runtime.apply"):
            with tel.section("runtime.flush", transfers=2, bucket="2"):
                pass

    with tel.section("engine.step"):
        th = threading.Thread(target=worker, name="flusher")
        th.start()
        th.join(timeout=30)
    assert not th.is_alive()
    by = {e["name"]: e for e in tel.tracer.to_obj()["traceEvents"]
          if e["ph"] == "X"}
    assert "parent" not in by["runtime.apply"].get("args", {})
    assert by["runtime.flush"]["args"]["parent"] == "runtime.apply"
    assert by["runtime.apply"]["tid"] != by["engine.step"]["tid"]
    assert by["runtime.flush"]["tid"] == by["runtime.apply"]["tid"]


def test_section_lands_in_the_profiler_trace(tmp_path):
    import jax
    import jax.numpy as jnp
    from jax.profiler import ProfileData

    tel = Telemetry()                   # no tracer: profiler spans only
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        with tel.section("engine.step", step_num=3):
            with tel.section("engine.dispatch", phase="decode", rows=4,
                             prefill_rows=0):
                jax.block_until_ready(jnp.ones(4) * 2)
    finally:
        jax.profiler.stop_trace()
    pd = ProfileData.from_file(str(next(tmp_path.rglob("*.xplane.pb"))))
    evs = {e.name: (e, dict(e.stats)) for p in pd.planes
           if p.name.startswith("/host:") for line in p.lines
           for e in line.events if e.name.startswith("engine.")}
    assert set(evs) == {"engine.step", "engine.dispatch"}
    assert evs["engine.dispatch"][1] == \
        {"phase": "decode", "rows": 4, "prefill_rows": 0}
    assert evs["engine.step"][1]["step_num"] == 3
    step, disp = evs["engine.step"][0], evs["engine.dispatch"][0]
    assert step.start_ns <= disp.start_ns and disp.end_ns <= step.end_ns


def _chunked_runtime_engine(cfg, params, tel):
    from repro.configs import ServingSpec
    from repro.core.control import ControlPlane
    from repro.serving.engine import ServingEngine
    eng = ServingEngine(cfg, params, max_len=32, expert_runtime="on",
                        telemetry=tel,
                        serving=ServingSpec(kv="paged", kv_block=4,
                                            prefill_chunk=4))
    ctl = ControlPlane(cfg, "moeless", num_devices=8,
                       max_replicas_per_device=2, telemetry=tel)
    return eng, ctl


def _serve_chunked(cfg, params, tel, time_scale=1.0):
    eng, ctl = _chunked_runtime_engine(cfg, params, tel)
    reqs = _mk_reqs(cfg, 3, gen=4, prompt_len=8)
    for r in reqs:
        r.arrival = 0.0
    res = eng.serve(reqs, num_slots=2, control=ctl, time_scale=time_scale)
    return reqs, res


def test_instrumented_chunked_runtime_serve_identical_to_noop(smoke_model):
    """Sections, host-clock spans and the runtime's flush sections are
    observation-only on the chunked-prefill path with the expert
    runtime executing plans: tokens and modelled metrics match NOOP."""
    cfg, params = smoke_model

    def run(tel):
        reqs, res = _serve_chunked(cfg, params, tel)
        return ([(r.rid, tuple(r.tokens)) for r in reqs],
                [(r.rid, r.ttft, r.e2e) for r in res.records],
                res.iterations, res.runtime.stats.transfers)

    assert run(None) == run(Telemetry(tracer=Tracer()))


def test_engine_spans_run_on_the_host_clock(smoke_model):
    """With the modelled clock scaled far past wall time, every span of
    a traced serve still lies inside the serve's host interval, and the
    sections nest as engine.step > engine.* / control.step / runtime.apply
    > control.loads, control.plan / runtime.flush."""
    import time
    cfg, params = smoke_model
    tel = Telemetry(tracer=Tracer())
    t0 = time.perf_counter()
    reqs, res = _serve_chunked(cfg, params, tel, time_scale=1e8)
    t1 = time.perf_counter()
    assert res.clock_s > 10 * (t1 - t0)        # the modelled clock ran off
    evs = [e for e in tel.tracer.to_obj()["traceEvents"] if e["ph"] != "M"]
    for e in evs:
        assert t0 * 1e6 <= e["ts"] <= t1 * 1e6, e
        assert e["ts"] + e.get("dur", 0.0) <= t1 * 1e6 + 1e-3, e
    want_parent = {
        "engine.admit": "engine.step", "engine.prepare": "engine.step",
        "engine.dispatch": "engine.step", "engine.fetch": "engine.step",
        "engine.commit": "engine.step", "control.step": "engine.step",
        "runtime.apply": "engine.step", "control.loads": "control.step",
        "control.plan": "control.step", "runtime.flush": "runtime.apply"}
    seen = set()
    for e in evs:
        if e["name"] == "runtime.apply" \
                and e["args"]["phase"] == "bootstrap":
            assert "parent" not in e["args"]    # applied at start()
        elif e["name"] in want_parent:
            assert e["args"]["parent"] == want_parent[e["name"]], e
            seen.add(e["name"])
    assert seen == set(want_parent)
    steps = [e for e in evs if e["name"] == "engine.step"]
    assert len(steps) == res.iterations
    dispatch = [e["args"] for e in evs if e["name"] == "engine.dispatch"]
    phases = {a["phase"] for a in dispatch}
    assert phases == {"mixed", "decode"}
    assert all((a["prefill_rows"] > 0) == (a["phase"] == "mixed")
               and a["rows"] >= a["prefill_rows"] for a in dispatch)
    flush = [e["args"] for e in evs if e["name"] == "runtime.flush"]
    for a in flush:
        for b in str(a["bucket"]).split(","):
            assert int(b) & (int(b) - 1) == 0          # a power of two
    # each request's spans share its rid and run queue -> prefill ->
    # decode -> finish in host time
    for r in reqs:
        mine = [e for e in evs if e.get("args", {}).get("rid") == r.rid
                and e["name"] in ("queue", "prefill", "decode", "finish")]
        assert [e["name"] for e in mine] == \
            ["queue", "prefill", "decode", "finish"]
        for a, b in zip(mine, mine[1:]):
            assert a["ts"] + a.get("dur", 0.0) <= b["ts"] + 1e-3


def test_step_and_queue_histograms_on_the_host_clock(smoke_model):
    import time
    cfg, params = smoke_model
    tel = Telemetry()
    t0 = time.perf_counter()
    _, res = _serve_chunked(cfg, params, tel, time_scale=1e8)
    wall = time.perf_counter() - t0
    d = tel.registry.as_dict()
    n = 0
    for phase in ("mixed", "decode"):
        k = d[f'engine_steps_total{{phase="{phase}"}}']
        assert d[f'engine_step_seconds_count{{phase="{phase}"}}'] == k
        n += k
    assert n == res.iterations
    step_s = sum(d[f'engine_step_seconds_sum{{phase="{p}"}}']
                 for p in ("mixed", "decode"))
    assert 0.0 < step_s <= wall
    assert d["scheduler_queue_delay_seconds_count"] == 3
    assert 0.0 <= d["scheduler_queue_delay_seconds_sum"] <= 3 * wall
    assert d["control_step_seconds_count"] == res.iterations
    assert 0.0 < d["control_step_seconds_sum"] <= step_s
    assert d["runtime_bank_flush_seconds_count"] >= res.iterations
    assert "runtime_overlap_hidden_seconds_total" not in d
    text = tel.registry.render_prometheus()
    assert "runtime_overlap_hidden" not in text
    assert "control_layer_latency" not in text
    assert "# HELP runtime_bank_flush_seconds host wall time to dispatch" \
        in text


def test_programs_carry_stable_names(smoke_model, tmp_path):
    """The engine's step and the runtime's slot-bank update run as jitted
    programs named engine_step and bank_update (XLA modules jit_<name>
    on the chip), and the greedy sampler as JAX's own _argmax, seen here
    as the profiler's host events for their calls, each inside its
    section."""
    import jax
    from jax.profiler import ProfileData
    cfg, params = smoke_model
    tel = Telemetry()
    eng, ctl = _chunked_runtime_engine(cfg, params, tel)
    reqs = _mk_reqs(cfg, 2, gen=3, prompt_len=8)
    eng.start(num_slots=2, control=ctl)
    for r in reqs:
        eng.submit(r)
    eng.step()                          # compile outside the trace
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        while eng.has_work:
            eng.step()
    finally:
        jax.profiler.stop_trace()
    eng.close()
    pd = ProfileData.from_file(str(next(tmp_path.rglob("*.xplane.pb"))))
    host = [(e.name, e.start_ns, e.end_ns) for p in pd.planes
            if p.name.startswith("/host:") for line in p.lines
            for e in line.events]

    def inside(prog, section):
        calls = [(s, e) for n, s, e in host
                 if n == f"PjitFunction({prog})"]
        spans = [(s, e) for n, s, e in host if n == section]
        assert calls and spans, (prog, section)
        return all(any(a <= s and e <= b for a, b in spans)
                   for s, e in calls)

    assert inside("engine_step", "engine.dispatch")
    assert inside("_argmax", "engine.fetch")
    assert inside("bank_update", "runtime.flush")
