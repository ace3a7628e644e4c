"""The trace reduction on hand-made traces."""
import pytest

from bench.trace_reduce import Trace

# ns; harness spans open the window at 0 and close it at 100
SPANS = [("bench.step", 0, 60), ("bench.control", 40, 50),
         ("bench.wait", 60, 100)]


def _trace():
    ops = {0: [("fusion.1", 5, 20), ("_ffn_q_kernel", 10, 30),
               ("all-to-all.3", 28, 40), ("copy", 70, 80),
               ("late", 95, 130)]}
    return Trace.from_events(ops, SPANS)


def test_busy_is_the_union_inside_the_window():
    t = _trace()
    assert t.window_s() == pytest.approx(100e-9)
    # [5, 40) + [70, 80) + [95, 100) after clipping
    assert t.busy_s() == pytest.approx(50e-9)
    assert t.idle_share() == pytest.approx(0.5)


def test_op_seconds_by_name():
    t = _trace()
    assert t.op_seconds(lambda n: "ffn" in n) == pytest.approx(20e-9)
    assert t.op_seconds(lambda n: n == "late") == pytest.approx(5e-9)


def test_exposed_time_excludes_overlapped_compute():
    t = _trace()
    # the all-to-all runs 28..40; the kernel covers 28..30
    assert t.exposed_seconds(lambda n: n.startswith("all-to-all")) == \
        pytest.approx(10e-9)


def test_busy_averages_over_devices():
    ops = {0: [("a", 0, 50)], 1: [("a", 0, 100)]}
    assert Trace.from_events(ops, [("bench.step", 0, 100)]).busy_s() == \
        pytest.approx(75e-9)


def test_breakdown_names_gaps_by_the_covering_span():
    b = _trace().breakdown()
    assert b["device_ops"][0] == ["_ffn_q_kernel ", pytest.approx(20e-9)]
    gaps = dict((n, v) for n, v in b["idle_gaps"])
    # idle 0..5 (step), 40..70 (control covers 40..50, the middle 55 is
    # in the step), 80..95 (wait)
    assert gaps["bench.wait"] == pytest.approx(15e-9)
    assert b["idle_gaps"][0] == ["bench.step", pytest.approx(30e-9)]


def test_short_name_drops_operands():
    from bench.trace_reduce import short_name
    op = ("%closed_call.8 = bf16[20,1024,14336]{2,1,0:T(8,128)(2,1)} "
          "custom-call(s32[20]{0} %a, bf16[20,1024,4096]{2,1,0} %b)")
    assert short_name(op) == "closed_call.8 bf16[20,1024,14336]"


def test_kernel_rank_tells_the_kernel_families_apart():
    from bench.trace_reduce import kernel_rank
    gmm = ("%closed_call.8 = bf16[20,1024,14336]{2,1,0:T(8,128)(2,1)} "
           "custom-call(s32[20]{0} %a, bf16[20,1024,4096]{2,1,0} %b)")
    attn = ("%closed_call.3 = bf16[8,8,4,128]{3,2,1,0} custom-call("
            "s32[8,208]{1,0} %t, bf16[8,8,4,128]{3,2,1,0} %q)")
    assert kernel_rank(gmm) == 3 and kernel_rank(attn) == 4
    assert kernel_rank("%fusion.1 = bf16[8,64]{1,0} fusion(%a)") is None
    assert kernel_rank("%c = (f32[8]{0}, s32[8]{0}) custom-call(%a)") is None


DATA = __import__("pathlib").Path(__file__).parent / "data"


def _plain_busy_ns(pd):
    """Busy time of /device:TPU:0 inside the harness spans' window,
    merged interval by interval, straight from the profile."""
    spans = [(e.start_ns, e.end_ns) for p in pd.planes
             if p.name.startswith("/host:") for l in p.lines
             for e in l.events if e.name.startswith("bench.")]
    lo, hi = min(s for s, _ in spans), max(e for _, e in spans)
    ivs = sorted((max(e.start_ns, lo), min(e.end_ns, hi))
                 for p in pd.planes if p.name == "/device:TPU:0"
                 for l in p.lines if l.name == "XLA Ops" for e in l.events
                 if e.end_ns > lo and e.start_ns < hi)
    busy, cur = 0.0, None
    for s, e in ivs:
        if cur is None or s > cur[1]:
            if cur is not None:
                busy += cur[1] - cur[0]
            cur = [s, e]
        else:
            cur[1] = max(cur[1], e)
    return busy + (cur[1] - cur[0] if cur else 0.0), hi - lo


def test_a_trace_recorded_on_the_chip():
    """A TPU v5e trace of three harness-like steps, each a matmul chain,
    the int8 expert FFN (two GMM kernels) and paged decode attention,
    with a 5 ms wait between steps."""
    from jax.profiler import ProfileData

    from bench.trace_reduce import kernel_rank
    tr = Trace.load(DATA, 1)
    pd = ProfileData.from_file(str(DATA / "tpu_small.xplane.pb"))
    busy, window = _plain_busy_ns(pd)
    assert tr.window_s() == pytest.approx(window * 1e-9)
    assert tr.busy_s() == pytest.approx(busy * 1e-9, rel=1e-9)
    assert 0.0 < tr.idle_share() < 1.0
    assert sorted(n for n, _, _ in tr.spans).count("bench.step") == 3
    names = tr.ops[0][0]
    assert sum(kernel_rank(n) == 3 for n in names) == 2     # gate-up, down
    assert sum(kernel_rank(n) == 4 for n in names) == 1     # attention
    gmm = tr.op_seconds(lambda n: kernel_rank(n) == 3)
    assert 0.0 < gmm < tr.busy_s()
    gaps = tr.breakdown()["idle_gaps"]
    assert gaps[0][0] == "bench.wait" and gaps[0][1] > 4e-3
