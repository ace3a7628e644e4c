"""The program's spans and device programs, and the readers over them,
on hand-made traces and on one recorded on the chip."""
from pathlib import Path
from types import SimpleNamespace

import pytest

from bench.harness import load_reader
from bench.program_trace import ProgramTrace, module_name
from bench.trace_reduce import Trace

DATA = Path(__file__).parent / "data"
RECORDED = DATA / "engine_spans.xplane.pb"

# ns; the harness's spans open the window at 0 and close it at 1000
SPANS = [("bench.step", 0, 400), ("bench.wait", 400, 500),
         ("bench.step", 500, 1000)]
OPS = {0: [("fusion.1", 60, 250), ("fusion.2", 360, 380),
           ("fusion.3", 530, 700), ("scatter.1", 950, 980),
           ("fusion.0", -50, -10)]}
PROGRAM = [
    ("engine.step", 10, 390, {"step_num": 0}),
    ("engine.dispatch", 50, 60,
     {"phase": "mixed", "rows": 2, "prefill_rows": 1}),
    ("control.step", 300, 350, {"phase": "mixed"}),
    ("engine.step", 510, 990, {"step_num": 1}),
    ("engine.dispatch", 520, 530,
     {"phase": "decode", "rows": 2, "prefill_rows": 0}),
    ("runtime.flush", 900, 950, {"transfers": 3, "bucket": "4"}),
    # program spans reaching outside the window
    ("engine.step", -100, 5, {"step_num": -1}),
    ("engine.step", 995, 1500, {"step_num": 2}),
]
MODULES = {0: [("jit_engine_step", -50, -10),
               ("jit_engine_step", 60, 250),
               ("jit_engine_step", 530, 700),
               ("jit_bank_update", 950, 980)]}


def _trace(program=PROGRAM, modules=MODULES):
    return ProgramTrace.from_events(OPS, SPANS, program, modules)


def _read(metric, trace):
    return load_reader(metric)(SimpleNamespace(trace=trace, steps=[]))


def test_window_stays_the_harness_spans_extent():
    plain = Trace.from_events(OPS, SPANS)
    t = _trace()
    assert (t.t0, t.t1) == (plain.t0, plain.t1) == (0, 1000)
    assert t.window_s() == pytest.approx(plain.window_s())
    assert t.busy_s() == pytest.approx(plain.busy_s())
    assert t.idle_share() == pytest.approx(plain.idle_share())


def test_sections_start_inside_the_window():
    t = _trace()
    assert [a["step_num"] for _, _, a in t.sections("engine.step")] \
        == [0, 1, 2]
    assert len(t.sections("engine.dispatch", phase="mixed")) == 1


def test_gaps_inside_steps_are_named_by_program_spans():
    gaps = dict((n, v) for n, v in _trace().breakdown()["idle_gaps"])
    # idle 0..60 (engine.step), 250..360 (control.step), 380..530
    # (bench.wait), 700..950 (engine.step), 980..1000 (engine.step)
    assert gaps["control.step"] == pytest.approx(110e-9)
    assert gaps["bench.wait"] == pytest.approx(150e-9)
    assert "bench.step" not in gaps
    plain = dict((n, v) for n, v in
                 Trace.from_events(OPS, SPANS).breakdown()["idle_gaps"])
    assert "bench.step" in plain and "control.step" not in plain


def test_breakdown_sums_device_programs():
    progs = dict(_trace().breakdown()["device_programs"])
    assert progs == {"jit_engine_step": pytest.approx(360e-9),
                     "jit_bank_update": pytest.approx(30e-9)}


def test_host_exposed_ms_per_step():
    # idle inside steps in the window: 10..390 less 60..250 and
    # 360..380 is 170; 510..990 less 530..700 and 950..980 is 280;
    # 995..1000 is 5; over three steps
    assert _read("host_exposed_ms_per_step", _trace()) == \
        pytest.approx(1e3 * 455e-9 / 3)


def test_bank_update_ms_per_step():
    assert _read("bank_update_ms_per_step", _trace()) == \
        pytest.approx(1e3 * 30e-9 / 3)


def test_step_device_ms_mixed_matches_dispatches_in_order():
    # the mixed dispatch at 50 launched the execution at 60..250; the
    # one before the window is never matched
    assert _read("step_device_ms.mixed", _trace()) == \
        pytest.approx(190e-6)
    t = _trace()
    got = [(a["phase"], ms) for _, _, a, ms, _ in
           t.dispatched("jit_engine_step", "engine.dispatch", 0)]
    assert got == [("mixed", 60), ("decode", 530)]


def test_a_lost_execution_shifts_no_later_match():
    """The profiler lost the mixed step's execution: that dispatch is
    left out, and the decode dispatch keeps its own."""
    lost = {0: [m for m in MODULES[0] if m[1] != 60]}
    t = _trace(modules=lost)
    got = [(a["phase"], ms) for _, _, a, ms, _ in
           t.dispatched("jit_engine_step", "engine.dispatch", 0)]
    assert got == [("decode", 530)]
    assert _read("step_device_ms.mixed", t) is None


@pytest.mark.parametrize("metric", ["host_exposed_ms_per_step",
                                    "bank_update_ms_per_step",
                                    "step_device_ms.mixed"])
def test_readers_find_nothing_in_a_program_without_spans(metric):
    """An older program has neither program spans nor these device
    program names: each reader returns nothing and raises nothing."""
    bare = ProgramTrace.from_events(
        OPS, SPANS, [], {0: [("jit_decode_step", 60, 250),
                             ("jit__scatter_slots", 950, 980)]})
    assert _read(metric, bare) is None
    assert load_reader(metric)(SimpleNamespace(trace=None,
                                               steps=[])) is None


def test_module_name_drops_the_hash():
    assert module_name("jit_engine_step(15526583290487371244)") == \
        "jit_engine_step"


def test_a_window_recorded_on_the_chip():
    """A TPU v5e trace of a few serving steps of a small MoE model with
    the expert runtime on and the Pallas kernels: the program's spans,
    named device programs and kernel names are all there, and the
    readers find something in it."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(str(RECORDED))
    t = ProgramTrace.over(Trace.from_xspace(pd, 1), pd, 1)
    names = {n for n, _, _, _ in t.program}
    assert {"engine.step", "engine.dispatch", "engine.fetch",
            "control.step", "runtime.apply", "runtime.flush"} <= names
    assert t.has_module("jit_engine_step")
    assert t.has_module("jit_bank_update")
    ops = " ".join(t.ops[0][0])
    assert '"kernel":"moe_ffn_quant"' in ops
    assert '"kernel":"moe_gmm_quant"' in ops
    assert '"kernel":"decode_attn_paged"' in ops
    phases = {a["phase"] for _, _, a in t.sections("engine.dispatch")}
    assert phases == {"mixed", "decode"}
    for metric in ("host_exposed_ms_per_step", "bank_update_ms_per_step",
                   "step_device_ms.mixed"):
        v = _read(metric, t)
        assert v is not None and v >= 0.0, metric
