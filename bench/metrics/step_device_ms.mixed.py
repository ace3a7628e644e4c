"""Mean device ms of the batched step program on steps carrying prompt
chunks: each `engine.dispatch` span of the window, in order, is matched
to the `jit_engine_step` execution it launched, and the executions
matched to spans whose `phase` is `mixed` are averaged (first device)."""
from bench.program_trace import ProgramTrace


def read(run):
    pt = ProgramTrace.of(run)
    if pt is None or not pt.modules:
        return None
    dev = min(pt.modules)
    d = [me - ms for _, _, a, ms, me in
         pt.dispatched("jit_engine_step", "engine.dispatch", dev)
         if a.get("phase") == "mixed"]
    return 1e-6 * sum(d) / len(d) if d else None
