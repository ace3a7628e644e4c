"""Device ms per engine step spent writing expert slot banks: the
device time of the `jit_bank_update` program's executions in the
window over the number of the window's `engine.step` spans."""
from bench.program_trace import ProgramTrace


def read(run):
    pt = ProgramTrace.of(run)
    if pt is None or not pt.has_module("jit_bank_update"):
        return None
    steps = pt.sections("engine.step")
    if not steps:
        return None
    return 1e3 * pt.module_seconds("jit_bank_update") / len(steps)
