"""Device-idle ms per engine step inside the program's `engine.step`
spans: the host work the chip waits for (batch build, token fetch,
control plane, slot-bank flush dispatch), summed over the window's
steps and divided by their number. Idle time outside every step
(waiting for arrivals) is not counted."""
from bench.program_trace import ProgramTrace


def read(run):
    pt = ProgramTrace.of(run)
    if pt is None or not pt.ops:
        return None
    steps = pt.sections("engine.step")
    if not steps:
        return None
    return 1e3 * pt.idle_inside((s, e) for s, e, _ in steps) / len(steps)
