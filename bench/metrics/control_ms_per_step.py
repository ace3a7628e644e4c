"""Host ms per engine step spent in `MoElessController.step` (predict,
scale, place), timed by the harness's proxy."""


def read(run):
    if not run.steps:
        return None
    return 1e3 * sum(s.control_s for s in run.steps) / len(run.steps)
