"""Expert slot copies the runtime made per engine step in the window:
the rise of `runtime_transfers_total` over the number of steps."""


def read(run):
    if not run.steps:
        return None
    a, b = run.registry
    key = "runtime_transfers_total"
    return (b.get(key, 0.0) - a.get(key, 0.0)) / len(run.steps)
