"""Mean host duration, in ms, of the window's `engine.step()` calls in
which `engine_steps_total{phase="mixed"}` rose (steps carrying prompt
chunks)."""


def read(run):
    d = [s.t1 - s.t0 for s in run.steps if s.phase == "mixed"]
    return 1e3 * sum(d) / len(d) if d else None
