"""Model FLOPs of the rows each window step processed
(`bench/flops.py` `step_flops`), summed, over the summed host durations
of those steps, as a share of the chip's bf16 peak. Time spent waiting
for arrivals lies outside every step and is left out."""
from bench.flops import step_flops
from bench.weights import dims


def read(run):
    if not run.steps:
        return None
    m = dims(run.config)
    flops = sum(step_flops(m, s.prefill, s.decode_ctx) for s in run.steps)
    secs = sum(s.t1 - s.t0 for s in run.steps)
    return 100.0 * flops / secs / run.peaks["bf16_flops_per_s"]
