"""Share of its roofline that the dequantising grouped-GMM kernels
reach: the least time the chip needs for the window's routed expert work
(`bench/flops.py` `gmm_work`, from the routed loads of every step),
over the device time of the GMM kernels' events in the trace. The
routed work is bound by memory in decode steps and near the balance
point in mixed steps; each step takes the larger of its two times."""
from bench.flops import gmm_work, roofline_s
from bench.trace_reduce import kernel_rank
from bench.weights import dims


def is_gmm(name: str) -> bool:
    """The grouped-GMM kernel calls: custom calls returning (E, C, F)."""
    return kernel_rank(name) == 3


def read(run):
    if run.trace is None:
        return None
    spent = run.trace.op_seconds(is_gmm)
    if spent <= 0:
        return None
    m = dims(run.config)
    need = 0.0
    for s in run.steps:
        if s.loads is None:
            continue
        for layer in s.loads:
            need += roofline_s(*gmm_work(layer, m["d"], m["f"]),
                               run.peaks)[0]
    return 100.0 * need / spent if need > 0 else None
