"""Share of its roofline that the paged decode attention kernel reaches:
the least time the chip needs to read every decode row's live K/V
(`bench/flops.py` `decode_attn_work`) over the device time of the
kernel's events in the trace (memory-bound). Decode-only steps run the
kernel; steps with prompt chunks attend through the jnp path and are not
counted. The wrapper's transpose of the pool lies outside the kernel's
event and is not counted either."""
from bench.flops import decode_attn_work, roofline_s
from bench.trace_reduce import kernel_rank
from bench.weights import dims


def is_decode_attn(name: str) -> bool:
    """The paged decode attention kernel calls: custom calls returning
    (B, KV, G, hd)."""
    return kernel_rank(name) == 4


def read(run):
    if run.trace is None:
        return None
    spent = run.trace.op_seconds(is_decode_attn)
    if spent <= 0:
        return None
    m = dims(run.config)
    need = 0.0
    for s in run.steps:
        if s.phase == "decode" and s.decode_ctx:
            need += m["layers"] * roofline_s(*decode_attn_work(
                s.decode_ctx, m["h"], m["kv"], m["hd"]), run.peaks)[0]
    return 100.0 * need / spent if need > 0 else None
