"""How close the reference's router comes to a tie at the served
positions of a configuration, and how far a bfloat16 router moves the
tokens it puts first: the reading that sets a configuration's
`router_margin`, from the plain reference alone.

    python3 bench/router_margin.py --config <file> --mix <mix> --seed <n>

The first `--requests` requests of the mix (its own sizes, ids from the
seed; expert-affine topics from the configuration's router) are
decoded greedily through the float32 reference, which stands in for the
served tokens. With `--decode 0` nothing is decoded: each request's
own prompt ids, repeated to its output length, stand in for the served
tokens (the same positions and lengths; the gaps of those tokens are
then not zero and `own_gap_max` is null). Over those served positions
it prints one JSON line:

- `clear_share`: the share of positions whose router margin
  (`bench.reference`) is at least each of the widths 0, 0.01, 0.03
  and 0.1 (logits);
- `witness`: the gap, under the float32 reference, of the token that
  the reference with a bfloat16 router (`router="bf16"`) puts first,
  mean over all positions and over the clear ones at each width, the
  widest, and the share of positions where it puts another token first;
- `seconds`: the weight maker, the greedy decode, one float32 pass over
  every request as the harness's check makes it, and the witness's
  pass; and `memory_peak_bytes`, the device's peak, also as it stood
  once the weights were made (`weights_peak_bytes`).
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

import numpy as np

WIDTHS = (0.0, 0.01, 0.03, 0.1)


def readings(c: dict, mix: dict, seed: int, requests: int,
             widths=WIDTHS, decode: bool = True) -> dict:
    import jax
    import jax.numpy as jnp

    from bench import traffic as TR
    from bench.harness import device_peak
    from bench.reference import Reference, served_gaps, token_top1
    from bench.weights import experts_of
    tw = time.perf_counter()
    ref = Reference(c, seed)
    jax.block_until_ready(ref.w)
    made = time.perf_counter() - tw
    weights_peak = device_peak(jax.local_devices()[:1])
    topics = None
    if mix["ids"]["kind"] == "expert_topics":
        topics = TR.topic_sets(token_top1(c, ref.w), experts_of(c)[0])
    reqs = TR.Generator(mix, seed, c["vocab_size"], topics).requests(requests)
    t0 = time.perf_counter()
    served = []
    for r in reqs:
        if not decode:
            served.append(r.prompt[np.arange(r.max_new) % len(r.prompt)])
            continue
        seq = list(r.prompt)
        for _ in range(r.max_new):
            seq.append(int(jnp.argmax(ref.logits(seq)[-1])))
        served.append(seq[len(r.prompt):])
    t1 = time.perf_counter()
    checked = [served_gaps(ref, r.prompt, s) for r, s in zip(reqs, served)]
    t2 = time.perf_counter()
    witness = [served_gaps(ref, r.prompt, s, router="bf16")[0]
               for r, s in zip(reqs, served)]
    t3 = time.perf_counter()
    margins = np.concatenate([m for _, m in checked])
    gaps = np.concatenate(witness)
    own = np.concatenate([g for g, _ in checked])
    clear = {w: margins >= w for w in widths}
    return {
        "requests": len(reqs),
        "prompt_tokens": [len(r.prompt) for r in reqs],
        "served_tokens": int(margins.size),
        "longest": max(len(r.prompt) + r.max_new for r in reqs),
        "own_gap_max": float(own.max()) if decode else None,
        "clear_share": {str(w): float(k.mean()) for w, k in clear.items()},
        "witness": {
            "mean_gap": {str(w): float(gaps[k].mean()) if k.any() else None
                         for w, k in clear.items()},
            "max_gap": float(gaps.max()),
            "flipped_share": float((gaps > 0).mean())},
        "margin_quantiles": dict(zip(
            ("p01", "p05", "p10", "p50"),
            map(float, np.quantile(margins, [0.01, 0.05, 0.1, 0.5])))),
        "weights_peak_bytes": weights_peak,
        "seconds": {"weights": made, "decode": t1 - t0, "check": t2 - t1,
                    "witness": t3 - t2},
    }


if __name__ == "__main__":
    root = Path(__file__).resolve().parents[1]
    sys.path[:0] = [str(root), str(root / "src")]
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--mix", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--decode", type=int, choices=(0, 1), default=1)
    a = ap.parse_args()
    from bench.harness import NoChip, device_peak, setup_jax
    from bench.traffic import load_mix
    try:
        devices = setup_jax(True, 1)
    except NoChip as e:
        sys.exit(f"router_margin: {e}")
    c = json.loads(Path(a.config).read_text())
    out = readings(c, load_mix(root / "bench", a.mix), a.seed, a.requests,
                   decode=bool(a.decode))
    out.update(config=a.config, seed=a.seed,
               device=devices[0].device_kind,
               memory_peak_bytes=device_peak(devices))
    print(json.dumps(out), flush=True)
