"""The one traffic generator: reads a mix file (`bench/traffic/<mix>.json`)
and makes the requests of one run from its seed.

A mix states its loop (`open` at `rate_per_s`, or `closed` with
`clients`), lognormal prompt and output lengths (median, sigma, clip
range), the order of its sizes and arrivals (`schedule_seed`), and how
token ids are drawn. The lognormal lengths and Poisson
arrivals follow `repro.core.trace.generate_requests`.

Every seed gets the same work: lengths are the stratified quantiles of
their lognormal (request i of n at quantile (i + 0.5) / n) and
inter-arrival gaps those of the exponential, put in one order drawn from
the mix's `schedule_seed`, the same for every run. The order is part of
the work: it decides how prompts' prefill overlaps other requests'
decoding, and so how many token gaps span a prefill step. The run's seed
draws the ids (and the weights), never the sizes or arrivals.

Ids `expert_topics`: one topic per routed expert, the vocabulary ids
whose top-1 expert under the cell's own layer-0 router (applied to the
normed embedding) is that expert. A request takes a topic by Zipf
popularity (`topic_zipf`) and draws its ids Zipf(`id_zipf`) within it:
the domain skew that trained routers show and random routers lack.
Ids `uniform`: every id equally likely.
"""
from __future__ import annotations

import json
from dataclasses import dataclass
from pathlib import Path
from statistics import NormalDist

import numpy as np


@dataclass
class Req:
    rid: int
    due: float            # seconds after the window opens (open loop)
    prompt: np.ndarray    # int32 token ids
    max_new: int


def load_mix(bench_dir: Path, name: str) -> dict:
    return json.loads((Path(bench_dir) / "traffic" / f"{name}.json")
                      .read_text())


def max_len(mix: dict, block: int) -> int:
    """KV rows one request can need, rounded up to whole blocks."""
    n = mix["prompt_tokens"]["max"] + mix["output_tokens"]["max"]
    return -(-n // block) * block


def _lengths(spec: dict, n: int, rng) -> np.ndarray:
    u = (np.arange(n) + 0.5) / n
    z = np.array([NormalDist().inv_cdf(x) for x in u])
    v = np.exp(np.log(spec["median"]) + spec["sigma"] * z)
    v = np.clip(np.round(v), spec["min"], spec["max"]).astype(int)
    return rng.permutation(v)


def _zipf(n: int, s: float) -> np.ndarray:
    p = 1.0 / np.arange(1, n + 1) ** s
    return p / p.sum()


def _stratified_counts(p: np.ndarray, n: int) -> np.ndarray:
    """n items split over p by largest remainders (the same every seed)."""
    raw = p * n
    c = np.floor(raw).astype(int)
    c[np.argsort(-(raw - c))[:n - c.sum()]] += 1
    return c


def topic_sets(top1: np.ndarray, num_experts: int) -> list:
    """Ids grouped by their top-1 expert; experts no id prefers are left
    out."""
    sets = [np.flatnonzero(top1 == e).astype(np.int32)
            for e in range(num_experts)]
    return [s for s in sets if len(s)]


class Generator:
    """Requests of one mix and seed. `topics` is the list of id sets for
    `expert_topics` mixes (None for `uniform`)."""

    def __init__(self, mix: dict, seed: int, vocab: int, topics=None):
        self.mix = mix
        self.vocab = vocab
        self.topics = topics
        self.rng = np.random.default_rng(int(seed))
        ids = mix["ids"]
        if ids["kind"] == "expert_topics":
            if not topics:
                raise ValueError("expert_topics ids need topic sets")
            # rank r of the popularity list -> a topic, and within each
            # topic rank -> id, both in a seed-drawn order
            self.topic_order = self.rng.permutation(len(topics))
            self.id_order = [self.rng.permutation(t) for t in topics]
        elif ids["kind"] != "uniform":
            raise ValueError(f"unknown ids kind {ids['kind']!r}")

    def _prompts(self, lens: np.ndarray) -> list:
        ids = self.mix["ids"]
        if ids["kind"] == "uniform":
            return [self.rng.integers(0, self.vocab, n, dtype=np.int32)
                    for n in lens]
        counts = _stratified_counts(
            _zipf(len(self.topics), ids["topic_zipf"]), len(lens))
        ranks = self.rng.permutation(np.repeat(np.arange(len(counts)),
                                               counts))
        out = []
        for n, r in zip(lens, ranks):
            t = self.topic_order[r]
            pool = self.id_order[t]
            p = _zipf(len(pool), ids["id_zipf"])
            out.append(pool[self.rng.choice(len(pool), size=n, p=p)]
                       .astype(np.int32))
        return out

    def requests(self, n: int, rate: float | None = None,
                 max_new: int | None = None, rid0: int = 0) -> list:
        """n requests. With `rate`, due times are Poisson gaps at that rate
        starting at 0; otherwise every request is due at 0. Sizes and due
        times depend on n, `rate` and the mix alone."""
        order = np.random.default_rng(int(self.mix["schedule_seed"]))
        plens = _lengths(self.mix["prompt_tokens"], n, order)
        olens = _lengths(self.mix["output_tokens"], n, order)
        if max_new is not None:
            olens = np.minimum(olens, max_new)
        due = np.zeros(n)
        if rate:
            u = (np.arange(n) + 0.5) / n
            gaps = order.permutation(-np.log1p(-u) / rate)
            due[1:] = np.cumsum(gaps[:-1])
        prompts = self._prompts(plens)
        return [Req(rid0 + i, float(due[i]), prompts[i], int(olens[i]))
                for i in range(n)]

    def window(self, seconds: float, rid0: int = 0) -> list:
        """The run's requests: for an open loop, round(rate * seconds)
        requests due inside the window; for a closed loop, the pool the
        clients draw from in order."""
        m = self.mix
        if m["loop"] == "open":
            n = max(1, round(m["rate_per_s"] * seconds))
            reqs = self.requests(n, rate=m["rate_per_s"], rid0=rid0)
            return [r for r in reqs if r.due < seconds]
        if m["loop"] == "closed":
            return self.requests(m["pool_requests"], rid0=rid0)
        raise ValueError(f"unknown loop {m['loop']!r}")
