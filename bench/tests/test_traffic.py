"""The traffic generator: the same work for every seed, data-driven mixes."""
import json

import numpy as np

from bench import traffic as TR

MIX = {"loop": "open", "rate_per_s": 3.0,
       "prompt_tokens": {"median": 768, "sigma": 0.6, "min": 128,
                         "max": 3072},
       "output_tokens": {"median": 32, "sigma": 0.8, "min": 8, "max": 256},
       "schedule_seed": 11,
       "ids": {"kind": "expert_topics", "topic_zipf": 1.0, "id_zipf": 1.0}}


def _topics(vocab=1000, e=8, seed=0):
    top1 = np.random.default_rng(seed).integers(0, e, vocab)
    return TR.topic_sets(top1, e)


def test_every_seed_gets_the_same_work():
    runs = [TR.Generator(MIX, s, 1000, _topics()).window(40.0)
            for s in (1, 2**31 + 7)]
    a, b = runs
    assert len(a) == len(b) == 120
    # the same sizes and arrivals in the same order; other ids
    for key in (lambda r: len(r.prompt), lambda r: r.max_new,
                lambda r: r.due):
        assert list(map(key, a)) == list(map(key, b))
    assert any((x.prompt != y.prompt).any() for x, y in zip(a, b))
    # inter-arrival gaps: n - 1 of the n exponential quantiles each
    u = (np.arange(120) + 0.5) / 120
    q = np.round(-np.log1p(-u) / 3.0, 9)
    for run in runs:
        gaps = np.round(np.diff([r.due for r in run]), 9)
        assert np.isin(gaps, q).all() and len(set(gaps)) == 119
    assert all(r.due < 40.0 for r in a)


def test_schedule_seed_orders_the_same_sizes():
    a = TR.Generator(MIX, 1, 1000, _topics()).window(40.0)
    b = TR.Generator(dict(MIX, schedule_seed=12), 1, 1000,
                     _topics()).window(40.0)
    for key in (lambda r: len(r.prompt), lambda r: r.max_new):
        assert sorted(map(key, a)) == sorted(map(key, b))
        assert list(map(key, a)) != list(map(key, b))   # another order


def test_same_seed_same_requests():
    a = TR.Generator(MIX, 5, 1000, _topics()).window(10.0)
    b = TR.Generator(MIX, 5, 1000, _topics()).window(10.0)
    assert all((x.prompt == y.prompt).all() and x.due == y.due
               for x, y in zip(a, b))


def test_topic_prompts_stay_in_one_topic():
    topics = _topics()
    sets = [set(t.tolist()) for t in topics]
    for r in TR.Generator(MIX, 3, 1000, topics).window(10.0):
        ids = set(r.prompt.tolist())
        assert any(ids <= s for s in sets)


def test_lengths_respect_the_clip_range():
    reqs = TR.Generator(MIX, 9, 1000, _topics()).window(40.0)
    assert min(len(r.prompt) for r in reqs) >= 128
    assert max(len(r.prompt) for r in reqs) <= 3072
    assert TR.max_len(MIX, 16) == 3328


def test_a_new_mix_is_a_file(tmp_path):
    (tmp_path / "traffic").mkdir()
    mix = dict(MIX, loop="closed", clients=4, pool_requests=12,
               ids={"kind": "uniform"})
    (tmp_path / "traffic" / "new-mix.json").write_text(json.dumps(mix))
    loaded = TR.load_mix(tmp_path, "new-mix")
    reqs = TR.Generator(loaded, 1, 500).window(10.0)
    assert len(reqs) == 12 and all(r.due == 0.0 for r in reqs)
    assert max(int(r.prompt.max()) for r in reqs) < 500
