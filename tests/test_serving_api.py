"""Request-level serving API: submit/step/run/stream/cancel, per-slot
sampling (greedy == temperature-0 bit-identity, seeded determinism,
batch-composition independence), stop sequences, priority admission, and
the serve()-as-thin-driver parity with a manually-driven session."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core.balancer import make_balancer
from repro.core.control import ControlPlane, IterationOutcome
from repro.models import model as M
from repro.models import transformer as T
from repro.serving.engine import ServingEngine
from repro.serving.scheduler import (GenRequest, RequestMetrics,
                                     SamplingParams, percentile_summary)

KEY = jax.random.PRNGKey(23)


@pytest.fixture(scope="module")
def moe_setup():
    # ample capacity so no token is ever dropped — required for the
    # batched == sequential identities (capacity is shared batch-wide)
    cfg = get_config("mixtral-8x7b", smoke=True).with_(dtype="float32")
    cfg = cfg.with_(moe=cfg.moe.__class__(
        num_experts=cfg.moe.num_experts, top_k=cfg.moe.top_k,
        d_ff=cfg.moe.d_ff, capacity_factor=float(cfg.moe.num_experts)))
    params = M.init_params(cfg, KEY)
    return cfg, params


def _mk_requests(cfg, lens_news, arrivals, sampling=None, seed=5):
    rng = np.random.default_rng(seed)
    return [GenRequest(
        rid=i, arrival=float(a),
        prompt=rng.integers(0, cfg.vocab_size, size=pl, dtype=np.int32),
        max_new_tokens=nn,
        sampling=sampling[i] if isinstance(sampling, list)
        else (sampling or SamplingParams()))
        for i, ((pl, nn), a) in enumerate(zip(lens_news, arrivals))]


# ------------------------------------------------------- sampler unit


def test_sample_tokens_greedy_is_argmax():
    """temperature<=0 rows are bit-identical to jnp.argmax — the
    pre-redesign greedy decode path."""
    logits = jax.random.normal(KEY, (6, 40), jnp.float32)
    zeros = jnp.zeros(6, jnp.float32)
    toks = T.sample_tokens(logits, zeros, jnp.zeros(6, jnp.int32),
                           jnp.ones(6, jnp.float32),
                           jnp.arange(6, dtype=jnp.int32),
                           jnp.arange(6, dtype=jnp.int32))
    np.testing.assert_array_equal(np.asarray(toks),
                                  np.asarray(jnp.argmax(logits, axis=-1)))


def test_sample_tokens_topk1_is_argmax():
    """top_k=1 collapses any temperature to the argmax token."""
    logits = jax.random.normal(KEY, (4, 33), jnp.float32)
    toks = T.sample_tokens(logits, jnp.full(4, 2.5, jnp.float32),
                           jnp.ones(4, jnp.int32),
                           jnp.ones(4, jnp.float32),
                           jnp.arange(4, dtype=jnp.int32),
                           jnp.zeros(4, jnp.int32))
    np.testing.assert_array_equal(np.asarray(toks),
                                  np.asarray(jnp.argmax(logits, axis=-1)))


def test_sample_tokens_topk_respected_per_row():
    """Every sampled token lies in its OWN row's top-k set — k differs
    per slot inside the one jitted call."""
    logits = jax.random.normal(jax.random.fold_in(KEY, 1), (8, 50))
    ks = jnp.asarray([1, 2, 3, 4, 1, 2, 3, 4], jnp.int32)
    for trial in range(5):
        toks = np.asarray(T.sample_tokens(
            logits, jnp.full(8, 1.3, jnp.float32), ks,
            jnp.ones(8, jnp.float32), jnp.arange(8, dtype=jnp.int32),
            jnp.full(8, trial, jnp.int32)))
        top = np.argsort(-np.asarray(logits), axis=-1)
        for r in range(8):
            assert toks[r] in top[r, :int(ks[r])]


def test_sample_tokens_key_folds_per_step():
    """Same seed + same logits but different step counters give a
    different draw stream (keys are folded per generated token)."""
    logits = jnp.broadcast_to(
        jax.random.normal(jax.random.fold_in(KEY, 2), (1, 64)), (32, 64))
    ones = jnp.ones(32, jnp.float32)
    toks = np.asarray(T.sample_tokens(
        logits, ones, jnp.zeros(32, jnp.int32), ones,
        jnp.zeros(32, jnp.int32), jnp.arange(32, dtype=jnp.int32)))
    assert len(set(toks.tolist())) > 1


# ----------------------------------------- serve() as thin driver


def test_serve_parity_with_manual_step_loop(moe_setup):
    """serve(trace) must be a THIN driver: a manually-driven
    submit/step session reproduces its greedy tokens and TTFT/TPOT/E2E
    metrics exactly (modeled clock => bit-identical floats)."""
    cfg, params = moe_setup
    lens = [(5, 4), (7, 3), (4, 5)]
    arrivals = [0.0, 0.0, 1.0]

    engine = ServingEngine(cfg, params, max_len=32)
    cp = ControlPlane(cfg, "megatron-lm", num_devices=4)
    reqs = _mk_requests(cfg, lens, arrivals)
    res = engine.serve(reqs, num_slots=2, control=cp, time_scale=100.0)

    engine2 = ServingEngine(cfg, params, max_len=32)
    cp2 = ControlPlane(cfg, "megatron-lm", num_devices=4)
    engine2.start(num_slots=2, control=cp2, time_scale=100.0)
    reqs2 = _mk_requests(cfg, lens, arrivals)
    handles = [engine2.submit(r) for r in reqs2]
    events = []
    while not engine2._session.sched.done:
        events.extend(engine2.step())
    res2 = engine2.result()

    assert [h.status for h in handles] == ["finished"] * 3
    # token-for-token identical...
    got = {h.rid: h.tokens for h in handles}
    assert got == {q.rid: q.tokens for q in reqs}
    # ...and metric-for-metric identical (exact float equality: both
    # replays advance the same modeled clock)
    key = lambda r: r.rid                                      # noqa: E731
    for a, b in zip(sorted(res.records, key=key),
                    sorted(res2.records, key=key)):
        assert a == b, (a, b)
    assert res.iterations == res2.iterations
    assert res.prefills == res2.prefills
    # every generated token surfaced exactly once as a TokenEvent
    assert sorted((e.rid, e.token) for e in events) == sorted(
        (rid, t) for rid, toks in got.items() for t in toks)
    assert sum(e.done for e in events) == 3


def test_control_plane_outcome_consistency(moe_setup):
    """ControlPlane.step returns per-iteration outcomes whose cumulative
    latency/cost match the instance meters (simulator & engine consume
    the same numbers)."""
    cfg, params = moe_setup
    cp = ControlPlane(cfg, "eplb", num_devices=4)
    lm = cfg.num_layers // cfg.moe.every_n_layers
    rng = np.random.default_rng(0)
    outs = [cp.step(float(t), None,
                    rng.integers(0, 50, size=(lm, cfg.moe.num_experts)))
            for t in range(5)]
    assert all(isinstance(o, IterationOutcome) for o in outs)
    assert all(len(o.plans) == lm for o in outs)
    np.testing.assert_allclose(sum(o.latency_s for o in outs),
                               sum(cp.iter_latency))
    np.testing.assert_allclose(sum(o.cost for o in outs), cp.cost)
    assert cp.iterations == 5 and len(cp.layer_latency) == 5 * lm


# ------------------------------------------------------------ sampling


def test_temperature_zero_requests_match_greedy_serve(moe_setup):
    """A replay where every request carries SamplingParams(temperature=0)
    generates exactly the tokens of the pre-redesign greedy path (the
    legacy one-at-a-time prefill/decode API)."""
    cfg, params = moe_setup
    lens = [(5, 5), (8, 4)]
    reqs = _mk_requests(cfg, lens, [0.0, 0.0],
                        sampling=SamplingParams(temperature=0.0))

    engine = ServingEngine(cfg, params, max_len=32)
    want = []
    for req in reqs:
        tok, cache, clen = engine.prefill(
            {"tokens": jnp.asarray(req.prompt[None])})
        out, _, _ = engine.decode(tok, cache, clen, req.max_new_tokens - 1)
        want.append([int(tok[0])] + [int(x) for x in np.asarray(out[0])])

    engine2 = ServingEngine(cfg, params, max_len=32)
    engine2.serve(reqs, num_slots=2)
    assert [r.tokens for r in reqs] == want


def test_seeded_sampling_deterministic_across_runs(moe_setup):
    cfg, params = moe_setup
    lens = [(5, 6), (6, 6), (4, 6)]
    mk = lambda seed: _mk_requests(                            # noqa: E731
        cfg, lens, [0.0, 0.0, 0.5],
        sampling=SamplingParams(temperature=0.9, top_k=32, seed=seed))
    engine = ServingEngine(cfg, params, max_len=32)

    r1 = mk(7)
    engine.serve(r1, num_slots=2)
    r2 = mk(7)
    engine.serve(r2, num_slots=2)
    assert [q.tokens for q in r1] == [q.tokens for q in r2]

    r3 = mk(8)          # different seed -> different stream
    engine.serve(r3, num_slots=2)
    assert [q.tokens for q in r1] != [q.tokens for q in r3]


def test_sampled_batched_matches_sequential(moe_setup):
    """Sampling keys are folded per REQUEST (seed, token index), not per
    slot/batch — so continuous batching generates exactly the tokens of
    one-at-a-time decoding even at temperature > 0."""
    cfg, params = moe_setup
    lens = [(5, 5), (9, 4), (3, 6)]
    sp = [SamplingParams(temperature=0.8, top_k=16, seed=100 + i)
          for i in range(3)]

    seq = _mk_requests(cfg, lens, [0.0, 0.0, 0.0], sampling=sp)
    engine = ServingEngine(cfg, params, max_len=32)
    for q in seq:
        engine.serve([q], num_slots=1)

    bat = _mk_requests(cfg, lens, [0.0, 0.0, 1.0], sampling=sp)
    engine2 = ServingEngine(cfg, params, max_len=32)
    res = engine2.serve(bat, num_slots=2)
    assert res.mean_batch_occupancy > 1.0
    assert [q.tokens for q in bat] == [q.tokens for q in seq]


def test_sampled_replay_completes_under_all_strategies(moe_setup):
    """A temperature>0, seeded replay completes under all four balancer
    strategies (acceptance criterion)."""
    cfg, params = moe_setup
    lens = [(5, 3), (6, 3)]
    for strategy in ("megatron-lm", "eplb", "oracle", "moeless"):
        engine = ServingEngine(cfg, params, max_len=32)
        cp = ControlPlane(cfg, strategy, num_devices=4)
        reqs = _mk_requests(
            cfg, lens, [0.0, 0.0],
            sampling=SamplingParams(temperature=1.0, top_p=0.9, seed=3))
        res = engine.serve(reqs, num_slots=2, control=cp)
        assert len(res.records) == 2
        assert all(r.out_tokens == 3 for r in res.records)
        assert cp.iterations == res.iterations + res.prefills
        assert cp.cost > 0


# ------------------------------------------------- cancel / stop / stream


def test_cancel_mid_decode_frees_slot_for_pending(moe_setup):
    """cancel() on a mid-decode request recycles its KV slot — the next
    pending arrival is admitted on the following step."""
    cfg, params = moe_setup
    engine = ServingEngine(cfg, params, max_len=32)
    engine.start(num_slots=1)
    a, b = _mk_requests(cfg, [(5, 20), (6, 4)], [0.0, 0.0])
    ha, hb = engine.submit(a), engine.submit(b)
    engine.step()
    engine.step()
    assert ha.status == "running" and hb.status == "queued"
    assert 1 < len(ha.tokens) < 20
    assert engine.cancel(ha)
    assert ha.status == "cancelled"
    assert engine._session.kv.num_free == 1
    engine.step()                       # admits b into the freed slot
    assert hb.status == "running" and b.slot == a.slot
    res = engine.run()
    assert hb.status == "finished" and len(hb.tokens) == 4
    assert res.cancelled == 1
    # cancelled requests never pollute the latency records
    assert [r.rid for r in res.records] == [b.rid]
    # cancelling twice (or after finish) is a no-op
    assert not engine.cancel(ha)
    assert not engine.cancel(hb)


def test_stop_sequence_terminates(moe_setup):
    """Generation ends as soon as the output's tail matches a stop-token
    sequence; the budget would have allowed more."""
    cfg, params = moe_setup
    probe = _mk_requests(cfg, [(5, 8)], [0.0])
    engine = ServingEngine(cfg, params, max_len=32)
    engine.serve(probe, num_slots=1)
    full = probe[0].tokens
    assert len(full) == 8

    stop = tuple(full[2:4])             # 2-token stop seq from the stream
    req = _mk_requests(cfg, [(5, 8)], [0.0],
                       sampling=SamplingParams(stop=(stop,)))[0]
    engine.serve([req], num_slots=1)
    assert req.finish_reason == "stop"
    assert req.tokens == full[:4]       # stop tokens kept, then cut


def test_stream_yields_incremental_tokens(moe_setup):
    cfg, params = moe_setup
    engine = ServingEngine(cfg, params, max_len=32)
    engine.start(num_slots=2)
    a, b = _mk_requests(cfg, [(5, 6), (6, 4)], [0.0, 0.0])
    ha, hb = engine.submit(a), engine.submit(b)
    got = list(engine.stream(ha))
    assert got == ha.tokens and len(got) == 6
    assert ha.status == "finished"
    # the co-batched request progressed while we streamed
    assert len(hb.tokens) >= 4 - 1
    engine.run()
    assert hb.status == "finished"


def test_priority_admission(moe_setup):
    """Among arrived requests, higher priority wins the free slot; FCFS
    within a priority level."""
    cfg, params = moe_setup
    sp = [SamplingParams(priority=0), SamplingParams(priority=0),
          SamplingParams(priority=5)]
    reqs = _mk_requests(cfg, [(4, 3)] * 3, [0.0] * 3, sampling=sp)
    engine = ServingEngine(cfg, params, max_len=32)
    engine.serve(reqs, num_slots=1)
    order = sorted(reqs, key=lambda r: r.t_admitted)
    assert [r.rid for r in order] == [2, 0, 1]


def test_submit_nan_arrival_means_now(moe_setup):
    cfg, params = moe_setup
    engine = ServingEngine(cfg, params, max_len=32)
    engine.start(num_slots=1)
    req = _mk_requests(cfg, [(4, 2)], [float("nan")])[0]
    h = engine.submit(req)
    res = engine.run()
    assert h.status == "finished" and req.arrival == 0.0
    assert len(res.records) == 1


@pytest.mark.parametrize("poison", [False, True])
def test_nonfinite_logits_are_counted(moe_setup, poison):
    """The batched steps count NaN/Inf among the logits they sample
    from, so a broken model surfaces in the result instead of as a
    plausible argmax token."""
    cfg, params = moe_setup
    if poison:
        params = dict(params, final_norm=jax.tree.map(
            lambda a: a * jnp.nan, params["final_norm"]))
    engine = ServingEngine(cfg, params, max_len=32)
    engine.start(num_slots=2)
    for req in _mk_requests(cfg, [(4, 3), (5, 3)], [0.0, 0.0]):
        engine.submit(req)
    res = engine.run()
    assert res.generated_tokens == 6
    if poison:
        assert res.nonfinite_logits > 0
    else:
        assert res.nonfinite_logits == 0


def test_oversized_request_rejected_handle(moe_setup):
    cfg, params = moe_setup
    engine = ServingEngine(cfg, params, max_len=16)
    engine.start(num_slots=1)
    h = engine.submit(_mk_requests(cfg, [(14, 8)], [0.0])[0])
    assert h.status == "rejected"
    assert list(engine.stream(h)) == []
    assert engine.result().rejected == 1


# --------------------------------------------------- satellites


def test_make_balancer_rejects_unknown_kwargs():
    for kind in ("megatron-lm", "eplb", "oracle", "moeless"):
        with pytest.raises(TypeError, match=kind):
            make_balancer(kind, num_experts=4, num_devices=2,
                          bogus_knob=1)
    with pytest.raises(TypeError, match="megatron-lm"):
        make_balancer("megatron-lm", num_experts=4, num_devices=2,
                      cv_threshold=0.2)    # moeless-only knob
    with pytest.raises(KeyError):
        make_balancer("no-such-strategy", num_experts=4, num_devices=2)
    # the valid spellings still construct
    make_balancer("eplb", num_experts=4, num_devices=2, period=10.0)
    make_balancer("moeless", num_experts=4, num_devices=2,
                  expert_bytes=1.0, cv_threshold=0.3)


def test_percentile_summary_excludes_single_token_tpot():
    mk = lambda rid, out, tpot: RequestMetrics(       # noqa: E731
        rid=rid, arrival=0.0, in_tokens=4, out_tokens=out,
        ttft=0.5, tpot=tpot, e2e=1.0)
    recs = [mk(0, 10, 0.2), mk(1, 1, 0.0), mk(2, 1, 0.0)]
    s = percentile_summary(recs)
    # single-token requests would have dragged mean TPOT to 0.067
    assert s["tpot"]["mean"] == pytest.approx(0.2)
    assert s["tpot"]["p50"] == pytest.approx(0.2)
    # ...but still count toward TTFT / E2E
    assert s["ttft"]["mean"] == pytest.approx(0.5)
    assert s["e2e"]["mean"] == pytest.approx(1.0)
    # all-single-token: TPOT block stays zeroed, no crash
    s2 = percentile_summary([mk(0, 1, 0.0)])
    assert s2["tpot"]["mean"] == 0.0 and s2["ttft"]["mean"] == 0.5


def test_sampling_params_validation():
    with pytest.raises(ValueError):
        SamplingParams(stop=((),))
    with pytest.raises(ValueError, match="top_p"):
        SamplingParams(temperature=1.0, top_p=0.0)   # empty nucleus
    sp = SamplingParams(stop=([1, 2], [3]))
    assert sp.stop == ((1, 2), (3,))
    assert sp.effective_seed(9) == 9
    assert SamplingParams(seed=4).effective_seed(9) == 4


def test_cancel_pending_with_duplicate_identity(moe_setup):
    """Cancelling a queued request must remove THAT request object even
    when another pending request compares equal field-wise (list.remove
    would trip on numpy-array __eq__ or drop the wrong one)."""
    cfg, params = moe_setup
    engine = ServingEngine(cfg, params, max_len=32)
    engine.start(num_slots=1)
    prompt = np.zeros(3, np.int32)
    blocker = GenRequest(rid=9, arrival=0.0, prompt=prompt + 1,
                         max_new_tokens=6)
    a = GenRequest(rid=0, arrival=0.0, prompt=prompt, max_new_tokens=2)
    b = GenRequest(rid=0, arrival=0.0, prompt=prompt, max_new_tokens=2)
    engine.submit(blocker)
    ha, hb = engine.submit(a), engine.submit(b)
    engine.step()                       # blocker occupies the only slot
    assert ha.status == "queued" and hb.status == "queued"
    assert engine.cancel(hb)            # equal-looking twin stays queued
    assert hb.status == "cancelled" and ha.status == "queued"
    engine.run()
    assert ha.status == "finished" and len(a.tokens) == 2
    assert not b.tokens


def test_cancel_and_result_on_closed_engine(moe_setup):
    cfg, params = moe_setup
    engine = ServingEngine(cfg, params, max_len=32)
    engine.start(num_slots=1)
    h = engine.submit(_mk_requests(cfg, [(4, 2)], [0.0])[0])
    engine.run()
    engine.close()
    assert not engine.cancel(h)         # no session: no-op, no KV alloc
    assert engine._session is None
    with pytest.raises(RuntimeError, match="session"):
        engine.result()
