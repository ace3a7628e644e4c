"""Serving engine: a request-level API over continuous-batch
prefill/decode on the real JAX model, with the MoEless control plane
attached.

The serving surface (paper §3.2 workflow, grown to a client-facing API):

    engine.start(num_slots=8, control=..., time_scale=...)
    h = engine.submit(GenRequest(..., sampling=SamplingParams(...)))
    engine.step()            # one admission+decode iteration
    engine.run()             # drive until idle -> ServeResult
    for tok in engine.stream(h): ...   # incremental tokens
    engine.cancel(h)         # mid-decode: the KV slot is recycled
                             # for the next pending arrival
    engine.serve(requests)   # trace replay = thin driver over the above

Request serving is continuous batching over a fixed slot pool
(repro.serving.kv): requests are prefilled alone, spliced into a free KV
slot, decoded together in ONE jitted step at static shapes with per-slot
cache lengths, and leave on EOS / stop sequence / token budget /
cancellation, freeing the slot for the next arrival. Sampling is ONE
jitted call over all slots with per-request RNG keys folded per
generated token (``models.transformer.sample_tokens``) — greedy is the
``temperature=0`` special case and is bit-identical to argmax decoding.

Every iteration drives the single control-plane implementation
(``repro.core.control.ControlPlane.step``): the Expert Load Predictor
estimates next-iteration per-layer loads from this iteration's gate
inputs (one jitted call, ONE device->host sync), the Scaler (Alg. 1)
sizes replicas, the Placer (Alg. 2) assigns them to EP ranks with
warm-start reuse, and the modeled iteration latency advances the serving
clock that TTFT / TPOT / E2E are recorded against.
"""
from __future__ import annotations

import dataclasses
import math
import threading
import time
from dataclasses import dataclass
from functools import partial
from typing import Callable, Iterator, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.control import (ControlPlane,  # noqa: F401 (re-export)
                                IterationOutcome, MoElessController)
from repro.launch.mesh import make_serving_mesh
from repro.models import transformer as T
from repro.obs.telemetry import NOOP
from repro.serving.kv import PagedKVCache, SlotKVCache
from repro.serving.scheduler import (ContinuousBatchingScheduler, GenRequest,
                                     RequestMetrics, SamplingParams,
                                     percentile_summary)


@jax.jit
def _count_nonfinite(logits):
    return jnp.sum(~jnp.isfinite(logits))


def _named(fn: partial, name: str) -> partial:
    """`fn` under a fixed program name: jitted, XLA names its module
    ``jit_<name>``, the handle a profiler trace gives its executions."""
    fn.__name__ = name
    return fn


class TokenEvent(NamedTuple):
    """One generated token, as surfaced by ``ServingEngine.step``."""
    rid: int
    token: int
    done: bool


@dataclass
class RequestHandle:
    """Client-side view of one submitted request."""
    req: GenRequest
    _engine: "ServingEngine"
    _rejected: bool = False

    @property
    def rid(self) -> int:
        return self.req.rid

    @property
    def tokens(self) -> list[int]:
        return self.req.tokens

    @property
    def status(self) -> str:
        """queued | running | finished | cancelled | rejected"""
        if self._rejected:
            return "rejected"
        if self.req.finish_reason in ("cancelled", "replica_failed"):
            return "cancelled"
        if self.req.finish_reason:
            return "finished"
        sess = self._engine._session
        if sess is not None and self.req.slot >= 0 \
                and sess.sched.running.get(self.req.slot) is self.req:
            return "running"
        return "queued"

    @property
    def finish_reason(self) -> str:
        return self.req.finish_reason

    def metrics(self) -> RequestMetrics:
        return RequestMetrics.of(self.req)


@dataclass
class ServeResult:
    """Outcome of one continuous-batching serving session."""
    records: list[RequestMetrics]
    iterations: int
    prefills: int
    rejected: int
    cancelled: int
    mean_batch_occupancy: float
    wall_s: float
    control: ControlPlane | None = None
    runtime: object | None = None     # ExpertRuntime when enabled
    clock_s: float = 0.0              # final serving-clock time
    dropped_tokens: float = 0.0       # MoE capacity drops (all phases)
    # NaN/Inf entries among the logits the batched steps sampled from
    nonfinite_logits: int = 0

    def summary(self) -> dict:
        return percentile_summary(self.records)

    @property
    def generated_tokens(self) -> int:
        return sum(r.out_tokens for r in self.records)


class _Session:
    """Mutable state of one serving session: the slot pool, the
    scheduler, the serving clock, and the per-slot sampling arrays that
    feed the one jitted ``sample_tokens`` call."""

    def __init__(self, cfg, params, num_slots: int, max_len: int,
                 eos_id, control, time_scale: float, runtime=None,
                 batch_mult: int = 1, serving=None):
        spec = serving if serving is not None else cfg.serving
        if spec.kv == "paged":
            self.kv = PagedKVCache(cfg, params, num_slots, max_len,
                                   block=spec.kv_block,
                                   num_blocks=spec.kv_blocks,
                                   batch_multiple=batch_mult,
                                   prefix_cache=spec.prefix_cache,
                                   chunked=spec.prefill_chunk > 0)
        else:
            self.kv = SlotKVCache(cfg, params, num_slots, max_len,
                                  batch_multiple=batch_mult)
        rows = self.kv.rows   # num_slots padded to the EP shard multiple
        self.batch_mult = batch_mult
        self.sched = ContinuousBatchingScheduler(self.kv, eos_id=eos_id)
        self.control = control
        self.runtime = runtime
        self.time_scale = time_scale
        self.now = 0.0
        self.cur = np.zeros(rows, np.int32)            # last token per slot
        self.temp = np.zeros(rows, np.float32)
        self.topk = np.zeros(rows, np.int32)
        self.topp = np.ones(rows, np.float32)
        self.seed = np.zeros(rows, np.int32)
        self.count = np.zeros(rows, np.int32)          # tokens sampled
        # chunked prefill: per-slot prompt (fed chunk-by-chunk into the
        # batched step) and its length; a slot is mid-prefill while
        # kv.lengths[slot] < plen[slot]
        self.plen = np.zeros(rows, np.int32)
        self.prompts: dict[int, np.ndarray] = {}
        self.cow_seen = 0              # kv.cow_blocks already counted
        self.nonfinite = 0             # non-finite sampled-from logits
        self.occupancy: list[int] = []
        self.iters = 0
        self.prefills = 0
        self.wall0 = time.perf_counter()

    def bind_slot(self, slot: int, req: GenRequest) -> None:
        s = req.sampling
        self.temp[slot] = s.temperature
        self.topk[slot] = s.top_k
        self.topp[slot] = s.top_p
        self.seed[slot] = s.effective_seed(req.rid)
        self.count[slot] = 0


class ServingEngine:
    """Prefill + decode with KV caches behind a request-level API;
    optionally drives a MoEless controller each iteration.

    ``expert_runtime="on"`` attaches a ``serving.expert_runtime.
    ExpertRuntime`` to every session: the control plane's replica plans
    are EXECUTED — applied as slot diffs to device-resident expert
    weight banks — and BOTH phases' MoE layers (each admission's
    prefill and the batched decode) run through the EP slot data plane
    (``distributed.ep.moe_ep_layer``) with the runtime's live
    tables/weights, so the predictor is fed by one routing semantics
    end to end. The EP path shares the capacity dispatch's
    capacity/drop semantics (one ``cfg.moe.capacity_factor``, same
    metrics, same kept tokens — drops are counted, never silent).
    Requires a session ``control`` plane (the plan source)."""

    def __init__(self, cfg, params, *, max_len: int = 512,
                 controller: ControlPlane | None = None,
                 window: int = 0, impl: str | None = None,
                 expert_runtime: str = "off", mesh=None,
                 telemetry=None, name: str = "engine", serving=None):
        if impl is not None:   # override the config's kernel backend
            from repro.kernels.ops import resolve_impl
            resolve_impl(impl)   # validate eagerly, not at first step
            cfg = cfg.with_(impl=impl)
        if expert_runtime not in ("off", "on"):
            raise ValueError(f"expert_runtime={expert_runtime!r} "
                             "(expected 'off' or 'on')")
        if expert_runtime == "on" and not cfg.is_moe:
            raise ValueError("expert_runtime='on' needs an MoE model")
        # `serving` (a configs.ServingSpec) overrides cfg.serving —
        # validate the knob dependency chain eagerly, not at first step
        spec = serving if serving is not None else cfg.serving
        if spec.kv not in ("contiguous", "paged"):
            raise ValueError(f"serving.kv={spec.kv!r} "
                             "(expected 'contiguous' or 'paged')")
        if spec.kv != "paged" and (spec.prefill_chunk > 0
                                   or spec.prefix_cache):
            raise ValueError("prefill_chunk / prefix_cache require "
                             "serving.kv='paged'")
        if spec.prefix_cache and spec.prefill_chunk <= 0:
            raise ValueError(
                "prefix_cache requires prefill_chunk > 0 — the solo "
                "splice path always recomputes the whole prompt, so a "
                "prefix hit could never skip work")
        if spec.kv == "paged" and (cfg.encdec is not None or any(
                sub.mixer != "attn" for sub in T.layer_pattern(cfg))):
            raise ValueError("serving.kv='paged' needs an attention-only "
                             "decoder (no SSM state, no enc-dec)")
        self.serving = spec
        self.cfg, self.params = cfg, params
        self.max_len = max_len
        self.controller = controller
        # telemetry is observation-only: it never touches the serving
        # clock or routing, so an instrumented run generates the same
        # tokens/metrics as a NOOP one. `name` prefixes this engine's
        # trace tracks (per-replica / per-strategy lanes).
        self.telemetry = NOOP if telemetry is None else telemetry
        self.name = name
        # rid -> host time of the request's last lifecycle boundary
        # (submit, admission, first token): where its next span starts
        self._marks: dict[int, float] = {}
        self.window = window
        self.expert_runtime = expert_runtime
        self._steps: dict[bool, callable] = {}
        self._ep_steps: dict = {}
        # `mesh` is the (data, ep, tp) serving mesh the EP slot data
        # plane runs on (launch.mesh.make_serving_mesh); None keeps the
        # 1-device mesh. Batches are padded to a multiple of data*ep so
        # the shard_map'd dispatch always divides evenly.
        if mesh is not None and tuple(mesh.axis_names) != \
                ("data", "ep", "tp"):
            raise ValueError(
                f"serving mesh must have axes ('data', 'ep', 'tp'), got "
                f"{tuple(mesh.axis_names)} — use "
                "launch.mesh.make_serving_mesh")
        if mesh is not None:
            # non-expert weights are replicated over the serving mesh
            # (the EP layer's router is P()); placing them once keeps
            # every jitted step from re-transferring them per call
            from jax.sharding import NamedSharding, PartitionSpec
            self.params = jax.device_put(
                params, NamedSharding(mesh, PartitionSpec()))
        self._ep_mesh = mesh
        self._collect = controller is not None and cfg.is_moe
        self._step = self._get_step(self._collect)
        # right-padded prefill is exact only when no sublayer carries
        # recurrent state (pad tokens would advance SSM states)
        self._pad_prefill = (cfg.encdec is None and all(
            sub.mixer == "attn" for sub in T.layer_pattern(cfg)))
        self.iteration = 0
        self._session: _Session | None = None
        # the gateway's async driver submits/cancels from the event-loop
        # thread while a background thread drives the step loop; the
        # RLock makes the session-mutating surface (submit / cancel /
        # step / start / close) safe to share across threads
        self._lock = threading.RLock()
        self._step_hooks: list[Callable] = []

    def _get_step(self, collect: bool):
        if collect not in self._steps:
            self._steps[collect] = jax.jit(_named(partial(
                T.decode_step, self.cfg, window=self.window,
                collect=collect), "engine_step"))
        return self._steps[collect]

    def _get_ep_step(self, collect: bool, ctx):
        """Jitted decode step with MoE sublayers routed through the EP
        slot data plane. `ctx` (static) is closed over; only the slot
        tables/weights are traced, so plan changes never recompile."""
        key = (collect, ctx)
        if key not in self._ep_steps:
            self._ep_steps[key] = jax.jit(_named(partial(
                T.decode_step, self.cfg, window=self.window,
                collect=collect, ep_ctx=ctx), "engine_step"))
        return self._ep_steps[key]

    def new_cache(self, batch_size: int):
        return T.init_cache(self.cfg, self.params, batch_size, self.max_len)

    # ------------------------------------------------------ legacy batch API

    def prefill(self, batch):
        """batch['tokens']: (B, S_prompt). Returns (next_tokens, cache)."""
        bsz = batch["tokens"].shape[0]
        cache = self.new_cache(bsz)
        logits, cache, metrics = self._step(
            self.params, batch, cache, jnp.asarray(0, jnp.int32))
        self._drive_controller(metrics)
        next_tok = jnp.argmax(logits[:, -1], axis=-1)
        return next_tok, cache, batch["tokens"].shape[1]

    def decode(self, tokens, cache, cache_len: int, steps: int,
               extra=None):
        """Greedy decode `steps` tokens. Returns (tokens (B, steps), cache)."""
        out = []
        cur = tokens
        for _ in range(steps):
            batch = {"tokens": cur[:, None]}
            if extra:
                batch.update(extra)
            logits, cache, metrics = self._step(
                self.params, batch, cache, jnp.asarray(cache_len, jnp.int32))
            self._drive_controller(metrics)
            cur = jnp.argmax(logits[:, -1], axis=-1)
            out.append(cur)
            cache_len += 1
            self.iteration += 1
        return jnp.stack(out, axis=1), cache, cache_len

    def _drive_controller(self, metrics, token_mask=None):
        if self.controller is None or "expert_load" not in metrics:
            return
        self.controller.step(
            float(self.iteration), self._gate_inputs(metrics),
            metrics["expert_load"], token_mask=token_mask)

    # ------------------------------------------------------------ prefill

    def prefill_request(self, prompt, collect: bool | None = None,
                        sampling: SamplingParams | None = None,
                        rid: int = 0):
        """Prefill ONE request (B=1) into a fresh cache. Attention-only
        models are right-padded to a power-of-two bucket (bounds jit
        recompilations; pad tokens sit after the prompt so causal
        attention never sees them and the masked metrics ignore them —
        pad rows DO occupy MoE capacity, identically on both data
        planes); recurrent models run at exact length. With a session
        expert runtime attached, the prefill's MoE sublayers execute
        through the EP slot data plane with the runtime's live
        tables/weights — the same path the batched decode takes — so
        prefill loads, drops, and routing feed the control plane under
        ONE semantics. The first output token is sampled under
        `sampling` (argmax when None / temperature<=0).
        Returns (first_token, cache, prompt_len, metrics, token_mask)."""
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        plen = int(prompt.shape[0])
        assert 0 < plen <= self.max_len
        toks = prompt
        if self._pad_prefill:
            bucket = min(self.max_len, max(8, 1 << (plen - 1).bit_length()))
            if bucket > plen:
                toks = np.pad(prompt, (0, bucket - plen))
        mask = (np.arange(toks.shape[0]) < plen)
        collect = self._collect if collect is None else collect
        runtime = self._session.runtime if self._session is not None \
            else None
        # the EP data plane shards the batch over data*ep ranks: pad the
        # single request to that multiple with all-masked zero rows (the
        # padded rows carry no active tokens, so metrics, drops, and the
        # request's own logits are unchanged — only row 0 is spliced
        # into the pool)
        bmult = 1
        if runtime is not None:
            m = runtime.ctx.mesh
            bmult = m.shape["data"] * m.shape["ep"]
        toks_b = np.zeros((bmult, toks.shape[0]), np.int32)
        toks_b[0] = toks
        mask_b = np.zeros((bmult, mask.shape[0]), bool)
        mask_b[0] = mask
        cache = self.new_cache(bmult)
        batch = {"tokens": jnp.asarray(toks_b),
                 "token_mask": jnp.asarray(mask_b)}
        if runtime is not None:
            # EP prefill: same jitted decode_step family as the batched
            # decode, MoE sublayers on the slot data plane (prefill
            # shapes compile their own cache entries; plan changes
            # re-program the traced tables without recompiling). The
            # bmult-1 all-zero pad rows are capacity-neutral
            # (ctx.pad_rows), so keep/drop matches the 1-row prefill
            step = self._get_ep_step(collect, dataclasses.replace(
                runtime.ctx, pad_rows=bmult - 1))
            logits, cache, metrics = step(
                self.params, batch, cache, jnp.asarray(0, jnp.int32),
                runtime.ep_state())
        else:
            step = self._get_step(collect)
            logits, cache, metrics = step(
                self.params, batch, cache, jnp.asarray(0, jnp.int32))
        s = sampling or SamplingParams()
        if s.temperature <= 0:        # greedy: the pre-redesign argmax path
            first_tok = int(jnp.argmax(logits[0, plen - 1]))
        else:
            first_tok = int(T.sample_tokens(
                logits[:1, plen - 1],
                jnp.full(1, s.temperature, jnp.float32),
                jnp.full(1, s.top_k, jnp.int32),
                jnp.full(1, s.top_p, jnp.float32),
                jnp.full(1, s.effective_seed(rid), jnp.int32),
                jnp.zeros(1, jnp.int32))[0])
        return first_tok, cache, plen, metrics, \
            jnp.asarray(mask_b.reshape(-1))

    # ------------------------------------------------- request-level API

    def start(self, *, num_slots: int = 8, eos_id=None,
              control: ControlPlane | None = None,
              time_scale: float = 1.0) -> None:
        """Open a serving session (slot pool + scheduler + clock). The
        serving clock starts at t=0 and advances by the modeled iteration
        latency when a `control` plane is attached (so TTFT / TPOT / E2E
        reflect the balancer under test), else by measured wall time.
        `time_scale` multiplies the clock advance — smoke models' modeled
        service times are orders of magnitude faster than real-trace
        arrival gaps, so scaling restores a production-like
        arrival/service ratio (and with it, actual batch concurrency)."""
        if self.cfg.encdec is not None:
            raise NotImplementedError(
                "continuous batching needs per-slot cache lengths, which "
                "encoder-decoder decode does not support (scalar-only "
                "positional offsets) — use the fixed-batch prefill/decode "
                "API for enc-dec models")
        runtime = None
        batch_mult = 1
        if self.expert_runtime == "on":
            if control is None:
                raise ValueError(
                    "expert_runtime='on' needs a session control plane — "
                    "the runtime executes ITS replica plans")
            from repro.serving.expert_runtime import ExpertRuntime
            if self._ep_mesh is None:
                self._ep_mesh = make_serving_mesh(1, ep=1)
            runtime = ExpertRuntime.for_control(
                self.cfg, self.params, control, mesh=self._ep_mesh,
                telemetry=self.telemetry,
                track=f"{self.name}/runtime")
            runtime.bootstrap(control)
            batch_mult = (self._ep_mesh.shape["data"]
                          * self._ep_mesh.shape["ep"])
        with self._lock:
            self._session = _Session(self.cfg, self.params, num_slots,
                                     self.max_len, eos_id, control,
                                     time_scale, runtime=runtime,
                                     batch_mult=batch_mult,
                                     serving=self.serving)

    def close(self) -> None:
        with self._lock:
            self._session = None
            self._marks.clear()

    @property
    def _sess(self) -> _Session:
        if self._session is None:
            self.start()
        return self._session

    @property
    def has_work(self) -> bool:
        """True while the open session has pending or running requests —
        what a background step-loop thread polls between wakeups."""
        sess = self._session
        return sess is not None and not sess.sched.done

    # ------------------------------------------------- step-loop hooks

    def add_step_hook(self, fn: Callable) -> None:
        """Register ``fn(events: list[TokenEvent])`` to run after every
        ``step`` (still under the engine lock) — the gateway driver fans
        these out to per-request asyncio queues."""
        self._step_hooks.append(fn)

    def remove_step_hook(self, fn: Callable) -> None:
        self._step_hooks.remove(fn)

    def submit(self, req: GenRequest) -> RequestHandle:
        """Enqueue one request into the running session (opened with
        defaults if needed). A NaN arrival means "now" (live submission);
        trace replays carry their own arrival times. Returns a handle
        whose status is `rejected` if the request cannot ever fit a KV
        slot (admission control). Thread-safe."""
        with self._lock:
            sess = self._sess
            if math.isnan(req.arrival):
                req.arrival = sess.now
            ok = sess.sched.submit(req)
            tel = self.telemetry
            if tel.enabled:
                if ok:
                    self._marks[req.rid] = time.perf_counter()
                    tel.sched_pending.set(len(sess.sched.pending))
                else:
                    tel.sched_rejected.labels(reason="capacity").inc()
            return RequestHandle(req, self, _rejected=not ok)

    def cancel(self, handle: RequestHandle) -> bool:
        """Cancel a queued or mid-decode request. A running request's KV
        slot is recycled immediately — the next pending arrival can be
        admitted on the very next ``step``. Returns False if the request
        had already finished (or the session is gone). Thread-safe."""
        with self._lock:
            sess = self._session
            if sess is None:
                return False
            ok = sess.sched.cancel(handle.req, sess.now)
            tel = self.telemetry
            if ok and tel.enabled:
                tel.sched_cancelled.inc()
                self._marks.pop(handle.req.rid, None)
                tel.instant(f"{self.name}/req{handle.req.rid}", "cancel",
                            time.perf_counter())
            return ok

    def step(self) -> list[TokenEvent]:
        """ONE serving iteration: admit every arrived request that fits a
        free slot (each prefilled alone, spliced into the pool), then run
        one batched decode step over the whole pool and sample all slots
        in one jitted call. Returns the tokens generated this iteration.
        Each admission and the decode step drive the control plane.
        Thread-safe; registered step hooks fire before the lock drops.
        A no-op on a closed session — a parked step-loop thread racing a
        ``close`` must not resurrect a fresh default session.

        Timed on the host clock as the section ``engine.step``, whose
        children are ``engine.admit`` (scheduler pop and KV admission),
        ``engine.prepare`` (host batch build and transfer),
        ``engine.dispatch`` (the jitted ``engine_step`` call),
        ``engine.fetch`` (sampling and the device->host token fetch),
        the control plane's ``control.step`` and the expert runtime's
        ``runtime.apply``, and ``engine.commit`` (KV advance and the
        token loop)."""
        with self._lock:
            if self._session is None:
                return []
            t0 = time.perf_counter()
            with self.telemetry.section("engine.step",
                                        step_num=self.iteration):
                events = self._step_impl(t0)
            for fn in list(self._step_hooks):
                fn(events)
            return events

    def _admitted(self, req: GenRequest, **args) -> None:
        """Telemetry of one admission (host clock): the queue delay since
        submit and the request's ``queue`` span."""
        tel = self.telemetry
        now = time.perf_counter()
        t_sub = self._marks.get(req.rid, now)
        self._marks[req.rid] = now
        tel.sched_admitted.inc()
        tel.sched_queue_delay.observe(now - t_sub)
        tel.span(f"{self.name}/req{req.rid}", "queue", t_sub, now,
                 args=dict(args, rid=req.rid))

    def _prefilled(self, req: GenRequest, plen: int) -> None:
        """The request's ``prefill`` span, admission to first token."""
        now = time.perf_counter()
        self.telemetry.span(
            f"{self.name}/req{req.rid}", "prefill",
            self._marks.get(req.rid, now), now,
            args={"rid": req.rid, "prompt_len": plen,
                  "prefix_hit_len": req.prefix_hit_len})
        self._marks[req.rid] = now

    def _step_impl(self, t_step0: float) -> list[TokenEvent]:
        sess = self._sess
        sched, kv = sess.sched, sess.kv
        events: list[TokenEvent] = []
        if sched.done:
            return events
        if not sched.running:
            nxt = sched.next_arrival()
            if nxt is not None:
                sess.now = max(sess.now, nxt)
        collect = self._collect or (
            sess.control is not None and sess.control.predictor is not None
            and self.cfg.is_moe)
        tel = self.telemetry
        if self.serving.prefill_chunk > 0:
            return self._step_chunked(sess, collect, events, t_step0)
        # admission: prefill every arrived request that fits a slot
        with tel.section("engine.admit"):
            while (req := sched.pop_admissible(sess.now)) is not None:
                t0 = time.perf_counter()
                if tel.enabled:
                    self._admitted(req)
                tok, cache1, plen, metrics, mask = self.prefill_request(
                    req.prompt, collect=collect, sampling=req.sampling,
                    rid=req.rid)
                dt = None
                if sess.control is not None and "expert_load" in metrics:
                    out = sess.control.step(
                        sess.now, self._gate_inputs(metrics),
                        metrics["expert_load"], token_mask=mask,
                        dropped=metrics.get("dropped"), phase="prefill")
                    dt = out.latency_s
                    if sess.runtime is not None:
                        sess.runtime.apply(sess.now, out.events,
                                           phase="prefill",
                                           compute_s=out.latency_s)
                self._drive_controller(metrics, token_mask=mask)
                if dt is None:
                    dt = time.perf_counter() - t0
                slot = kv.alloc()
                kv.insert(slot, cache1, plen, owner=req.rid)
                sess.bind_slot(slot, req)
                sched.start(req, slot, sess.now)
                sess.now += dt * sess.time_scale
                sess.prefills += 1
                sess.cur[slot] = tok
                sess.count[slot] = 1
                done = sched.on_token(slot, tok, sess.now)  # TTFT
                events.append(TokenEvent(req.rid, tok, done))
                if tel.enabled:
                    tel.engine_steps.labels(phase="prefill").inc()
                    tel.engine_step_seconds.labels(phase="prefill").observe(
                        time.perf_counter() - t0)
                    tel.engine_tokens.inc()
                    self._prefilled(req, plen)
                    if done:
                        self._finish_req(req)
        if tel.enabled:
            tel.sched_pending.set(len(sched.pending))
        if not sched.running:
            return events
        # one batched decode step over the whole pool (static shapes),
        # then one jitted sampling call over every slot
        t0 = time.perf_counter()
        with tel.section("engine.prepare"):
            if isinstance(kv, PagedKVCache):
                lengths, active, tables = kv.step_state()
                batch = {"tokens": jnp.asarray(sess.cur[:, None]),
                         "active": active, "block_tables": tables,
                         "new_counts": active.astype(jnp.int32)}
            else:
                lengths, active = kv.step_lengths()
                batch = {"tokens": jnp.asarray(sess.cur[:, None]),
                         "active": active}
        with tel.section("engine.dispatch", phase="decode",
                         rows=len(sched.running), prefill_rows=0):
            logits, kv.cache, metrics = self._dispatch(
                sess, collect, batch, lengths)
        with tel.section("engine.fetch") as fetch:
            toks = self._fetch_tokens(sess, logits[:, -1])
        dt = None
        if sess.control is not None and "expert_load" in metrics:
            out = sess.control.step(
                sess.now, self._gate_inputs(metrics),
                metrics["expert_load"], token_mask=active,
                dropped=metrics.get("dropped"), phase="decode")
            dt = out.latency_s
            if sess.runtime is not None:
                sess.runtime.apply(sess.now, out.events, phase="decode",
                                   compute_s=out.latency_s)
        self._drive_controller(metrics, token_mask=active)
        if dt is None:
            dt = time.perf_counter() - t0
        sess.now += dt * sess.time_scale
        sess.iters += 1
        self.iteration += 1
        n_active = len(sched.running)
        sess.occupancy.append(n_active)
        with tel.section("engine.commit"):
            capped = set(kv.advance())
            for slot in list(sched.running):
                tok = int(toks[slot])
                sess.cur[slot] = tok
                sess.count[slot] += 1
                req = sched.running[slot]
                done = sched.on_token(slot, tok, sess.now)
                if not done and slot in capped:
                    # KV ring/blocks at capacity: one more decode would
                    # overwrite live cache — finish with reason "length"
                    sched.force_finish(slot, sess.now)
                    done = True
                events.append(TokenEvent(req.rid, tok, done))
                if done and tel.enabled:
                    self._finish_req(req)
        if tel.enabled:
            self._stepped("decode", t0, fetch.seconds, n_active)
            tel.engine_tokens.inc(n_active)
            if isinstance(kv, PagedKVCache):
                tel.kv_blocks_used.set(kv.used_blocks)
                tel.kv_blocks_free.set(kv.free_blocks)
        return events

    def _dispatch(self, sess, collect, batch, lengths):
        """The batched step: ONE jitted ``engine_step`` over the pool."""
        kv = sess.kv
        if sess.runtime is not None:
            # EP slot data plane: the MoE layers execute the control
            # plane's plans through the runtime's live slot
            # tables/weights (re-programmed each iteration, no
            # recompile). The KV pool's pad rows (num_slots rounded up
            # to the shard multiple) are capacity-neutral (ctx.pad_rows)
            step_fn = self._get_ep_step(collect, dataclasses.replace(
                sess.runtime.ctx, pad_rows=kv.rows - kv.num_slots))
            return step_fn(self.params, batch, kv.cache, lengths,
                           sess.runtime.ep_state())
        step_fn = self._get_step(collect)
        return step_fn(self.params, batch, kv.cache, lengths)

    def _stepped(self, phase: str, t0: float, sync_s: float,
                 n_active: int) -> None:
        """Telemetry of one finished batched step that began (host
        clock) at `t0`: its phase counter and seconds, the token fetch's
        seconds, occupancy, and the engine's ``decode_step`` span."""
        tel = self.telemetry
        t1 = time.perf_counter()
        tel.engine_steps.labels(phase=phase).inc()
        tel.engine_step_seconds.labels(phase=phase).observe(t1 - t0)
        tel.engine_host_sync.observe(sync_s)
        tel.engine_occupancy.set(n_active)
        tel.span(self.name, "decode_step", t0, t1,
                 args={"occupancy": n_active, "phase": phase})

    def _step_chunked(self, sess, collect, events,
                      t_step0: float) -> list[TokenEvent]:
        """Chunked-prefill iteration (paged KV only): admission is pure
        table work — ``kv.begin`` matches the prefix cache, refcount-
        shares the matched blocks, and reserves the rest; NO solo model
        call. Each mid-prefill slot then contributes up to
        ``prefill_chunk`` prompt tokens per iteration to the SAME
        batched step the decoding slots run, as extra masked rows — the
        decode batch never stalls behind a long prompt. A slot's first
        output token is sampled from the logits of its final prompt
        position the step its last chunk lands. `t_step0` is the host
        time the step began, admission included."""
        sched, kv = sess.sched, sess.kv
        tel = self.telemetry
        chunk = self.serving.prefill_chunk
        with tel.section("engine.admit"):
            while (req := sched.pop_admissible(sess.now)) is not None:
                slot = kv.alloc()
                hit = kv.begin(slot, req.prompt, req.max_new_tokens,
                               owner=req.rid)
                req.prefix_hit_len = hit
                sess.bind_slot(slot, req)
                prompt = np.asarray(req.prompt, np.int32).reshape(-1)
                sess.prompts[slot] = prompt
                sess.plen[slot] = prompt.shape[0]
                sched.start(req, slot, sess.now)
                sess.prefills += 1
                if tel.enabled:
                    self._admitted(req, prefix_hit_len=hit)
                    if hit:
                        tel.kv_prefix_hits.inc()
                        tel.kv_prefix_tokens_saved.inc(hit)
                    cow = kv.cow_blocks - sess.cow_seen
                    if cow:
                        tel.kv_cow_copies.inc(cow)
                sess.cow_seen = kv.cow_blocks
        if tel.enabled:
            tel.sched_pending.set(len(sched.pending))
        if not sched.running:
            return events
        t0 = time.perf_counter()
        with tel.section("engine.prepare"):
            rows = kv.rows
            counts = np.zeros(rows, np.int32)
            first_rows: set[int] = set()   # prompt completes this step
            for slot in sched.running:
                left = int(sess.plen[slot]) - int(kv.lengths[slot])
                if left > 0:
                    counts[slot] = min(chunk, left)
                    if counts[slot] == left:
                        first_rows.add(slot)
                else:
                    counts[slot] = 1
            n_prefill = sum(1 for slot in sched.running
                            if kv.lengths[slot] < sess.plen[slot])
            s_new = chunk if n_prefill else 1   # two jit entries total
            tokens = np.zeros((rows, s_new), np.int32)
            for slot in sched.running:
                c = int(counts[slot])
                pos = int(kv.lengths[slot])
                if pos < sess.plen[slot]:
                    tokens[slot, :c] = sess.prompts[slot][pos:pos + c]
                else:
                    tokens[slot, 0] = sess.cur[slot]
            lengths, active, tables = kv.step_state()
            counts_j = jnp.asarray(counts)
            mask = jnp.arange(s_new, dtype=jnp.int32)[None] \
                < counts_j[:, None]
            batch = {"tokens": jnp.asarray(tokens), "active": active,
                     "token_mask": mask, "block_tables": tables,
                     "new_counts": counts_j}
        phase = "mixed" if n_prefill else "decode"
        with tel.section("engine.dispatch", phase=phase,
                         rows=len(sched.running), prefill_rows=n_prefill):
            logits, kv.cache, metrics = self._dispatch(
                sess, collect, batch, lengths)
        with tel.section("engine.fetch") as fetch:
            # each row's next-token logits sit at its LAST written
            # position
            idx = jnp.asarray(np.maximum(counts - 1, 0))
            last = jnp.take_along_axis(logits, idx[:, None, None],
                                       axis=1)[:, 0]
            toks = self._fetch_tokens(sess, last)
        dt = None
        if sess.control is not None and "expert_load" in metrics:
            out = sess.control.step(
                sess.now, self._gate_inputs(metrics),
                metrics["expert_load"], token_mask=mask.reshape(-1),
                dropped=metrics.get("dropped"), phase=phase)
            dt = out.latency_s
            if sess.runtime is not None:
                sess.runtime.apply(sess.now, out.events, phase=phase,
                                   compute_s=out.latency_s)
        self._drive_controller(metrics, token_mask=mask.reshape(-1))
        if dt is None:
            dt = time.perf_counter() - t0
        sess.now += dt * sess.time_scale
        sess.iters += 1
        self.iteration += 1
        n_active = len(sched.running)
        sess.occupancy.append(n_active)
        emitted = 0
        with tel.section("engine.commit"):
            capped = set(kv.advance(counts))
            for slot in list(sched.running):
                req = sched.running[slot]
                if kv.lengths[slot] < sess.plen[slot]:
                    continue             # still mid-prefill: no token yet
                if slot in first_rows:
                    sess.count[slot] = 1     # the request's first token
                else:
                    sess.count[slot] += 1
                tok = int(toks[slot])
                sess.cur[slot] = tok
                emitted += 1
                done = sched.on_token(slot, tok, sess.now)  # TTFT on first
                if not done and slot in capped:
                    sched.force_finish(slot, sess.now)
                    done = True
                events.append(TokenEvent(req.rid, tok, done))
                if tel.enabled and slot in first_rows:
                    self._prefilled(req, int(sess.plen[slot]))
                if done and tel.enabled:
                    self._finish_req(req)
        if tel.enabled:
            self._stepped(phase, t_step0, fetch.seconds, n_active)
            tel.engine_tokens.inc(emitted)
            tel.kv_blocks_used.set(kv.used_blocks)
            tel.kv_blocks_free.set(kv.free_blocks)
        return events

    @staticmethod
    def _fetch_tokens(sess, last) -> np.ndarray:
        """Sample every slot from its next-token logits `last` (rows, V)
        and fetch the tokens to the host — the iteration's one
        device->host sync, which also carries the count of non-finite
        logits so a NaN/Inf model output is metered, not hidden behind
        an argmax."""
        if any(sess.temp[s] > 0 for s in sess.sched.running):
            toks = T.sample_tokens(
                last, jnp.asarray(sess.temp), jnp.asarray(sess.topk),
                jnp.asarray(sess.topp), jnp.asarray(sess.seed),
                jnp.asarray(sess.count))
        else:   # all-greedy batch: skip the sampler's per-slot sort work
            toks = jnp.argmax(last, axis=-1)
        toks, bad = jax.device_get((toks, _count_nonfinite(last)))
        sess.nonfinite += int(bad)
        return np.asarray(toks)

    def _finish_req(self, req: GenRequest) -> None:
        """Record one request's terminal telemetry (finish counter +
        closing decode span / finish instant on its trace track), on
        the host clock."""
        tel = self.telemetry
        tel.sched_finished.labels(reason=req.finish_reason or "done").inc()
        t = time.perf_counter()
        t_first = self._marks.pop(req.rid, t)
        if tel.tracing:
            track = f"{self.name}/req{req.rid}"
            tel.span(track, "decode", t_first, t, args={"rid": req.rid})
            tel.instant(track, "finish", t,
                        args={"rid": req.rid,
                              "reason": req.finish_reason,
                              "out_tokens": len(req.tokens)})

    def stream(self, handle: RequestHandle) -> Iterator[int]:
        """Incrementally yield `handle`'s tokens, driving ``step`` while
        the request still has work in flight. Ends on finish (EOS / stop
        sequence / budget) or cancellation."""
        sent = 0
        while True:
            toks = handle.req.tokens
            while sent < len(toks):
                yield toks[sent]
                sent += 1
            if handle.status in ("finished", "cancelled", "rejected"):
                return
            if self._session is None or self._session.sched.done:
                return
            self.step()

    def run(self, *, verbose: bool = False) -> ServeResult:
        """Drive ``step`` until the session has no pending or running
        requests, then snapshot the session's metrics."""
        sess = self._sess
        while not sess.sched.done:
            self.step()
            if verbose and sess.iters % 50 == 0:
                print(f"  t={sess.now:8.2f}s iter={sess.iters} "
                      f"active={len(sess.sched.running)} "
                      f"pending={len(sess.sched.pending)} "
                      f"done={len(sess.sched.finished)}")
        return self.result()

    def result(self) -> ServeResult:
        if self._session is None:
            raise RuntimeError("no serving session — call start() / "
                               "serve() first")
        sess = self._session
        return ServeResult(
            records=sess.sched.metrics(), iterations=sess.iters,
            prefills=sess.prefills, rejected=len(sess.sched.rejected),
            cancelled=len(sess.sched.cancelled),
            mean_batch_occupancy=float(np.mean(sess.occupancy))
            if sess.occupancy else 0.0,
            wall_s=time.perf_counter() - sess.wall0, control=sess.control,
            runtime=sess.runtime, clock_s=sess.now,
            dropped_tokens=float(getattr(sess.control, "dropped_tokens",
                                         0.0) or 0.0),
            nonfinite_logits=sess.nonfinite)

    # ------------------------------------------------------ trace replay

    def serve(self, requests, *, num_slots: int = 8, eos_id=None,
              control: ControlPlane | None = None,
              time_scale: float = 1.0,
              verbose: bool = False) -> ServeResult:
        """Continuous-batching replay of `requests` (list[GenRequest]) —
        a thin driver over the request-level API: open a session, submit
        everything, run to completion."""
        self.start(num_slots=num_slots, eos_id=eos_id, control=control,
                   time_scale=time_scale)
        for r in sorted(requests, key=lambda r: r.arrival):
            self.submit(r)
        res = self.run(verbose=verbose)
        self.close()
        return res

    @staticmethod
    def _gate_inputs(metrics):
        gi = metrics.get("gate_input")
        if gi is None:
            return None
        return gi.reshape(gi.shape[0], -1, gi.shape[-1])
