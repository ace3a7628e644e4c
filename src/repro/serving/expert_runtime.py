"""Serverless expert runtime — the device-resident slot state machine
that EXECUTES the control plane's replica plans in the serving hot path
(paper §2.4/§5; closes the plan→execution gap).

The control plane (``repro.core.control.ControlPlane.step``) decides,
per iteration and per MoE layer, how many replicas each expert function
gets and where they live. Until now those plans were only *metered*
analytically — the data plane decoded through a static expert layout.
``ExpertRuntime`` owns the per-device slot-resident expert weight
buffers the jitted EP dispatch (``distributed.ep.moe_ep_layer``)
consumes, and applies each ``IterationOutcome`` as a **diff**:

  * function locality — a warm (expert, device) replica keeps its slot;
    it is never re-copied. An unchanged plan moves zero bytes.
  * minimal transfers — only replicas with no live instance cost a slot
    weight copy; the copy count equals the plan's diff against current
    residency (``LayerPlan.diff_size``).
  * cold-start hiding — a new replica whose modeled cold start fits
    inside the predictor's lead time is *prewarmed* (serves this
    iteration); otherwise it is *cold* and serves from the NEXT
    iteration via the control plane's warm-subset ``served`` plan
    (asynchronous scaling, paper §5). Weights materialise either way —
    the copy IS the cold start.
  * keep-alive eviction — instances idle past ``keep_alive`` free their
    slot and are billed for their actual residency, exactly like the
    analytic ``ServerlessExpertPool`` they are validated against.

Metering: cold/warm/prewarmed counts and GB-seconds of residency follow
the SAME classification the analytic pool applies (same plans, same
timestamps, same lead/exec times ⇒ equal counts — a tested invariant),
while ``bytes_moved`` counts the weight bytes actually written into
slot banks on this host. Both byte bases honour
``cfg.moe.slot_dtype``: with ``'int8'`` the banks hold symmetric
per-row-scale quantized experts (``repro.kernels.quant``), so every
cold start moves ~4x fewer bytes and every GB-s of residency bills
~4x cheaper — and ``_slot_row_bytes`` stays exactly equal to
``costmodel.param_bytes(cfg)``, preserving runtime==analytic parity.

Slot geometry and the rank mapping contract: the plan's `num_devices`
logical devices each own `slots_per_device` logical slots, flattened to
``total_slots`` physical slots. The physical bank is padded up to the
next multiple of the mesh's `ep` degree (``phys_slots``) so it splits
evenly over ranks; pad slots are permanently empty and never referenced
by routing tables. Physical slot s lives on EP rank
``s // (phys_slots // ep)``, so logical device g's block of slots maps
to rank ``(g * slots_per_device) // (phys_slots // ep)`` — contiguous
logical devices project onto contiguous ranks (the block mapping
``distributed.ep.device_rank`` when ep divides num_devices). A replica
planned onto a full device spills to the ring-nearest logical device
with a free slot, mirroring ``plan_to_tables``; under the block mapping
the logical ring refines the rank ring, so spills stay rank-local when
they can. The spill rule is a pure function of the LOGICAL geometry —
never of `ep` — so the slot layout (and therefore every routed bit) is
identical on every mesh factorisation of the same logical plan.

Multi-rank execution: the slot weight banks are created under
``NamedSharding`` (slot axis over 'ep', FFN width over 'tp'), so a slot
materialisation writes bytes only on the owning rank — metered per rank
in ``RuntimeStats.rank_bytes``. With ``double_buffer=True`` (default)
each flush writes the diff into the BACK bank (plus the diff the front
received last flush — catch-up), then swaps: the donated scatter has no
data dependency on the bank the in-flight iteration is reading, so
next-iteration materialisation copies overlap the current iteration's
EP FFN compute. Copies whose replica is absent from this iteration's
warm-subset ``served`` plan (i.e. serve only NEXT iteration — the
ahead-of-time lane, cold or prewarmed) are counted
``overlap_eligible``; copies the very next dispatch needs (bootstrap,
where served == plan) are ``exposed``.
"""
from __future__ import annotations

import math
import time
import warnings
from dataclasses import dataclass, field

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core import serverless as SL
from repro.core.control import (MOELESS_EXEC_TIME, PlanEvent,
                                default_slots_per_device)
from repro.core.costmodel import V5E, Hardware, derive_coeffs
from repro.distributed.ep import EPContext, _slot_spec
from repro.kernels import quant as QT
from repro.launch.mesh import make_serving_mesh
from repro.models import transformer as T


@dataclass
class RuntimeStats:
    """Cumulative meters of the executing runtime (all layers)."""
    cold_starts: int = 0
    warm_starts: int = 0
    prewarmed: int = 0
    transfers: int = 0             # slot weight copies actually performed
    bytes_moved: float = 0.0       # actual bytes written into slot banks
    evictions: int = 0             # keep-alive expiries
    instance_seconds_gb: float = 0.0   # GB-seconds of actual residency
    # transfer/compute overlap: a copy whose replica serves only from
    # the NEXT iteration (absent from the warm-subset served plan — the
    # cold-start lane) has no consumer in the current dispatch, so the
    # double-buffered scatter overlaps this iteration's FFN compute;
    # copies the next dispatch needs immediately are exposed
    overlap_eligible_copies: int = 0
    exposed_copies: int = 0
    overlap_hidden_s: float = 0.0  # sum min(cold_start, compute window)
    # bytes written on each EP mesh rank's slot shard ("rank0", ...)
    rank_bytes: dict = field(default_factory=dict)
    # per-phase breakdown: prefill iterations apply plans through the
    # SAME diff machinery as decode (and the bootstrap load), so their
    # cold/warm/prewarm and bytes are metered under their own key
    by_phase: dict = field(default_factory=dict)

    def counts(self) -> tuple[int, int, int]:
        return self.cold_starts, self.warm_starts, self.prewarmed

    def phase(self, name: str) -> dict:
        return self.by_phase.setdefault(name, {
            "iterations": 0, "cold_starts": 0, "warm_starts": 0,
            "prewarmed": 0, "transfers": 0, "bytes_moved": 0.0})


@dataclass
class ApplyReport:
    """What ONE ``apply`` call did to the slot state."""
    transfers: int = 0
    bytes_moved: float = 0.0
    cold_starts: int = 0
    warm_starts: int = 0
    prewarmed: int = 0
    evictions: int = 0
    overlap_eligible: int = 0
    exposed: int = 0
    per_layer_transfers: list = field(default_factory=list)
    rank_bytes: dict = field(default_factory=dict)


@dataclass
class _SlotInstance:
    """One live expert function instance, resident in one slot."""
    slot: int
    born: float
    last_used: float


class ExpertRuntime:
    """Owns the slot-resident expert weights for every MoE layer of one
    model and executes the control plane's plans as slot diffs.

    Lifecycle:  ``bootstrap(control)`` installs the balancer's prewarm
    plans (if any), ``apply(t, events)`` executes one iteration's
    ``PlanEvent`` list, ``ep_state()`` exports the live tables/weights
    for the jitted decode step, ``finalize(now)`` settles residency
    billing.
    """

    def __init__(self, cfg, params, *, num_devices: int,
                 slots_per_device: int = 0, mesh=None,
                 keep_alive: float = 60.0, hw: Hardware = V5E,
                 coeffs=None, double_buffer: bool = True,
                 telemetry=None, track: str = "runtime"):
        assert cfg.is_moe, "expert runtime serves MoE models"
        from repro.obs.telemetry import NOOP
        # observation-only; `track` names this runtime's trace lane
        self.telemetry = NOOP if telemetry is None else telemetry
        self.track = track
        if cfg.act != "swiglu":
            raise NotImplementedError(
                "EP slot banks hold swiglu experts (w_gate/w_up/w_down); "
                f"act={cfg.act!r} is not wired into the slot data plane")
        self.cfg = cfg
        self.keep_alive = keep_alive
        self.hw = hw
        self.coeffs = coeffs if coeffs is not None else derive_coeffs(cfg)
        self._cold_start_s = SL.cold_start_latency(self.coeffs.expert_bytes,
                                                   hw)

        pattern = T.layer_pattern(cfg)
        self.moe_positions = [j for j, sub in enumerate(pattern)
                              if sub.ffn == "moe"]
        self.pattern_len = len(pattern)
        self.mpp = len(self.moe_positions)       # MoE sublayers per period
        self.periods = cfg.num_layers // len(pattern)
        self.n_layers = self.periods * self.mpp  # == ControlPlane.n_layers

        e = cfg.moe.num_experts
        self.num_experts = e
        self.num_devices = num_devices
        # logical slots per modeled device — same default the
        # MoElessController uses for its slot-table export
        self.slots_per_device = slots_per_device \
            or default_slots_per_device(e, num_devices)
        self.total_slots = num_devices * self.slots_per_device

        if mesh is None:
            mesh = make_serving_mesh(1, ep=1)
        self.mesh = mesh
        self.ep = mesh.shape["ep"]
        # pad the physical bank to the next multiple of ep so the slot
        # axis splits evenly over ranks; the old `total // ep` silently
        # dropped the remainder slots from the data plane. Pad slots are
        # permanently empty (never allocated, never in tables).
        self.phys_slots = -(-self.total_slots // self.ep) * self.ep
        self.pad_slots = self.phys_slots - self.total_slots
        if self.pad_slots:
            warnings.warn(
                f"expert runtime: {self.total_slots} slots "
                f"({num_devices} devices x {self.slots_per_device}) do "
                f"not split over {self.ep} EP ranks; padding the bank "
                f"with {self.pad_slots} masked slot(s)",
                RuntimeWarning, stacklevel=2)
        self.slots_per_rank = self.phys_slots // self.ep
        self.ctx = EPContext(mesh=mesh,
                             slots_per_device=self.slots_per_rank,
                             capacity_factor=cfg.moe.capacity_factor)

        # padded per-expert weight banks, ONE pad at construction
        # (satellite fix: materialisation must not re-pad per call):
        # leaves (P, E+1, D, F) / (P, E+1, F, D). Under
        # cfg.moe.slot_dtype='int8' the padded bank is QUANTIZED once
        # here (kernels.quant: int8 values + fp32 per-row scales) and
        # every later slot materialisation scatters the ~4x smaller
        # rows — cold starts move quantized bytes, never fp32 bytes.
        slot_dtype = getattr(cfg.moe, "slot_dtype", "fp32")
        if slot_dtype not in ("fp32", "int8"):
            raise ValueError(f"unknown slot_dtype {slot_dtype!r}")
        self.padded = {}
        self.banks = {}
        self._back = {}
        self._pending = {}
        self._slot_row_bytes = {}
        self._bank_shardings = {}
        self.double_buffer = double_buffer
        for j in self.moe_positions:
            bank = params["layers"][j]["moe"]["experts"]
            padded = {
                k: jnp.concatenate([w, jnp.zeros_like(w[:, :1])], axis=1)
                for k, w in bank.items()}
            if slot_dtype == "int8":
                padded = QT.quantize_expert_bank(padded)
            self.padded[j] = padded
            # slot banks live SHARDED: the slot axis over 'ep' (each
            # rank owns its slots_per_rank block), FFN width over 'tp',
            # a leading periods axis replicated — so every slot scatter
            # writes bytes only on the owning rank
            shardings = {
                k: NamedSharding(mesh, P(None, *_slot_spec(k)))
                for k in padded}
            self._bank_shardings[j] = shardings

            def _zero_bank():
                return {
                    k: jax.device_put(
                        jnp.zeros(
                            (self.periods, self.phys_slots) + w.shape[2:],
                            w.dtype),
                        shardings[k])
                    for k, w in padded.items()}

            self.banks[j] = _zero_bank()
            # back buffer of the double-buffered bank: flushes write
            # here (no data dependency on the bank in-flight compute
            # reads), then the buffers swap
            self._back[j] = _zero_bank() if double_buffer else None
            self._pending[j] = ([], [], [])
            # bytes of ONE slot row as stored — by construction equal to
            # costmodel.param_bytes(cfg) (== coeffs.expert_bytes), the
            # runtime-vs-analytic metering contract
            self._slot_row_bytes[j] = float(sum(
                int(np.prod(w.shape[2:])) * w.dtype.itemsize
                for w in padded.values()))

        # host-side slot state machine, per MoE layer l = p*mpp + m
        lm, s = self.n_layers, self.total_slots
        self.slot_expert = np.full((lm, s), e, np.int32)   # E => empty
        self.instances: list[dict] = [dict() for _ in range(lm)]
        # routing tables exported to the jitted step (0-padded: padding
        # is never selected because r_idx < nrep)
        self.table_slots = np.zeros((lm, e, s), np.int32)
        self.table_nrep = np.ones((lm, e), np.int32)
        self._have_tables = False
        self.stats = RuntimeStats()
        self.stats.rank_bytes = {f"rank{r}": 0.0 for r in range(self.ep)}
        self.iterations = 0
        # jit caches one program per (position shapes, bucket size); the
        # power-of-two bucketing in _flush bounds how many that is.
        # Explicit out_shardings keep each rank the owner of its slot
        # shard across updates (the specs are identical for every MoE
        # position, so one jit serves them all).
        self._update_fn = jax.jit(
            _scatter_slots, donate_argnums=(0,),
            out_shardings=self._bank_shardings[self.moe_positions[0]])

    # ------------------------------------------------------ construction

    @classmethod
    def for_control(cls, cfg, params, control, *, mesh=None,
                    keep_alive: float | None = None, telemetry=None,
                    track: str = "runtime"):
        """Runtime sized to a ``ControlPlane``: same modeled device
        count, same slot caps, same cost coefficients and keep-alive —
        the preconditions for count/billing parity with the analytic
        pool."""
        if keep_alive is None:
            keep_alive = getattr(control.bal, "keep_alive", 60.0)
        sd = getattr(control, "slots_per_device", 0) \
            or getattr(control.bal, "max_replicas_per_device", 0)
        return cls(cfg, params, num_devices=control.num_devices,
                   slots_per_device=sd, mesh=mesh, keep_alive=keep_alive,
                   coeffs=control.coeffs, telemetry=telemetry, track=track)

    def bootstrap(self, control=None, t: float = 0.0) -> ApplyReport:
        """Install an initial deployment so the EP data plane has live
        tables BEFORE the first control-plane step — required now that
        prefill also routes through the slot data plane (the first
        admission's forward runs before any plan has been metered).

        With a prewarmed balancer (paper §5) the balancer's
        deployment-time plans are applied, so the runtime's residency
        starts exactly where the analytic pool's did. Otherwise a
        static uniform plan (one replica per expert, Megatron layout)
        is materialised as the initial weight load — the same bytes any
        deployment pays before serving its first token."""
        bal = getattr(control, "bal", None)
        prev = getattr(bal, "prev", None)
        serverless = bool(getattr(bal, "serverless", False))
        if prev:
            events = [PlanEvent(plan=prev[l], served=prev[l],
                                lead_time=math.inf,
                                exec_time=MOELESS_EXEC_TIME,
                                serverless=True)
                      for l in range(self.n_layers)]
        else:
            from repro.core.plan import static_plan
            plan = static_plan(self.num_experts, self.num_devices)
            events = [PlanEvent(plan=plan, served=plan,
                                lead_time=math.inf,
                                exec_time=MOELESS_EXEC_TIME,
                                serverless=serverless)
                      for _ in range(self.n_layers)]
        return self.apply(t, events, phase="bootstrap")

    # -------------------------------------------------------- lifecycle

    def cold_start_latency(self) -> float:
        return self._cold_start_s

    def _bill(self, inst: _SlotInstance, until: float) -> None:
        alive = until - inst.born
        self.stats.instance_seconds_gb += \
            alive * self.coeffs.expert_bytes / 1e9

    def _reap(self, layer: int, now: float) -> None:
        inst = self.instances[layer]
        for key in [k for k, i in inst.items()
                    if now - i.last_used > self.keep_alive]:
            i = inst.pop(key)
            self._bill(i, i.last_used + self.keep_alive)
            self.slot_expert[layer, i.slot] = self.num_experts
            self.stats.evictions += 1

    def _reclaim(self, layer: int, now: float, n: int, keep: set) -> None:
        """Evict the `n` least recently used instances of `layer` that
        are not in `keep`, billed until `now`. Serverless plans reach it
        only when the slots are otherwise exhausted, where the analytic
        pool (which has no slot cap) would keep them alive."""
        inst = self.instances[layer]
        idle = sorted((k for k in inst if k not in keep),
                      key=lambda k: inst[k].last_used)
        for key in idle[:n]:
            i = inst.pop(key)
            self._bill(i, now)
            self.slot_expert[layer, i.slot] = self.num_experts
            self.stats.evictions += 1

    def _alloc(self, layer: int, g: int) -> int:
        """Lowest free slot on logical device g, spilling to the
        ring-nearest device with capacity (mirrors ``plan_to_tables``)."""
        sd, gdev = self.slots_per_device, self.num_devices
        row = self.slot_expert[layer]

        def free_on(gg: int) -> int:
            base = gg * sd
            for s in range(base, base + sd):
                if row[s] == self.num_experts:
                    return s
            return -1

        g = g % gdev
        slot = free_on(g)
        if slot >= 0:
            return slot
        candidates = [gg for gg in range(gdev) if free_on(gg) >= 0]
        if not candidates:
            raise RuntimeError(
                f"layer {layer}: no free slot for a replica on device {g} "
                f"({self.total_slots} slots all resident)")
        near = min(candidates,
                   key=lambda gg: min((gg - g) % gdev, (g - gg) % gdev))
        warnings.warn(
            f"expert runtime: layer {layer} replica overflowed device {g} "
            f"(cap {sd}/device) and spilled to device {near}",
            RuntimeWarning, stacklevel=3)
        return free_on(near)

    # ------------------------------------------------------------ apply

    def rank_of_slot(self, slot: int) -> int:
        """EP mesh rank owning physical slot `slot` under the sharded
        bank layout (slot axis split evenly over 'ep')."""
        return slot // self.slots_per_rank

    def apply(self, t: float, events: list, phase: str = "decode",
              *, compute_s: float | None = None) -> ApplyReport:
        """Execute one iteration's planning decisions: reap expired
        instances, diff every layer's FULL plan against residency,
        materialise ONLY the changed slots, and rebuild the routing
        tables from the warm-subset ``served`` plans. `phase` tags the
        iteration ('prefill' | 'decode' | 'bootstrap') in the per-phase
        meters — prefill now executes plans through this same path.

        `compute_s` is the modeled iteration latency the copies can hide
        under: each overlap-eligible copy (replica absent from the
        served plan — consumed only next iteration) accrues
        ``min(cold_start_latency, compute_s)`` of hidden transfer time,
        the analytic bound the measured wall-clock overlap is compared
        against in serving_bench."""
        if len(events) != self.n_layers:
            raise ValueError(f"{len(events)} plan events for "
                             f"{self.n_layers} MoE layers")
        rep = ApplyReport()
        rep.rank_bytes = {f"rank{r}": 0.0 for r in range(self.ep)}
        evict0 = self.stats.evictions
        hidden0 = self.stats.overlap_hidden_s
        updates = {j: ([], [], []) for j in self.moe_positions}
        for layer, ev in enumerate(events):
            self._reap(layer, t)
            inst = self.instances[layer]
            served_set = set(ev.served.iter_replicas())
            desired = set(ev.plan.iter_replicas())
            if not ev.serverless:
                # serverful semantics: the plan IS the deployment —
                # replicas absent from it release their slot now
                # (keep-alive would otherwise pin every historical
                # placement of a periodic rebalancer forever)
                self._reclaim(layer, t, len(inst), keep=desired)
            else:
                # keep-alive can pin every slot with instances the plan
                # no longer wants; reclaim the least recently used of
                # them, as a serverless platform does under capacity
                # pressure
                short = sum(1 for key in desired if key not in inst) \
                    - int(np.sum(self.slot_expert[layer]
                                 == self.num_experts))
                if short > 0:
                    self._reclaim(layer, t, short, keep=desired)
            n_transfer = 0
            for key in ev.plan.iter_replicas():
                if key in inst:
                    inst[key].last_used = t + ev.lead_time + ev.exec_time
                    self.stats.warm_starts += 1
                    rep.warm_starts += 1
                    continue
                e, g = key
                slot = self._alloc(layer, g)
                self.slot_expert[layer, slot] = e
                inst[key] = _SlotInstance(
                    slot=slot, born=t,
                    last_used=t + ev.lead_time + ev.exec_time)
                if self._cold_start_s <= ev.lead_time:
                    self.stats.prewarmed += 1
                    rep.prewarmed += 1
                else:
                    self.stats.cold_starts += 1
                    rep.cold_starts += 1
                n_transfer += 1
                p, j = layer // self.mpp, \
                    self.moe_positions[layer % self.mpp]
                ps, ss, es = updates[j]
                ps.append(p)
                ss.append(slot)
                es.append(e)
                row_bytes = self._slot_row_bytes[j]
                self.stats.bytes_moved += row_bytes
                rep.bytes_moved += row_bytes
                rk = f"rank{self.rank_of_slot(slot)}"
                self.stats.rank_bytes[rk] += row_bytes
                rep.rank_bytes[rk] += row_bytes
                # overlap classification: a replica outside the served
                # plan serves only NEXT iteration, so its copy has no
                # consumer in the current dispatch — the double-buffered
                # scatter hides it under this iteration's compute
                if key not in served_set:
                    self.stats.overlap_eligible_copies += 1
                    rep.overlap_eligible += 1
                    window = compute_s if compute_s is not None \
                        else ev.exec_time
                    self.stats.overlap_hidden_s += \
                        min(self._cold_start_s, window)
                else:
                    self.stats.exposed_copies += 1
                    rep.exposed += 1
            self.stats.transfers += n_transfer
            rep.transfers += n_transfer
            rep.per_layer_transfers.append(n_transfer)
            self._build_tables(layer, ev.served)
        rep.evictions = self.stats.evictions - evict0
        t_w0 = time.perf_counter()
        self._flush(updates)
        flush_wall = time.perf_counter() - t_w0
        self._have_tables = True
        self.iterations += 1
        ph = self.stats.phase(phase)
        ph["iterations"] += 1
        ph["cold_starts"] += rep.cold_starts
        ph["warm_starts"] += rep.warm_starts
        ph["prewarmed"] += rep.prewarmed
        ph["transfers"] += rep.transfers
        ph["bytes_moved"] += rep.bytes_moved
        tel = self.telemetry
        if tel.enabled:
            for kind, n in (("cold", rep.cold_starts),
                            ("warm", rep.warm_starts),
                            ("prewarmed", rep.prewarmed)):
                if n:
                    tel.runtime_starts.labels(kind=kind).inc(n)
            if rep.transfers:
                tel.runtime_transfers.inc(rep.transfers)
                tel.runtime_bytes.inc(rep.bytes_moved)
                for rk, b in rep.rank_bytes.items():
                    if b:
                        tel.runtime_rank_bytes.labels(rank=rk).inc(b)
            if rep.evictions:
                tel.runtime_evictions.inc(rep.evictions)
            if rep.overlap_eligible:
                tel.runtime_overlap_copies.labels(kind="eligible").inc(
                    rep.overlap_eligible)
            if rep.exposed:
                tel.runtime_overlap_copies.labels(kind="exposed").inc(
                    rep.exposed)
            hid = self.stats.overlap_hidden_s - hidden0
            if hid:
                tel.runtime_overlap_hidden.inc(hid)
            tel.runtime_resident.set(self.resident_replicas())
            tel.runtime_flush_seconds.observe(flush_wall)
            if tel.tracing and rep.transfers:
                # span anchored at the serving-clock apply time, with
                # the flush's measured wall duration
                tel.span(self.track, "bank_flush", t, t + flush_wall,
                         args={"phase": phase,
                               "transfers": rep.transfers,
                               "bytes": rep.bytes_moved})
        return rep

    def _build_tables(self, layer: int, served) -> None:
        inst = self.instances[layer]
        slots = self.table_slots[layer]
        nrep = self.table_nrep[layer]
        slots[:] = 0
        for e in range(self.num_experts):
            placement = served.placement[e]
            nrep[e] = max(1, len(placement))
            for r, g in enumerate(placement):
                slots[e, r] = inst[(e, int(g))].slot

    def _scatter(self, bank, j, ps, ss, es):
        """One donated jitted scatter, sized to a power-of-two bucket so
        a steady stream of small diffs reuses a handful of compiled
        update programs."""
        k = len(ps)
        bucket = 1 << (k - 1).bit_length()
        ps = ps + [ps[-1]] * (bucket - k)
        ss = ss + [ss[-1]] * (bucket - k)
        es = es + [es[-1]] * (bucket - k)
        return self._update_fn(
            bank, self.padded[j],
            jnp.asarray(ps, jnp.int32),
            jnp.asarray(ss, jnp.int32),
            jnp.asarray(es, jnp.int32))

    def _flush(self, updates: dict) -> None:
        """Write the changed slots' weights into the device banks.

        Double-buffered (default): the new diff PLUS the diff the front
        bank received last flush (catch-up, kept in ``_pending``) is
        scattered into the BACK bank, then the buffers swap — the
        donated scatter never touches the bank an in-flight iteration
        is reading, so the copies overlap compute instead of serialising
        behind it. ``bytes_moved`` / ``rank_bytes`` meter each replica
        copy once (the logical cold-start traffic); the catch-up write
        is pipeline bookkeeping, not a second cold start."""
        for j, (ps, ss, es) in updates.items():
            if not self.double_buffer:
                if len(ps):
                    self.banks[j] = self._scatter(
                        self.banks[j], j, ps, ss, es)
                continue
            pp, sp, ep_ = self._pending[j]
            cps, css, ces = pp + list(ps), sp + list(ss), ep_ + list(es)
            if not cps:
                continue
            back = self._scatter(self._back[j], j, cps, css, ces)
            self._back[j] = self.banks[j]
            self.banks[j] = back
            self._pending[j] = (list(ps), list(ss), list(es))

    # ------------------------------------------------------------ export

    def ep_state(self) -> list:
        """The per-layer slot tables + weight banks as the decode step's
        ``ep_state`` pytree: one entry per sublayer pattern position
        (None for non-MoE positions), leaves stacked over periods."""
        if not self._have_tables:
            raise RuntimeError("expert runtime has no tables yet — "
                               "bootstrap() or apply() a plan first")
        state = [None] * self.pattern_len
        for m, j in enumerate(self.moe_positions):
            state[j] = {
                "expert_slots": jnp.asarray(self.table_slots[m::self.mpp]),
                "nrep": jnp.asarray(self.table_nrep[m::self.mpp]),
                **self.banks[j],
            }
        return state

    # ---------------------------------------------------------- metering

    def resident_replicas(self) -> int:
        return sum(len(d) for d in self.instances)

    def residency_set(self, layer: int) -> set:
        """Live (expert, device) instances of one layer."""
        return set(self.instances[layer])

    def finalize(self, now: float) -> RuntimeStats:
        """Settle residency billing (idempotent — instances are released
        as they are billed), mirroring ``ServerlessExpertPool.finalize``."""
        for layer in range(self.n_layers):
            inst = self.instances[layer]
            for key, i in list(inst.items()):
                self._bill(i, min(now, i.last_used + self.keep_alive))
                self.slot_expert[layer, i.slot] = self.num_experts
                del inst[key]
        return self.stats


def _scatter_slots(banks, padded, p_idx, s_idx, e_idx):
    """banks[k] (P, S, ...), padded[k] (P, E+1, ...): write the (K,)
    changed slots' expert rows. Runs donated under jit — only the
    touched rows move."""
    return {k: b.at[p_idx, s_idx].set(padded[k][p_idx, e_idx])
            for k, b in banks.items()}
