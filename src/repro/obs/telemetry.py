"""The ``Telemetry`` handle — ONE object threaded through the serving
stack (engine, scheduler accounting, expert runtime, control plane,
gateway router/driver/server, launchers) that carries

  * a ``MetricsRegistry`` holding the whole metric taxonomy (declared
    HERE, in one place, so names never drift between subsystems), and
  * optionally a ``Tracer`` collecting Chrome trace-event spans /
    instants (``tracing`` is True only when a tracer is attached), and
  * ``section(name, **args)``: the span API of the serving stack's host
    work. A section is a ``jax.profiler.TraceAnnotation``, so it lands
    on the host plane of a profiler trace beside the device programs it
    launches, and, with a tracer attached, the same span (with its
    parent section) in the Chrome JSON. Both read the host clock
    (``time.perf_counter``); no span reads the modelled serving clock.

Default is the ``NOOP`` singleton: ``enabled`` is False and every
metric/trace call is swallowed, so un-instrumented runs (tier-1 tests,
committed BENCH baselines) pay one attribute load + branch per
instrumentation site and nothing else. Instrument sites guard with
``if tel.enabled:`` before computing label values.

Metric naming follows Prometheus conventions —
``<subsystem>_<name>_<unit>[_total]`` with the subsystem one of
``scheduler`` / ``engine`` / ``runtime`` / ``control`` / ``router``
(+ per-replica ``replica_*`` gauges, and the ``kernel_grid_steps``
gauge of the GMM kernels' static schedule). The README's Observability
section tables the full taxonomy.
"""
from __future__ import annotations

import threading
import time
import weakref

from jax.profiler import StepTraceAnnotation, TraceAnnotation

from repro.obs.registry import TIME_BUCKETS, MetricsRegistry
from repro.obs.tracing import Tracer

# byte-ish histograms use wider buckets than latencies
BYTE_BUCKETS = (1e3, 1e4, 1e5, 1e6, 1e7, 1e8, 1e9)


class Telemetry:
    """Live telemetry: a registry (always) + a tracer (optional)."""

    enabled = True

    def __init__(self, registry: MetricsRegistry | None = None,
                 tracer: Tracer | None = None):
        self.registry = MetricsRegistry() if registry is None else registry
        self.tracer = tracer
        self._local = threading.local()     # per-thread open sections
        r = self.registry
        # ---------------------------------------------------- scheduler
        self.sched_admitted = r.counter(
            "scheduler_admitted_total",
            "requests admitted into the running batch")
        self.sched_rejected = r.counter(
            "scheduler_rejected_total",
            "requests rejected at admission control", labels=("reason",))
        self.sched_finished = r.counter(
            "scheduler_finished_total",
            "requests finished, by finish reason", labels=("reason",))
        self.sched_cancelled = r.counter(
            "scheduler_cancelled_total",
            "requests cancelled (client disconnect / replica failure)")
        self.sched_pending = r.gauge(
            "scheduler_pending", "requests waiting for a KV slot")
        self.sched_queue_delay = r.histogram(
            "scheduler_queue_delay_seconds",
            "host submit -> host admission delay")
        # ------------------------------------------------------- engine
        self.engine_steps = r.counter(
            "engine_steps_total", "engine iterations, by phase",
            labels=("phase",))
        self.engine_tokens = r.counter(
            "engine_tokens_total", "tokens generated")
        self.engine_step_seconds = r.histogram(
            "engine_step_seconds",
            "host wall time of one engine iteration (admission "
            "included), by phase",
            labels=("phase",))
        self.engine_host_sync = r.histogram(
            "engine_host_sync_seconds",
            "wall time blocked on the device->host token fetch")
        self.engine_occupancy = r.gauge(
            "engine_batch_occupancy",
            "active slots in the batched decode step")
        # ----------------------------------------------------- paged KV
        self.kv_blocks_used = r.gauge(
            "kv_blocks_used", "paged-KV pool blocks currently referenced")
        self.kv_blocks_free = r.gauge(
            "kv_blocks_free", "paged-KV pool blocks on the free list")
        self.kv_prefix_hits = r.counter(
            "kv_prefix_hits_total",
            "admissions that matched a cached prompt prefix")
        self.kv_prefix_tokens_saved = r.counter(
            "kv_prefix_tokens_saved_total",
            "prompt tokens served from the prefix cache (prefill FLOPs "
            "and KV writes skipped)")
        self.kv_cow_copies = r.counter(
            "kv_cow_copies_total",
            "shared blocks copied on first divergent write")
        # ----------------------------------------- expert runtime
        self.runtime_starts = r.counter(
            "runtime_replica_starts_total",
            "expert replica starts, by lifecycle kind "
            "(cold / warm / prewarmed)", labels=("kind",))
        self.runtime_transfers = r.counter(
            "runtime_transfers_total", "slot weight copies performed")
        self.runtime_bytes = r.counter(
            "runtime_bytes_moved_total",
            "bytes written into expert slot banks")
        self.runtime_rank_bytes = r.counter(
            "runtime_rank_bytes_total",
            "slot-bank bytes written per EP mesh rank", labels=("rank",))
        self.runtime_evictions = r.counter(
            "runtime_evictions_total", "keep-alive / plan evictions")
        self.runtime_overlap_copies = r.counter(
            "runtime_overlap_copies_total",
            "slot copies by overlap class (eligible hide under compute; "
            "exposed block the next dispatch)", labels=("kind",))
        self.runtime_resident = r.gauge(
            "runtime_resident_replicas",
            "expert replicas currently resident in slot banks")
        self.runtime_flush_seconds = r.histogram(
            "runtime_bank_flush_seconds",
            "host wall time to dispatch one slot-bank flush (the "
            "double-buffered scatters are enqueued, not awaited; their "
            "device time is the jit_bank_update program's)")
        # ------------------------------------------------------ control
        self.control_iterations = r.counter(
            "control_iterations_total",
            "control-plane iterations, by phase", labels=("phase",))
        self.control_dropped = r.counter(
            "control_dropped_tokens_total",
            "MoE capacity-dropped tokens, by phase", labels=("phase",))
        self.control_stragglers = r.counter(
            "control_stragglers_total",
            "layer iterations whose load imbalance flagged a straggler")
        self.control_l1_error = r.gauge(
            "control_pred_load_l1_error",
            "L1 error of predicted vs actual expert load, per layer "
            "(paper Fig. 11/12)", labels=("layer",))
        self.control_imbalance = r.gauge(
            "control_imbalance_factor",
            "max/mean expert load of the last iteration, per layer",
            labels=("layer",))
        self.control_load_max = r.gauge(
            "control_load_max",
            "max expert load of the last iteration, per layer",
            labels=("layer",))
        self.control_load_mean = r.gauge(
            "control_load_mean",
            "mean expert load of the last iteration, per layer",
            labels=("layer",))
        self.control_step_seconds = r.histogram(
            "control_step_seconds",
            "host wall time of one control-plane step (load fetch, "
            "scale, place)")
        # ------------------------------------------------------- router
        self.router_requests = r.counter(
            "router_requests_total",
            "gateway requests, by outcome", labels=("outcome",))
        self.router_scale_events = r.counter(
            "router_scale_events_total",
            "autoscaler decisions, by action", labels=("action",))
        self.router_replicas = r.gauge(
            "router_replicas", "live engine replicas behind the router")
        self.router_http_seconds = r.histogram(
            "router_http_request_seconds",
            "gateway HTTP request handling wall time, by route",
            labels=("route",))
        self.replica_pending = r.gauge(
            "replica_pending", "pending requests", labels=("replica",))
        self.replica_running = r.gauge(
            "replica_running", "running requests", labels=("replica",))
        self.replica_outstanding = r.gauge(
            "replica_outstanding_tokens",
            "token budget still owed", labels=("replica",))
        self.replica_queue_delay = r.gauge(
            "replica_queue_delay_seconds",
            "age of the oldest pending request", labels=("replica",))
        self.replica_gb_seconds = r.gauge(
            "replica_gb_seconds", "metered GB-s of residency",
            labels=("replica",))
        self.replica_healthy = r.gauge(
            "replica_healthy", "1 while the replica serves",
            labels=("replica",))
        # ------------------------------------------------------ kernels
        self.kernel_grid_steps = r.gauge(
            "kernel_grid_steps",
            "grid steps of one call of a grouped-GMM kernel, by kernel "
            "and capacity (static, recorded when the kernel is traced)",
            labels=("kernel", "capacity"))
        for (kernel, capacity), steps in _KERNEL_SCHEDULES.items():
            self._set_schedule(kernel, capacity, steps)
        _LIVE.add(self)

    def _set_schedule(self, kernel: str, capacity: int,
                      grid_steps: int) -> None:
        self.kernel_grid_steps.labels(
            kernel=kernel, capacity=capacity).set(grid_steps)

    # ------------------------------------------------------- tracing

    @property
    def tracing(self) -> bool:
        return self.tracer is not None

    def span(self, track: str, name: str, t0: float, t1: float,
             args: dict | None = None) -> None:
        if self.tracer is not None:
            self.tracer.span(track, name, t0, t1, args)

    def _open_sections(self) -> list:
        """Names of this thread's open traced sections, innermost last."""
        stack = getattr(self._local, "stack", None)
        if stack is None:
            stack = self._local.stack = []
        return stack

    def section(self, name: str, **args) -> "_Section":
        """Context manager timing one span of host work on the host
        clock: a profiler ``TraceAnnotation`` named `name` with `args`
        as its stats (a ``StepTraceAnnotation`` when `args` holds
        ``step_num``), and with a tracer attached the same span in the
        Chrome JSON on this thread's ``host/<thread>`` track, its
        enclosing section named as ``parent``. The entered section's
        ``seconds`` is its duration once it exits."""
        return _Section(self, name, args)

    def instant(self, track: str, name: str, t: float,
                args: dict | None = None) -> None:
        if self.tracer is not None:
            self.tracer.instant(track, name, t, args)


# Kernel schedules are facts of the process's traced programs, not of
# one engine: each is kept here and set on every live `Telemetry`.
_KERNEL_SCHEDULES: dict[tuple[str, int], int] = {}
_LIVE: "weakref.WeakSet[Telemetry]" = weakref.WeakSet()


def record_kernel_schedule(kernel: str, capacity: int,
                           grid_steps: int) -> None:
    """Record the static schedule of a grouped-GMM kernel as it is
    traced: its grid steps per call at `capacity` rows per slot, in the
    ``kernel_grid_steps`` gauge of every `Telemetry` alive now or made
    later. Costs nothing per step."""
    _KERNEL_SCHEDULES[(kernel, capacity)] = grid_steps
    for tel in list(_LIVE):
        tel._set_schedule(kernel, capacity, grid_steps)


class _Section:
    """One ``Telemetry.section`` (see there)."""

    __slots__ = ("tel", "name", "args", "ann", "ev", "t0", "seconds")

    def __init__(self, tel: Telemetry, name: str, args: dict):
        self.tel, self.name, self.args = tel, name, args
        self.ev = None
        self.seconds = 0.0

    def __enter__(self) -> "_Section":
        ann = StepTraceAnnotation if "step_num" in self.args \
            else TraceAnnotation
        self.ann = ann(self.name, **self.args)
        self.ann.__enter__()
        tracer = self.tel.tracer
        self.t0 = time.perf_counter()
        if tracer is not None:
            stack = self.tel._open_sections()
            th = threading.current_thread().name
            args = dict(self.args, parent=stack[-1]) if stack \
                else self.args
            self.ev = tracer.open_span(f"host/{th}", self.name, self.t0,
                                       args)
            stack.append(self.name)
        return self

    def __exit__(self, *exc) -> None:
        t1 = time.perf_counter()
        self.seconds = t1 - self.t0
        if self.ev is not None:
            self.tel.tracer.close_span(self.ev, t1)
            self.tel._open_sections().pop()
        self.ann.__exit__(*exc)


class _NoopSection:
    """The shared disabled section: enters and exits doing nothing."""

    seconds = 0.0

    def __enter__(self) -> "_NoopSection":
        return self

    def __exit__(self, *exc) -> None:
        pass


_NOOP_SECTION = _NoopSection()


class _NoopMetric:
    """Swallows every metric call (defensive: instrument sites guard on
    ``tel.enabled`` and should never reach these)."""

    def labels(self, **kv):
        return self

    def inc(self, amount: float = 1.0) -> None:
        pass

    def set(self, value: float) -> None:
        pass

    def dec(self, amount: float = 1.0) -> None:
        pass

    def observe(self, value: float) -> None:
        pass


_NOOP_METRIC = _NoopMetric()


class NullTelemetry:
    """The disabled default: no registry, no tracer, no overhead."""

    enabled = False
    tracing = False
    registry = None
    tracer = None

    def __getattr__(self, name):
        return _NOOP_METRIC

    def span(self, track, name, t0, t1, args=None) -> None:
        pass

    def instant(self, track, name, t, args=None) -> None:
        pass

    def section(self, name, **args) -> _NoopSection:
        return _NOOP_SECTION


NOOP = NullTelemetry()
