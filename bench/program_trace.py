"""The program's own spans and device programs in a traced window.

`bench/trace_reduce.py` keeps the device operations and the harness's
`bench.*` spans, whose extent is the traced window. This adds, from the
same `.xplane.pb`:

- program spans: the host events named `engine.*`, `control.*` or
  `runtime.*` (the serving stack's `Telemetry.section`s), with their
  args (`phase`, `rows`, ...);
- device programs: the events of the `XLA Modules` line of each device,
  one per execution, named `jit_<function>(<hash>)`.

The window stays the `bench.*` spans' extent: program spans reaching
outside it move no number, they only name what lies inside it. A
program without these spans or names (an older commit) leaves every
reading here empty, and the readers return nothing.
"""
from __future__ import annotations

import glob
from pathlib import Path

import numpy as np

from bench.trace_reduce import DEVICE_PREFIX, Trace, _clip, _union

PROGRAM_PREFIXES = ("engine.", "control.", "runtime.")
MODULES_LINE = "XLA Modules"


def module_name(event: str) -> str:
    """`jit_engine_step(1234)` -> `jit_engine_step`."""
    return event.split("(", 1)[0]


class ProgramTrace(Trace):
    """A `Trace` with the program's spans, a list of (name, start, end,
    args), and the device programs, device index -> list of (module,
    start, end); times in ns on the trace's clock."""

    def __init__(self, ops: dict, spans: list, program=(), modules=None):
        super().__init__(ops, spans)
        self._busy_of: dict = {}
        self.program = sorted(program, key=lambda s: s[1])
        self.modules = {d: sorted(evs, key=lambda m: m[1])
                        for d, evs in (modules or {}).items()}

    @classmethod
    def from_events(cls, ops: dict, spans: list, program=(),
                    modules=None) -> "ProgramTrace":
        base = Trace.from_events(ops, spans)
        return cls(base.ops, base.spans, program, modules)

    @classmethod
    def over(cls, trace: Trace, pd, num_devices: int) -> "ProgramTrace":
        """The program spans and device programs of the profile `pd`
        (`jax.profiler.ProfileData`), over its already reduced
        `trace`."""
        program, modules = [], {}
        for plane in pd.planes:
            if plane.name.startswith(DEVICE_PREFIX):
                dev = int(plane.name[len(DEVICE_PREFIX):].split()[0])
                if dev >= num_devices:
                    continue
                for line in plane.lines:
                    if line.name == MODULES_LINE:
                        modules.setdefault(dev, []).extend(
                            (module_name(e.name), e.start_ns, e.end_ns)
                            for e in line.events)
            elif plane.name.startswith("/host:"):
                for line in plane.lines:
                    for e in line.events:
                        if e.name.startswith(PROGRAM_PREFIXES):
                            program.append((e.name, e.start_ns, e.end_ns,
                                            dict(e.stats)))
        return cls(trace.ops, trace.spans, program, modules)

    @classmethod
    def of(cls, run) -> "ProgramTrace | None":
        """The program trace of a per-layer reader's `run`: its `trace`
        itself where that is one (tests), else read once from the
        profile the harness wrote and kept on the run."""
        if run.trace is None:
            return None
        if isinstance(run.trace, cls):
            return run.trace
        pt = getattr(run, "program_trace", None)
        if pt is None:
            from jax.profiler import ProfileData

            from bench.harness import TRACE_DIR
            files = sorted(glob.glob(str(Path(TRACE_DIR) / "**" /
                                         "*.xplane.pb"), recursive=True))
            if not files:
                return None
            pt = run.program_trace = cls.over(
                run.trace, ProfileData.from_file(files[-1]),
                len(run.trace.ops))
        return pt

    def _busy(self, dev: int) -> np.ndarray:
        if dev not in self._busy_of:
            self._busy_of[dev] = super()._busy(dev)
        return self._busy_of[dev]

    # ------------------------------------------------------- readings

    def sections(self, name: str, **args) -> list:
        """(start, end, args) of the program spans named `name` whose
        args include `args` and that start inside the window."""
        return [(s, e, a) for n, s, e, a in self.program
                if n == name and self.t0 <= s <= self.t1
                and all(a.get(k) == v for k, v in args.items())]

    def idle_inside(self, intervals) -> float:
        """Device seconds inside the union of `intervals` (ns, clipped
        to the window) in which no operation runs, averaged over the
        devices."""
        iv = np.asarray([(s, e) for s, e in intervals], np.float64)
        cover = _union(_clip(iv.reshape(-1, 2), self.t0, self.t1))
        if not self.ops or len(cover) == 0:
            return 0.0
        span = float(np.sum(cover[:, 1] - cover[:, 0]))
        tot = []
        for d in sorted(self.ops):
            busy = self._busy(d)
            inside = sum(float(np.sum(np.diff(_clip(busy, s, e), axis=1)))
                         for s, e in cover)
            tot.append(span - inside)
        return 1e-9 * float(np.mean(tot))

    def module_events(self, module: str, dev: int) -> np.ndarray:
        """(n, 2) [start, end) of `module`'s executions on `dev`."""
        iv = [(s, e) for m, s, e in self.modules.get(dev, ())
              if m == module]
        return np.asarray(iv, np.float64).reshape(-1, 2)

    def has_module(self, module: str) -> bool:
        return any(m == module for evs in self.modules.values()
                   for m, _, _ in evs)

    def module_seconds(self, module: str) -> float:
        """Device seconds of `module`'s executions inside the window,
        averaged over the devices."""
        if not self.modules:
            return 0.0
        tot = [float(np.sum(np.diff(_clip(self.module_events(module, d),
                                          self.t0, self.t1), axis=1)))
               for d in sorted(self.modules)]
        return 1e-9 * float(np.mean(tot))

    def dispatched(self, module: str, section: str, dev: int) -> list:
        """Each `section` span inside the window, in order, with the
        execution of `module` it launched on `dev`: the first one that
        starts after the span does and before the next such span starts.
        Returns (span start, span end, args, module start, module end)
        for the spans matched; a span with no execution there (one the
        profiler lost) is left out, and shifts no later match."""
        runs = self.module_events(module, dev)
        spans = self.sections(section)
        out = []
        for k, (s, e, a) in enumerate(spans):
            nxt = spans[k + 1][0] if k + 1 < len(spans) else np.inf
            i = int(np.searchsorted(runs[:, 0], s))
            if i < len(runs) and runs[i, 0] < nxt:
                out.append((s, e, a, runs[i, 0], runs[i, 1]))
        return out

    # ------------------------------------------------------ breakdown

    def _label(self, t: float) -> str:
        """The innermost span, harness or program, covering time t
        ('host' if none)."""
        best, width = "host", np.inf
        for n, s, e in self.spans:
            if s <= t <= e and e - s < width:
                best, width = n, e - s
        for n, s, e, _ in self.program:
            if s > t:
                break
            if t <= e and e - s < width:
                best, width = n, e - s
        return best

    def breakdown(self, top: int = 10) -> dict:
        """`Trace.breakdown`, each idle gap named by the innermost span
        of either kind covering its middle, plus the device seconds of
        each device program in the window (first device)."""
        out = super().breakdown(top)
        if self.modules:
            dev = min(self.modules)
            names = sorted({m for m, _, _ in self.modules[dev]})
            secs = sorted(((m, float(np.sum(np.diff(_clip(
                self.module_events(m, dev), self.t0, self.t1), axis=1)))
                * 1e-9) for m in names), key=lambda kv: -kv[1])
            out["device_programs"] = [[m, v] for m, v in secs[:top]]
        return out
