"""Reduction of a JAX profiler trace to device metrics.

Reads the `.xplane.pb` that `jax.profiler` writes, with nothing but JAX
(`jax.profiler.ProfileData`):

- device operations: the events of the `XLA Ops` line of each
  `/device:TPU:<n>` plane;
- harness spans: the host events named `bench.*` (the `TraceAnnotation`s
  the harness puts around its calls into each layer), on the same clock.

The traced window runs from the first harness span's start to the last
one's end. Busy time is the union of the device operations' intervals
inside it; the idle share is one minus busy over the window.
"""
from __future__ import annotations

import glob
from pathlib import Path

import numpy as np

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
SPAN_PREFIX = "bench."


def _union(iv: np.ndarray) -> np.ndarray:
    """Sorted, merged copy of (n, 2) [start, end) intervals."""
    if len(iv) == 0:
        return iv.reshape(0, 2)
    iv = iv[np.argsort(iv[:, 0], kind="stable")]
    reach = np.maximum.accumulate(iv[:, 1])
    new = np.ones(len(iv), bool)
    new[1:] = iv[1:, 0] > reach[:-1]
    first = np.flatnonzero(new)
    last = np.append(first[1:] - 1, len(iv) - 1)
    return np.stack([iv[first, 0], reach[last]], 1)


def _clip(iv: np.ndarray, lo: float, hi: float) -> np.ndarray:
    iv = np.clip(iv, lo, hi)
    return iv[iv[:, 1] > iv[:, 0]]


def short_name(op: str) -> str:
    """`%name = type[shape]...` of an XLA op, without its operands."""
    lhs, _, rhs = op.partition(" = ")
    return f"{lhs.lstrip('%')} {rhs.split(' ', 1)[0].split('{', 1)[0]}"


def kernel_rank(op: str):
    """Rank of the array a kernel call (`custom-call`) returns, None for
    any other op or a tuple result. The program's two Pallas kernel
    families differ in it: grouped GMMs return (E, C, F), paged decode
    attention (B, KV, G, hd)."""
    lhs, _, rhs = op.partition(" = ")
    if " custom-call(" not in rhs or rhs.startswith("("):
        return None
    shape = rhs.split(" ", 1)[0].split("{", 1)[0]
    if "[" not in shape:
        return None
    dims = shape[shape.index("[") + 1:shape.index("]")]
    return len(dims.split(",")) if dims else 0


class Trace:
    """Device operations and harness spans of one traced window. Times
    are nanoseconds on the trace's clock. `ops` maps a device index to
    (names, name index, start, end): one entry per operation, the names
    held once each."""

    def __init__(self, ops: dict, spans: list):
        self.ops = ops
        self.spans = spans          # list of (name, start_ns, end_ns)
        if spans:
            self.t0 = min(s for _, s, _ in spans)
            self.t1 = max(e for _, _, e in spans)
        else:
            self.t0 = self.t1 = 0.0

    @classmethod
    def from_events(cls, ops: dict, spans: list) -> "Trace":
        """From plain lists: device index -> [(name, start, end), ...]."""
        packed = {}
        for d, evs in ops.items():
            names, idx = {}, []
            for n, _, _ in evs:
                idx.append(names.setdefault(n, len(names)))
            packed[d] = (list(names), np.asarray(idx, np.int64),
                         np.asarray([e[1] for e in evs], np.float64),
                         np.asarray([e[2] for e in evs], np.float64))
        return cls(packed, spans)

    @classmethod
    def from_xspace(cls, pd, num_devices: int) -> "Trace":
        ops, spans = {}, []
        for plane in pd.planes:
            if plane.name.startswith(DEVICE_PREFIX):
                dev = int(plane.name[len(DEVICE_PREFIX):].split()[0])
                if dev >= num_devices:
                    continue
                names, idx, st, en = {}, [], [], []
                for line in plane.lines:
                    if line.name == OPS_LINE:
                        for e in line.events:
                            idx.append(names.setdefault(e.name, len(names)))
                            st.append(e.start_ns)
                            en.append(e.end_ns)
                ops[dev] = (list(names), np.asarray(idx, np.int64),
                            np.asarray(st, np.float64),
                            np.asarray(en, np.float64))
            elif plane.name.startswith("/host:"):
                for line in plane.lines:
                    for e in line.events:
                        if e.name.startswith(SPAN_PREFIX):
                            spans.append((e.name, e.start_ns, e.end_ns))
        return cls(ops, spans)

    @classmethod
    def load(cls, trace_dir, num_devices: int) -> "Trace":
        from jax.profiler import ProfileData
        files = sorted(glob.glob(str(Path(trace_dir) / "**" /
                                     "*.xplane.pb"), recursive=True))
        if not files:
            raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
        return cls.from_xspace(ProfileData.from_file(files[-1]),
                               num_devices)

    # ------------------------------------------------------- reductions

    def window_s(self) -> float:
        return (self.t1 - self.t0) * 1e-9

    def _intervals(self, dev: int, match=None, invert=False) -> np.ndarray:
        names, idx, s, e = self.ops[dev]
        if match is not None:
            hit = np.asarray([bool(match(n)) != invert for n in names],
                             bool)
            keep = hit[idx] if len(idx) else np.zeros(0, bool)
            s, e = s[keep], e[keep]
        return _clip(np.stack([s, e], 1), self.t0, self.t1)

    def _busy(self, dev: int) -> np.ndarray:
        return _union(self._intervals(dev))

    def busy_s(self) -> float:
        """Seconds in which an operation ran, averaged over the devices."""
        if not self.ops:
            return 0.0
        tot = [float(np.sum(b[:, 1] - b[:, 0])) for b in
               (self._busy(d) for d in sorted(self.ops))]
        return 1e-9 * float(np.mean(tot))

    def idle_share(self) -> float:
        w = self.window_s()
        return 1.0 - self.busy_s() / w if w > 0 else float("nan")

    def op_seconds(self, match) -> float:
        """Device seconds of the operations whose name `match` accepts,
        inside the window, averaged over the devices."""
        if not self.ops:
            return 0.0
        tot = [float(np.sum(np.diff(self._intervals(d, match), axis=1)))
               for d in sorted(self.ops)]
        return 1e-9 * float(np.mean(tot))

    def exposed_seconds(self, match) -> float:
        """Device seconds of the operations `match` accepts during which
        no other operation runs on that device, averaged over devices."""
        if not self.ops:
            return 0.0
        tot = []
        for d in sorted(self.ops):
            mine = _union(self._intervals(d, match))
            other = _union(self._intervals(d, match, invert=True))
            exp = 0.0
            for s, e in mine:
                cov = _clip(other, s, e)
                exp += (e - s) - float(np.sum(cov[:, 1] - cov[:, 0]))
            tot.append(exp)
        return 1e-9 * float(np.mean(tot))

    def _label(self, t: float) -> str:
        """The innermost harness span covering time t ('host' if none)."""
        best, width = "host", np.inf
        for n, s, e in self.spans:
            if s <= t <= e and e - s < width:
                best, width = n, e - s
        return best

    def breakdown(self, top: int = 10) -> dict:
        """The device operations that took most time (summed by short
        name on the first device) and the longest idle gaps there, each
        named by the harness span that covers the gap's middle."""
        if not self.ops:
            return {"device_ops": [], "idle_gaps": []}
        dev = min(self.ops)
        names, idx, s, e = self.ops[dev]
        dur = np.clip(np.minimum(e, self.t1) - np.maximum(s, self.t0), 0,
                      None)
        per = np.bincount(idx, weights=dur, minlength=len(names))
        by_name: dict = {}
        for n, d in zip(names, per):
            k = short_name(n)
            by_name[k] = by_name.get(k, 0.0) + d * 1e-9
        ops = sorted(by_name.items(), key=lambda kv: -kv[1])[:top]
        b = self._busy(dev)
        edges = np.concatenate([[self.t0], b.ravel(), [self.t1]])
        gaps = [[self._label((s + e) / 2), (e - s) * 1e-9]
                for s, e in sorted(zip(edges[0::2], edges[1::2]),
                                   key=lambda g: g[0] - g[1])[:top]
                if e > s]
        return {"device_ops": [[n, v] for n, v in ops], "idle_gaps": gaps}
