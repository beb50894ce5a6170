"""From a profiler trace (``.xplane.pb``) to what the per-layer readers read.

Device time is the ``XLA Ops`` line of each ``/device:TPU:<n>`` plane (the
operations that occupy the core; they nest, a ``while`` holds its body, so
busy time is the union of their intervals and the breakdown is by self time).
Host spans are the benchmark's own ``TraceAnnotation``s (``bench.op`` around
one op, ``bench.record``/``bench.force`` inside an op kind). Both are on the
trace's one clock. The traced window runs from the first ``bench.op``'s start
to the last one's end; everything is clipped to it.
"""

from __future__ import annotations

import glob
import os
import re

import numpy as np

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
_OPCODE = re.compile(r"([a-z][a-z0-9_\-]*)\(")


def short_name(hlo: str) -> str:
    """``'%fusion.1 = (bf16[..]) fusion(f32[..] %x), kind=kLoop'`` -> ``'fusion.1:fusion'``."""
    lhs, sep, rhs = hlo.partition(" = ")
    if not sep:
        return hlo[:60]
    m = _OPCODE.search(rhs)
    return f"{lhs.lstrip('%')}:{m.group(1) if m else '?'}"[:80]


def union_length(starts: np.ndarray, ends: np.ndarray) -> float:
    """Total length covered by the intervals (they may nest or overlap)."""
    merged = merge(starts, ends)
    return float((merged[1] - merged[0]).sum())


def merge(starts: np.ndarray, ends: np.ndarray):
    """Disjoint, sorted (starts, ends) covering the same points."""
    if len(starts) == 0:
        return np.zeros(0), np.zeros(0)
    order = np.argsort(starts, kind="stable")
    s, e = np.asarray(starts, float)[order], np.asarray(ends, float)[order]
    reach = np.maximum.accumulate(e)
    new = np.ones(len(s), bool)
    new[1:] = s[1:] > reach[:-1]
    first = np.flatnonzero(new)
    last = np.append(first[1:] - 1, len(s) - 1)
    return s[first], reach[last]


def clip(starts, ends, lo: float, hi: float):
    s, e = np.clip(starts, lo, hi), np.clip(ends, lo, hi)
    keep = e > s
    return s[keep], e[keep]


def covered(merged, lo: np.ndarray, hi: np.ndarray) -> np.ndarray:
    """Length of each [lo, hi] that the disjoint sorted intervals cover."""
    s, e = merged
    if len(s) == 0:
        return np.zeros(len(lo))
    cum = np.concatenate([[0.0], np.cumsum(e - s)])

    def upto(t):  # covered length left of t
        i = np.searchsorted(s, t, side="right")  # intervals that start at or before t
        j = np.maximum(i - 1, 0)
        return np.where(i > 0, cum[j] + np.minimum(t, e[j]) - s[j], 0.0)

    return upto(np.asarray(hi, float)) - upto(np.asarray(lo, float))


def self_times(starts, ends, names) -> dict:
    """Per name, the time its events ran with no nested event running."""
    order = sorted(range(len(starts)), key=lambda i: (starts[i], -(ends[i] - starts[i])))
    out, stack = {}, []  # stack of [end, name, self]
    for i in order:
        while stack and stack[-1][0] <= starts[i]:
            _, name, own = stack.pop()
            out[name] = out.get(name, 0.0) + own
        dur = ends[i] - starts[i]
        if stack:
            stack[-1][2] -= dur
        stack.append([ends[i], names[i], dur])
    for _, name, own in stack:
        out[name] = out.get(name, 0.0) + own
    return out


class Trace:
    def __init__(self, devices: dict, spans: dict):
        """``devices``: ordinal -> (starts, ends, names) in seconds;
        ``spans``: name -> (starts, ends) of the benchmark's host spans."""
        self.spans = {k: (np.asarray(v[0], float), np.asarray(v[1], float)) for k, v in spans.items()}
        ops = self.spans.get("bench.op", (np.zeros(0), np.zeros(0)))
        if len(ops[0]) == 0:
            raise ValueError("the trace holds no bench.op span: nothing was traced")
        self.lo, self.hi = float(ops[0].min()), float(ops[1].max())
        self.window_s = self.hi - self.lo
        self.n_ops = int(len(ops[0]))
        self.devices = {}
        for d, (s, e, names) in devices.items():
            s, e = np.asarray(s, float), np.asarray(e, float)
            keep = (e > self.lo) & (s < self.hi)
            self.devices[d] = (np.clip(s[keep], self.lo, self.hi), np.clip(e[keep], self.lo, self.hi),
                               [n for n, k in zip(names, keep) if k])
        if not self.devices or not any(len(v[0]) for v in self.devices.values()):
            raise ValueError("no operation ran on a device inside the traced window")
        self.busy_by_device = {d: union_length(s, e) for d, (s, e, _) in self.devices.items()}
        self.busy_s = float(np.mean(list(self.busy_by_device.values())))
        self.busiest = max(self.busy_by_device, key=self.busy_by_device.get)

    # -- what the readers ask for ------------------------------------
    def idle_pct(self) -> float:
        return 100.0 * (1.0 - self.busy_by_device[self.busiest] / self.window_s)

    def busy_in_ops_per_op(self) -> float:
        """Device-busy seconds inside the op spans, per op, busiest device."""
        s, e, _ = self.devices[self.busiest]
        ops = self.spans["bench.op"]
        return float(covered(merge(s, e), ops[0], ops[1]).sum()) / self.n_ops

    def span_mean_s(self, name: str) -> float | None:
        if name not in self.spans or not len(self.spans[name][0]):
            return None
        s, e = clip(*self.spans[name], self.lo, self.hi)
        return float((e - s).sum() / len(s)) if len(s) else None

    def breakdown(self) -> dict:
        s, e, names = self.devices[self.busiest]
        own = self_times(list(s), list(e), [short_name(n) for n in names])
        device_ops = sorted(own.items(), key=lambda kv: -kv[1])[:10]
        gs, ge = self.gaps()
        in_op = covered(merge(*self.spans["bench.op"]), gs, ge)
        gaps = {"bench.between_ops": float(((ge - gs) - in_op).sum())}
        inner = 0.0
        for name in sorted(self.spans):  # the op kind's spans lie inside bench.op, side by side
            if name != "bench.op":
                gaps[name] = float(covered(merge(*self.spans[name]), gs, ge).sum())
                inner += gaps[name]
        gaps["bench.op_other"] = float(in_op.sum()) - inner
        idle = sorted(((k, v) for k, v in gaps.items() if v > 0), key=lambda kv: -kv[1])[:10]
        return {"device_ops": [[k, v] for k, v in device_ops], "idle_gaps": [[k, v] for k, v in idle]}

    def gaps(self):
        """Idle intervals of the busiest device inside the window."""
        ms, me = merge(*self.devices[self.busiest][:2])
        gs = np.concatenate([[self.lo], me])
        ge = np.concatenate([ms, [self.hi]])
        keep = ge > gs
        return gs[keep], ge[keep]


def from_profile(path: str) -> Trace:
    import jax

    data = jax.profiler.ProfileData.from_file(path)
    devices, spans = {}, {}
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            for line in plane.lines:
                if line.name == "XLA Ops":
                    ev = [(e.start_ns * 1e-9, (e.start_ns + e.duration_ns) * 1e-9, e.name) for e in line.events]
                    devices[int(m.group(1))] = tuple(zip(*ev)) if ev else ((), (), ())
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith("bench."):
                        s, en = spans.setdefault(e.name, ([], []))
                        s.append(e.start_ns * 1e-9)
                        en.append((e.start_ns + e.duration_ns) * 1e-9)
    return Trace(devices, spans)


def load(directory: str) -> Trace:
    found = sorted(glob.glob(os.path.join(directory, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {directory}")
    return from_profile(found[-1])
