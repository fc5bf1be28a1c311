"""Reduction of a profiler trace (``.xplane.pb``) to the numbers the
benchmark reports: device busy time, the traced window, device time per
operation, and the device's idle gaps with what the host was doing in each.

The window is the interval of the benchmark's own ``bench.trace_window``
annotation, which it holds open from just after the profiler starts until
just before it stops, so the window and the device's operations are on one
clock.  Busy time is the union of the device operations' intervals inside
the window.  An idle gap of at least ``GAP_MIN_NS`` is put down to the host
event that overlaps it most (the benchmark's ``bench.*`` annotations, the
host's dispatch of a jitted function, ...); shorter gaps between operations
are summed under one label.  Of the host events that cover about as much of
a gap as the best, the shortest names it.
"""
from __future__ import annotations

import dataclasses
import gzip
import re

import numpy as np

__all__ = ["TraceSummary", "reduce_trace", "load_profile", "WINDOW_ANNOTATION"]

WINDOW_ANNOTATION = "bench.trace_window"
#: gaps shorter than this are op-to-op launch gaps, summed under one label
GAP_MIN_NS = 50_000
SHORT_GAPS = "op-to-op gaps under 50 us"
NO_HOST_EVENT = "no host event"
#: the device plane's lines of operations and of the programs they run in
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
#: an operation's event is named by its HLO text: "%name = shape opcode(..."
_HLO = re.compile(r"^(%?[\w.-]+) = (.*?) ([a-z][a-z0-9-]*)\(")
_SHAPE = re.compile(r"[a-z]+[0-9]*\[[0-9,]*\]")
#: control flow: their events span the operations of their bodies
CONTAINERS = ("while", "conditional", "call")


def op_label(text: str) -> tuple[str, str, str]:
    """(instruction name, opcode, first output shape) of an operation's
    event name."""
    m = _HLO.match(text)
    if not m:
        return text[:80], "", ""
    shape = _SHAPE.search(m.group(2))
    return m.group(1), m.group(3), shape.group(0) if shape else ""


@dataclasses.dataclass
class TraceSummary:
    window_s: float
    busy_s: float
    #: (program, HLO text of an operation) -> [events, device seconds]
    ops: dict
    #: idle seconds by what the host was doing
    idle_by_host: dict
    n_devices: int

    def op_seconds(self, match) -> tuple[int, float]:
        """(events, seconds) of the operations whose HLO text ``match``
        accepts."""
        n, s = 0, 0.0
        for (_, text), (cnt, sec) in self.ops.items():
            if match(text):
                n += cnt
                s += sec
        return n, s

    def top_ops(self, n: int = 10) -> list:
        """The operations that took most device time, control flow left
        out (its time is its body's), as
        ``[["program: name opcode shape", s], ...]``."""
        rows = []
        for (module, text), (_, sec) in self.ops.items():
            name, opcode, shape = op_label(text)
            if opcode not in CONTAINERS:
                rows.append([f"{module}: {name} {opcode} {shape}".strip(),
                             sec])
        return sorted(rows, key=lambda r: -r[1])[:n]

    def top_gaps(self, n: int = 10) -> list:
        rows = sorted(self.idle_by_host.items(), key=lambda kv: -kv[1])[:n]
        return [[label, float(sec)] for label, sec in rows]


def load_profile(path):
    """A ``ProfileData`` from an ``.xplane.pb`` file (``.gz`` allowed)."""
    from jax.profiler import ProfileData

    path = str(path)
    if path.endswith(".gz"):
        with gzip.open(path, "rb") as f:
            return ProfileData.from_serialized_xspace(f.read())
    return ProfileData.from_file(path)


def _merge(iv: np.ndarray) -> np.ndarray:
    """Union of [start, end) intervals, sorted, as an (m, 2) array."""
    if not len(iv):
        return iv.reshape(0, 2)
    iv = iv[np.argsort(iv[:, 0], kind="stable")]
    ends = np.maximum.accumulate(iv[:, 1])
    new = np.ones(len(iv), bool)
    new[1:] = iv[1:, 0] > ends[:-1]
    starts = iv[new, 0]
    idx = np.flatnonzero(new)
    last = np.append(idx[1:] - 1, len(iv) - 1)
    return np.stack([starts, ends[last]], axis=1)


def _device_planes(pd) -> list:
    return [p for p in pd.planes
            if p.name.startswith("/device:") and "TPU" in p.name
            and "SparseCore" not in p.name]


def reduce_trace(path) -> TraceSummary:
    pd = load_profile(path)
    host_ev = []
    window = None
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name == WINDOW_ANNOTATION:
                    window = (ev.start_ns, ev.start_ns + ev.duration_ns)
                elif ev.duration_ns > 0:
                    host_ev.append((ev.start_ns, ev.duration_ns, ev.name))
    if window is None:
        raise ValueError(f"no {WINDOW_ANNOTATION!r} annotation in the trace")
    w0, w1 = window

    devices = _device_planes(pd)
    if not devices:
        raise ValueError("no TPU device plane in the trace")
    ops: dict = {}
    busy_total = 0.0
    gaps_all = []
    for plane in devices:
        lines = {line.name: line for line in plane.lines}
        mods = sorted((ev.start_ns, ev.name.split("(", 1)[0])
                      for ev in lines[MODULES_LINE].events) \
            if MODULES_LINE in lines else []
        starts = np.array([m[0] for m in mods], np.float64)
        evs = [(ev.start_ns, ev.duration_ns, ev.name)
               for ev in (lines[OPS_LINE].events if OPS_LINE in lines
                          else ())]
        s = np.array([e[0] for e in evs], np.float64)
        e = s + np.array([e[1] for e in evs], np.float64)
        inside = np.flatnonzero((e > w0) & (s < w1))
        s, e = np.maximum(s[inside], w0), np.minimum(e[inside], w1)
        mod = np.searchsorted(starts, s, side="right") - 1
        for j, m, sec in zip(inside.tolist(), mod.tolist(),
                             ((e - s) * 1e-9).tolist()):
            rec = ops.setdefault((mods[m][1] if m >= 0 else "", evs[j][2]),
                                 [0, 0.0])
            rec[0] += 1
            rec[1] += sec
        iv = np.stack([s, e], axis=1)
        busy = _merge(iv)
        busy_total += float((busy[:, 1] - busy[:, 0]).sum()) * 1e-9
        edges = np.concatenate([[w0], busy.ravel(), [w1]]).reshape(-1, 2)
        gaps_all.append(edges[edges[:, 1] > edges[:, 0]])

    idle = _attribute(np.concatenate(gaps_all), host_ev)
    n = len(devices)
    return TraceSummary(
        window_s=(w1 - w0) * 1e-9,
        busy_s=busy_total / n,
        ops=ops,
        idle_by_host={k: v / n for k, v in idle.items()},
        n_devices=n,
    )


def _attribute(gaps: np.ndarray, host_ev: list) -> dict:
    out: dict = {}
    width = gaps[:, 1] - gaps[:, 0]
    short = width < GAP_MIN_NS
    if short.any():
        out[SHORT_GAPS] = float(width[short].sum()) * 1e-9
    if not host_ev:
        if (~short).any():
            out[NO_HOST_EVENT] = float(width[~short].sum()) * 1e-9
        return out
    hs = np.array([e[0] for e in host_ev], np.float64)
    he = hs + np.array([e[1] for e in host_ev], np.float64)
    names = [e[2] for e in host_ev]
    dur = he - hs
    for g0, g1 in gaps[~short]:
        ov = np.minimum(he, g1) - np.maximum(hs, g0)
        top = ov.max()
        if top <= 0:
            label = NO_HOST_EVENT
        else:
            # of the events that cover about as much of the gap as the
            # best, the shortest is the most specific
            near = np.flatnonzero(ov >= 0.9 * top)
            label = names[int(near[np.argmin(dur[near])])]
        out[label] = out.get(label, 0.0) + (g1 - g0) * 1e-9
    return out
