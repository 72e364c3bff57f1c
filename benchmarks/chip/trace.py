"""Reduce a profiler trace (``.xplane.pb``) to the benchmark's numbers.

Only this file reads traces, so every PR computes device time, idle time
and the forward's executions the same way.  What it reads:

* device operations: the events of the ``XLA Ops`` line of every
  ``/device:TPU:<n>`` plane; busy time is the union of their intervals,
  averaged over the devices that ran anything;
* forward executions: the events of the ``XLA Modules`` lines whose name
  starts with the served forward's module name (``jit_forward``);
* host spans: the benchmark's own ``TraceAnnotation``s on the host plane
  (``apply_fn``, with the call, its bucket and its real rows, and
  ``feeder_release``);
* the traced window: from the first to the last event of the host spans
  and the device operations.

Idle gaps are the stretches of the window in which no device operation
ran; each is put down to the host span that overlaps it most, or to
``none``.
"""
from __future__ import annotations

import dataclasses
import glob
import os

import numpy as np

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PLANE = "/host:CPU"
FORWARD_MODULE = "jit_forward"
HOST_SPANS = ("apply_fn", "feeder_release")


@dataclasses.dataclass
class Span:
    name: str
    start_ns: float
    end_ns: float
    stats: dict


@dataclasses.dataclass
class TraceSummary:
    window_ns: tuple[float, float]
    busy_ns: float                       # mean over devices that ran ops
    n_devices: int
    op_ns: dict[str, float]              # device time by op name
    forward: list[Span]                  # forward executions, by start
    host: list[Span]                     # benchmark host spans, by start
    gaps: list[tuple[float, float, str]]  # idle gaps with host label

    @property
    def window_s(self) -> float:
        return (self.window_ns[1] - self.window_ns[0]) * 1e-9

    @property
    def busy_s(self) -> float:
        return self.busy_ns * 1e-9

    def top_ops(self, k: int = 10) -> list[list]:
        items = sorted(self.op_ns.items(), key=lambda kv: -kv[1])[:k]
        return [[name, ns * 1e-9] for name, ns in items]

    def idle_by_label(self, k: int = 10) -> list[list]:
        """Idle seconds by what the host was doing, most first."""
        tot: dict[str, float] = {}
        cnt: dict[str, int] = {}
        for a, b, label in self.gaps:
            tot[label] = tot.get(label, 0.0) + (b - a)
            cnt[label] = cnt.get(label, 0) + 1
        items = sorted(tot.items(), key=lambda kv: -kv[1])[:k]
        return [[f"{label} ({cnt[label]} gaps)", float(ns) * 1e-9]
                for label, ns in items]

    def paired_forward(self) -> list[tuple[Span, Span]]:
        """(``apply_fn`` span, forward execution) pairs, in order.

        One device runs its programs in the order they were dispatched,
        so the n-th execution that started after the first traced
        ``apply_fn`` belongs to the n-th traced ``apply_fn``; executions
        of calls dispatched before the trace began, and calls whose
        execution the trace did not see, are left out."""
        calls = [s for s in self.host if s.name == "apply_fn"]
        if not calls:
            return []
        execs = [e for e in self.forward if e.start_ns >= calls[0].start_ns]
        return list(zip(calls, execs))


def find_trace(log_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return paths[-1]


def _union(intervals: np.ndarray) -> np.ndarray:
    """Merged, sorted (start, end) intervals."""
    if not len(intervals):
        return intervals.reshape(0, 2)
    iv = intervals[np.argsort(intervals[:, 0])]
    out = [list(iv[0])]
    for a, b in iv[1:]:
        if a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return np.asarray(out, float)


def _labeller(host: list[Span]):
    """gap -> the name of the host span that overlaps it most, or none."""
    starts = np.asarray([s.start_ns for s in host], float)
    longest = max((s.end_ns - s.start_ns for s in host), default=0.0)

    def label(a: float, b: float) -> str:
        best, name = 0.0, "none"
        lo = int(np.searchsorted(starts, a - longest))
        hi = int(np.searchsorted(starts, b))
        for s in host[lo:hi]:
            ov = min(b, s.end_ns) - max(a, s.start_ns)
            if ov > best:
                best, name = ov, s.name
        return name
    return label


def reduce(path: str) -> TraceSummary:
    """Read the trace at ``path`` (a file or a profiler log directory)."""
    from jax.profiler import ProfileData
    if os.path.isdir(path):
        path = find_trace(path)
    data = ProfileData.from_file(path)
    op_ns: dict[str, float] = {}
    per_device: list[np.ndarray] = []
    forward: list[Span] = []
    host: list[Span] = []
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            iv = []
            for line in plane.lines:
                if line.name == OPS_LINE:
                    for e in line.events:
                        iv.append((e.start_ns, e.start_ns + e.duration_ns))
                        op_ns[e.name] = op_ns.get(e.name, 0.0) + e.duration_ns
                elif line.name == MODULES_LINE:
                    forward += [Span(e.name, e.start_ns,
                                     e.start_ns + e.duration_ns, {})
                                for e in line.events
                                if e.name.startswith(FORWARD_MODULE)]
            if iv:
                per_device.append(_union(np.asarray(iv, float)))
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                host += [Span(e.name, e.start_ns, e.start_ns + e.duration_ns,
                              dict(e.stats))
                         for e in line.events if e.name in HOST_SPANS]
    if not per_device:
        raise ValueError(f"no device operations in {path}")
    forward.sort(key=lambda s: s.start_ns)
    host.sort(key=lambda s: s.start_ns)
    edges = [iv[0, 0] for iv in per_device] + [s.start_ns for s in host]
    ends = [iv[-1, 1] for iv in per_device] + [s.end_ns for s in host]
    window = (float(min(edges)), float(max(ends)))
    busy = float(np.mean([np.sum(iv[:, 1] - iv[:, 0]) for iv in per_device]))
    # gaps on the first device: one chip here; a multi-chip cell would
    # label each device's gaps alike
    iv = per_device[0]
    starts = np.concatenate([[window[0]], iv[:, 1]])
    stops = np.concatenate([iv[:, 0], [window[1]]])
    keep = stops > starts
    label = _labeller(host)
    gaps = [(a, b, label(a, b)) for a, b in zip(starts[keep], stops[keep])]
    return TraceSummary(window, busy, len(per_device), op_ns, forward, host,
                        gaps)
