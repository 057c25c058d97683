"""Reduction of a `jax.profiler` trace to device busy time, idle gaps and ops.

Busy time is the union of the intervals in which an operation runs on a
device ("XLA Ops" line of each device plane), clipped to the traced
window. The window runs from the start of the first harness annotation
(the traffic's label around each call: `pass`, `request`, `study`) to
the end of the last. Idle time is the rest of the window; each idle
stretch is cut where the host's activity changes and each piece is
named by it: the innermost program span then open (the program's
`repro.obs` spans, moved onto the trace's clock), else the harness
annotation, else "outside".
"""
from __future__ import annotations

import glob
import os
from typing import Iterable, Optional, Sequence

OPS_LINE = "XLA Ops"


def op_name(text: str) -> str:
    """"%fusion.12 = f32[...] fusion(...)" -> "fusion.12"."""
    return text.split(" = ", 1)[0].lstrip("%")


def find_xplane(logdir: str) -> str:
    paths = sorted(glob.glob(os.path.join(logdir, "plugins", "profile", "*",
                                          "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no trace under {logdir}")
    return paths[-1]


def union(intervals: Iterable[Sequence[float]]) -> "list[list[float]]":
    """Merged, sorted [start, end] intervals."""
    out: "list[list[float]]" = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def clip(intervals, lo: float, hi: float) -> "list[list[float]]":
    return [[max(s, lo), min(e, hi)] for s, e in intervals
            if min(e, hi) > max(s, lo)]


def total(intervals) -> float:
    return sum(e - s for s, e in intervals)


def gaps(busy, lo: float, hi: float) -> "list[list[float]]":
    """The stretches of [lo, hi] that no busy interval covers."""
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append([t, min(s, hi)])
        t = max(t, e)
        if t >= hi:
            break
    if t < hi:
        out.append([t, hi])
    return [g for g in out if g[1] > g[0]]


class Trace:
    """Device op events and host annotations of one trace, in nanoseconds."""

    def __init__(self, device_ops: "dict[str, list[tuple[str, float, float]]]",
                 annotations: "list[tuple[str, float, float]]"):
        self.device_ops = device_ops      # plane -> [(name, start, end)]
        self.annotations = sorted(annotations, key=lambda a: a[1])

    @classmethod
    def load(cls, path: str, labels: "Sequence[str]") -> "Trace":
        """Device ops and the host annotations named `labels` of a trace."""
        from jax.profiler import ProfileData

        pd = ProfileData.from_file(path)
        device_ops, annotations = {}, []
        for plane in pd.planes:
            if plane.name.startswith("/device:") and "TPU" in plane.name:
                for line in plane.lines:
                    if line.name == OPS_LINE:
                        device_ops[plane.name] = [
                            (op_name(e.name), e.start_ns, e.end_ns)
                            for e in line.events
                        ]
            elif plane.name.startswith("/host:"):
                for line in plane.lines:
                    for e in line.events:
                        if e.name in labels:
                            annotations.append((e.name, e.start_ns, e.end_ns))
        return cls(device_ops, annotations)

    def window(self) -> "tuple[float, float]":
        if not self.annotations:
            raise ValueError("the trace holds no harness annotation")
        return (self.annotations[0][1],
                max(a[2] for a in self.annotations))

    def busy(self, plane: str) -> "list[list[float]]":
        lo, hi = self.window()
        return clip(union((s, e) for _, s, e in self.device_ops[plane]), lo, hi)

    def summary(self, host_spans: "Optional[list[tuple[str, float, float, int]]]" = None,
                top: int = 10) -> dict:
        """busy_s and window_s (mean over device planes), idle share, the
        device ops that took most time and idle time by host activity."""
        lo, hi = self.window()
        window_s = (hi - lo) / 1e9
        planes = sorted(self.device_ops)
        if not planes:
            return {"busy_s": 0.0, "window_s": window_s, "devices": 0,
                    "device_ops": [], "idle_gaps": []}
        busy_s, op_time, idle = [], {}, {}
        segments = self.activity(host_spans or [], lo, hi)
        for plane in planes:
            busy = self.busy(plane)
            busy_s.append(total(busy) / 1e9)
            for name, s, e in self.device_ops[plane]:
                d = min(e, hi) - max(s, lo)
                if d > 0:
                    op_time[name] = op_time.get(name, 0.0) + d / 1e9 / len(planes)
            for name, d in split(gaps(busy, lo, hi), segments):
                idle[name] = idle.get(name, 0.0) + d / 1e9 / len(planes)
        busy_mean = sum(busy_s) / len(busy_s)
        return {
            "busy_s": busy_mean,
            "window_s": window_s,
            "devices": len(planes),
            "idle_pct": 100.0 * (1.0 - busy_mean / window_s),
            "device_ops": sorted(op_time.items(), key=lambda kv: -kv[1])[:top],
            "idle_gaps": sorted(idle.items(), key=lambda kv: -kv[1])[:top],
        }

    def activity(self, host_spans, lo: float, hi: float):
        """[(start, end, name)]: what the host was doing, from lo to hi, as
        the innermost program span, else the harness annotation, else
        "outside"."""
        items = sorted([(s, e, d, n) for n, s, e, d in host_spans]
                       + [(s, e, -1, n) for n, s, e in self.annotations])
        bounds = sorted({lo, hi} | {min(max(x, lo), hi)
                                    for s, e, _, _ in items for x in (s, e)})
        out, active, i = [], [], 0
        for a, b in zip(bounds, bounds[1:]):
            while i < len(items) and items[i][0] <= a:
                active.append(items[i])
                i += 1
            active = [it for it in active if it[1] > a]
            name = max(active, key=lambda it: it[2])[3] if active else "outside"
            if out and out[-1][2] == name and out[-1][1] == a:
                out[-1][1] = b
            else:
                out.append([a, b, name])
        return out

    def clock_offset(self, host_marks: "list[tuple[str, float]]") -> float:
        """Trace ns minus host perf_counter ns, from the harness's own
        annotations whose start the host also recorded (in order)."""
        starts = [a[1] for a in self.annotations]
        n = min(len(starts), len(host_marks))
        if n == 0:
            raise ValueError("no annotation to align the clocks by")
        return sum(starts[i] - host_marks[i][1] for i in range(n)) / n


def split(gap_list, segments):
    """(name, length) of each piece of the gaps, cut at the segments' edges;
    both lists sorted and non-overlapping."""
    j = 0
    for g0, g1 in gap_list:
        while j < len(segments) and segments[j][1] <= g0:
            j += 1
        k = j
        while k < len(segments) and segments[k][0] < g1:
            d = min(g1, segments[k][1]) - max(g0, segments[k][0])
            if d > 0:
                yield segments[k][2], d
            k += 1


def spans_on_trace(spans, offset_ns: float):
    """Program spans as (name, start, end, depth) on the trace's clock;
    "group[3]" and "solve_chunk[run]" keep the part before the bracket."""
    return [(sp.name.split("[")[0], sp.t_start * 1e9 + offset_ns,
             sp.t_end * 1e9 + offset_ns, sp.depth) for sp in spans]
