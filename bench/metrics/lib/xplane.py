"""Reduction of a JAX profiler trace (``.xplane.pb``) to what the
per-layer metrics read.

* busy: the union of the intervals in which an operation ran on a
  device (the device plane's ``XLA Ops`` line), inside the traced window;
* per-op and per-program device time: durations of the ``XLA Ops`` and
  ``XLA Modules`` events, summed by name;
* idle gaps: the stretches of the window in which no operation ran,
  each named by the innermost benchmark host span (``bench.*``, recorded
  with ``jax.profiler.TraceAnnotation``) open at its midpoint, or
  ``host: engine`` when none was open.

The traced window is the host span ``bench.window`` when the trace has
one, else the extent of the device events.
"""
from __future__ import annotations

import bisect
import glob
import os
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.window"
NO_SPAN = "host: engine"

Interval = Tuple[int, int]            # [start_ns, end_ns)


def union(intervals: Sequence[Interval]) -> List[Interval]:
    """Merge overlapping or touching intervals."""
    out: List[List[int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals: Sequence[Interval], lo: int, hi: int) -> List[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def gaps(merged: Sequence[Interval], lo: int, hi: int) -> List[Interval]:
    """The stretches of [lo, hi) that no merged interval covers."""
    out, t = [], lo
    for s, e in merged:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def innermost(spans: Sequence[Tuple[int, int, str]], t: int) -> str:
    """Name of the shortest span that contains ``t``."""
    best, best_len = NO_SPAN, None
    for s, e, name in spans:
        if s <= t < e and (best_len is None or e - s < best_len):
            best, best_len = name, e - s
    return best


@dataclass
class Summary:
    window: Interval
    busy_ns: int
    device_planes: List[str]
    ops: Dict[str, List[float]] = field(default_factory=dict)
    # (program, op) -> [count, seconds]: each op under the program
    # (``XLA Modules`` event) that was running when it started
    op_in_module: Dict[Tuple[str, str], List[float]] = \
        field(default_factory=dict)
    modules: Dict[str, List[float]] = field(default_factory=dict)
    idle: Dict[str, float] = field(default_factory=dict)
    gap_count: int = 0

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9

    @property
    def busy_s(self) -> float:
        """Busy seconds, averaged over the device planes traced."""
        return self.busy_ns * 1e-9 / max(1, len(self.device_planes))

    def op_in_module_time(self, module_match, op_match
                          ) -> Tuple[int, float]:
        n, s = 0, 0.0
        for (mod, name), (c, t) in self.op_in_module.items():
            if module_match(mod) and op_match(name):
                n += c
                s += t
        return n, s

    def module_time(self, match) -> Tuple[int, float]:
        n, s = 0, 0.0
        for name, (c, t) in self.modules.items():
            if match(name):
                n += c
                s += t
        return n, s

    def breakdown(self, top: int = 10) -> Dict[str, list]:
        ops = sorted(self.ops.items(), key=lambda kv: -kv[1][1])[:top]
        idle = sorted(self.idle.items(), key=lambda kv: -kv[1])[:top]
        return {"device_ops": [[n, t] for n, (_, t) in ops],
                "idle_gaps": [[n, t] for n, t in idle]}


def reduce(planes) -> Optional[Summary]:
    """``planes``: an iterable of objects with ``name`` and ``lines``,
    each line with ``name`` and ``events`` (``name``, ``start_ns``,
    ``duration_ns``) — what ``jax.profiler.ProfileData`` gives."""
    dev_ops: List[Tuple[int, int, str]] = []
    dev_mods: List[Tuple[int, int, str]] = []
    host: List[Tuple[int, int, str]] = []
    dev_names = []
    for plane in planes:
        is_dev = plane.name.startswith("/device:") and \
            "CPU" not in plane.name
        for line in plane.lines:
            if is_dev and line.name in (OPS_LINE, MODULES_LINE):
                into = dev_ops if line.name == OPS_LINE else dev_mods
                for ev in line.events:
                    s = int(ev.start_ns)
                    into.append((s, s + int(ev.duration_ns), ev.name))
            elif not is_dev:
                for ev in line.events:
                    if ev.name.startswith(SPAN_PREFIX):
                        s = int(ev.start_ns)
                        host.append((s, s + int(ev.duration_ns), ev.name))
        if is_dev and any(ln.name == OPS_LINE for ln in plane.lines):
            dev_names.append(plane.name)
    win = [(s, e) for s, e, n in host if n == WINDOW_SPAN]
    if win:
        lo, hi = win[0]
    elif dev_ops:
        lo = min(s for s, _, _ in dev_ops)
        hi = max(e for _, e, _ in dev_ops)
    else:
        return None
    ops_in = [(s, e, n) for s, e, n in dev_ops if e > lo and s < hi]
    mods_in = [(s, e, n) for s, e, n in dev_mods if e > lo and s < hi]
    merged = union(clip([(s, e) for s, e, _ in ops_in], lo, hi))
    summary = Summary((lo, hi), sum(e - s for s, e in merged), dev_names)
    for into, evs in ((summary.ops, ops_in), (summary.modules, mods_in)):
        for s, e, n in evs:
            c = into.setdefault(n, [0, 0.0])
            c[0] += 1
            c[1] += (e - s) * 1e-9
    mods_in.sort()
    mod_starts = [s for s, _, _ in mods_in]
    for s, e, n in ops_in:
        k = bisect.bisect_right(mod_starts, s) - 1
        mod = mods_in[k][2] if k >= 0 and mods_in[k][1] >= s else ""
        c = summary.op_in_module.setdefault((mod, n), [0, 0.0])
        c[0] += 1
        c[1] += (e - s) * 1e-9
    spans = [h for h in host if h[2] != WINDOW_SPAN]
    spans.sort()
    starts = [s for s, _, _ in spans]
    for gs, ge in gaps(merged, lo, hi):
        mid = (gs + ge) // 2
        # only spans that start before the midpoint can contain it
        k = bisect.bisect_right(starts, mid)
        label = innermost(spans[max(0, k - 256):k], mid)
        summary.idle[label] = summary.idle.get(label, 0.0) + (ge - gs) * 1e-9
        summary.gap_count += 1
    return summary


def find_trace(trace_dir: str) -> Optional[str]:
    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return paths[-1] if paths else None


def summarize(trace_dir: str) -> Optional[Summary]:
    path = find_trace(trace_dir)
    if path is None:
        return None
    from jax.profiler import ProfileData
    return reduce(ProfileData.from_file(path).planes)
