"""Wall-clock spans of the program's host work (DESIGN.md §12).

The sim-clock tracer (``repro.obs.trace``) explains a tuple's latency in
simulated time; this recorder explains where the HOST's wall time goes
while the engine advances that clock.  Each ``Engine`` owns one
``SpanRecorder`` (``Engine.spans``), off by default and turned on by
``Engine.enable_spans``.  The engine hands it to its ``Sim``, whose
``run_until`` then runs inside a ``stream.sim.run_until`` span and each
dispatched callback inside one of its own
(``stream.<owner>.<callback>``), and to every ``FusedPlane``, whose hot
path opens ``stream.fused.*`` spans around staging, dispatch,
device-to-host reads and admission.

A span, when on, does two things:

  * it enters a ``jax.profiler.TraceAnnotation`` of its name while a
    profiler session is collecting (``TraceMe.is_enabled``), so program
    spans land in the profiler's trace on the device ops' clock; with no
    session a TraceMe records nothing, so none is built;
  * it adds one to its name's count and its SELF time to the name's
    total: wall time inside the span less the time in spans nested
    inside it (``time.perf_counter_ns``).  Every span boundary charges
    the time since the previous boundary to the innermost open span, or
    to ``unspanned`` when none is open, so between two snapshots the
    self times plus the unspanned time add up to the wall time.

Off, the per-event path pays nothing: ``Sim.run_until`` tests the flag
once per call, and each plane site one flag test per batch.  Planes
built outside an engine share ``NULL_SPANS``, which stays off.

Stdlib-only at import (like the registry); jax is imported when a
recorder is enabled.
"""
from __future__ import annotations

import heapq
import time
from typing import Any, Callable, Dict, List, Optional


class SpanRecorder:
    """Per-name counts and self times of nested wall-clock spans."""

    def __init__(self, clock: Callable[[], int] = time.perf_counter_ns):
        self.enabled = False
        self.clock = clock
        self.unspanned_ns = 0
        self._stats: Dict[str, list] = {}     # [count, self ns, name]
        self._stack: List[list] = []          # open spans' stats
        self._anns: List[tuple] = []          # (depth, open annotation)
        self._last = clock()                  # the latest boundary
        self._callbacks: Dict[Any, list] = {}
        self._annotation = None

    def enable(self) -> None:
        if self is NULL_SPANS:
            raise RuntimeError("NULL_SPANS is shared and stays off")
        import jax
        self._annotation = jax.profiler.TraceAnnotation
        self._last = self.clock()
        self.enabled = True

    # ------------------------------------------------------------ spans
    def _charge(self) -> int:
        """Charge the time since the last boundary; returns now."""
        t = self.clock()
        if self._stack:
            self._stack[-1][1] += t - self._last
        else:
            self.unspanned_ns += t - self._last
        self._last = t
        return t

    def enter(self, name: str) -> None:
        self._open(self._stat(name))

    def _open(self, stat: list) -> None:
        self._charge()
        self._stack.append(stat)
        ann = self._annotation
        if ann is not None and ann.is_enabled():
            ann = ann(stat[2])
            ann.__enter__()
            self._anns.append((len(self._stack), ann))

    def exit(self) -> None:
        self._charge()
        anns = self._anns
        if anns and anns[-1][0] == len(self._stack):
            anns.pop()[1].__exit__(None, None, None)
        self._stack.pop()[0] += 1

    def switch(self, name: str) -> None:
        """Close the innermost span and open its next sibling."""
        self.exit()
        self.enter(name)

    def dispatch(self, sim, t_end: float) -> None:
        """``Sim.run_until``'s loop with spans: the due events run inside
        one ``stream.sim.run_until`` span (whose self time is the heap
        work between callbacks), each callback inside its own
        ``stream.<owner>.<callback>`` span."""
        self.enter("stream.sim.run_until")
        stack = self._stack
        loop = stack[-1]
        depth = len(stack)
        try:
            if self._annotation.is_enabled():
                while sim._heap and sim._heap[0][0] <= t_end:
                    t, _, fn, args = heapq.heappop(sim._heap)
                    sim.t = t
                    self._open(self._callback_stat(fn))
                    fn(*args)
                    self.exit()
            else:
                # the per-event fast path: enter/exit inlined
                clock, callbacks = self.clock, self._callbacks
                pop = heapq.heappop
                while sim._heap and sim._heap[0][0] <= t_end:
                    t, _, fn, args = pop(sim._heap)
                    sim.t = t
                    try:
                        stat = callbacks[fn]
                    except KeyError:
                        stat = self._callback_stat(fn)
                    now = clock()
                    loop[1] += now - self._last
                    self._last = now
                    stack.append(stat)
                    fn(*args)
                    now = clock()
                    stat[1] += now - self._last
                    stat[0] += 1
                    self._last = now
                    stack.pop()
        except BaseException:
            # close what the exception left open, the loop's span too
            while len(stack) >= depth:
                self.exit()
            raise
        self.exit()

    def _callback_stat(self, fn: Callable) -> list:
        stat = self._callbacks.get(fn)
        if stat is None:
            stat = self._callbacks[fn] = self._stat(callback_name(fn))
        return stat

    def _stat(self, name: str) -> list:
        stat = self._stats.get(name)
        if stat is None:
            stat = self._stats[name] = [0, 0, name]
        return stat

    # --------------------------------------------------------- readout
    @property
    def counts(self) -> Dict[str, int]:
        return {n: s[0] for n, s in self._stats.items()}

    def snapshot(self) -> Dict[str, Any]:
        """Totals so far, taken as a span boundary and stamped with the
        wall clock: ``{"wall_ns", "unspanned_ns", "spans": {name:
        [count, self_ns]}}``.  Between two snapshots the self times and
        the unspanned time add up to the wall time."""
        t = self._charge()
        return {"wall_ns": t, "unspanned_ns": self.unspanned_ns,
                "spans": {n: s[:2] for n, s in self._stats.items()}}


NULL_SPANS = SpanRecorder()


def callback_name(fn: Callable) -> str:
    """``stream.<owner>.<callback>`` for a scheduled callable: the owner
    is the bound method's operator name, else its lower-cased class
    (``channel``, ``engine``, ...).  A wrapper that replaced an
    operator's method (a closure over the original bound method, as a
    benchmark's recorder installs) takes the wrapped method's owner, so
    a span never carries a closure's qualified name."""
    name = getattr(fn, "__name__", type(fn).__name__).strip("<>_")
    owner = getattr(fn, "__self__", None)
    if owner is None:
        inner = _wrapped_method(fn)
        if inner is not None:
            owner = inner.__self__
    label = getattr(owner, "name", None)
    if not isinstance(label, str):
        label = type(owner).__name__.lower() if owner is not None \
            else "call"
    return f"stream.{label}.{name}"


def _wrapped_method(fn: Callable) -> Optional[Any]:
    """The bound method of the same name a closure calls, if any."""
    name = getattr(fn, "__name__", None)
    for cell in getattr(fn, "__closure__", None) or ():
        try:
            v = cell.cell_contents
        except ValueError:                # empty cell
            continue
        if hasattr(v, "__self__") and getattr(v, "__name__", None) == name:
            return v
    return None
