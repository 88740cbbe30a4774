"""Session windows: data-driven gaps, pane MERGING, moving-deadline hints
(DESIGN.md §15).

Tumbling/sliding windows (windows.py) know their fire time the moment a
tuple is assigned; a SESSION window does not — every tuple extends its
session's end to ``ts + gap``, and a tuple landing between two sessions
MERGES them into one.  That makes sessions the honest adversary for the
paper's deadline-aware TAC: the fire deadline a hint promised keeps
moving, so the lookahead must RE-HINT on every extension/merge and the
cache must ``renew`` the pane's timestamp rather than trust the first
deadline it saw (core/tac.py).

Three pieces:

  * ``SessionWindowAssigner`` — per-key dynamic session registry logic.
    ``fold`` is the one canonical merge rule, shared verbatim by the
    stateful operator and the lookahead so both mirror the same session
    structure (lockstep hints).  Session ids are CANONICAL: the surviving
    ``wid`` is always derived from the earliest event timestamp in the
    session, so the final registry is independent of per-key arrival
    order — the property the chaos oracle (streaming/chaos.py) and the
    Hypothesis merge tests (tests/test_sessions.py) rely on.
  * ``SessionWindowedOp`` — pane state keyed ``WindowKey(key, wid)`` on
    the inherited keyed machinery.  A merge runs as a two-step protocol
    THROUGH that machinery (so pane reads park/prefetch exactly like any
    keyed access): the absorbed pane receives a ``_MergeDrain`` message
    that takes its accumulator and purges it, then self-delivers a
    ``_MergeAbsorb`` carrying the state into the surviving pane, where
    ``merge_fn`` combines the two accumulators.  A bridging tuple
    therefore never loses either side's state, even when one side is
    parked on a backend fetch mid-merge.  Accesses to a pane with
    absorbs still in flight are HELD and resume once they land, so a
    fire always reads the merged state.  On the fused plane (§14) data
    and fire accesses are device lanes and the merge steps run on the
    host between batches, through the same methods.
  * ``SessionLookaheadOp`` — mirrors the registry per key and emits
    deadline hints carrying the session's CURRENT end; on extension or
    merge it re-hints unconditionally (bypassing admission, like the
    fire burst) so a resident pane's TAC deadline is renewed in place.

Late tuples follow the windows.py policies: beyond the lateness horizon
they drop; inside it, ``update`` re-opens the fired session (Aion-style
late-side update) and the re-fired emit carries the refreshed
accumulator, while ``drop`` discards them.
"""
from __future__ import annotations

import heapq
import math
from typing import Any, Callable, Dict, List, Optional, Tuple

from repro.streaming.engine import HINT_COST, StatefulOp, _IOReq
from repro.streaming.events import Hint, Tuple_, WindowKey
from repro.streaming.fused import Lane
from repro.streaming.windows import (FIRE, WindowedLookaheadOp,
                                     WindowedStatefulOp)

# session ids quantize the creating event timestamp to microseconds: two
# distinct sessions of one key are separated by > gap >> 1µs, so ids
# never collide, and ``start_of`` inverts the id for horizon checks
_WID_SCALE = 1e6


class _MergeDrain:
    """Self-addressed message to an ABSORBED pane: take its accumulator,
    purge the pane, and forward the state to the surviving pane."""
    __slots__ = ("surv",)

    def __init__(self, surv: int):
        self.surv = surv

    def __repr__(self):
        return f"<DRAIN->{self.surv}>"


class _MergeAbsorb:
    """Self-addressed message to a SURVIVING pane: combine the absorbed
    pane's accumulator into its own via ``merge_fn``."""
    __slots__ = ("state",)

    def __init__(self, state: Any):
        self.state = state

    def __repr__(self):
        return f"<ABSORB {self.state!r}>"


def _new_session(ts: float, wid: int, gap: float) -> dict:
    return {"start": ts, "end": ts + gap, "wid": wid, "fired": False}


class SessionWindowAssigner:
    """Data-driven session membership with a fixed inactivity ``gap``.

    A tuple at event time ``ts`` spans ``[ts, ts + gap)``; it joins every
    session that interval overlaps, merging them when it bridges more
    than one.  ``wid_of(ts)`` derives the session id from the earliest
    event timestamp, and ``fold`` keeps that canonical: when a tuple
    extends a session's start backwards, the EARLIER timestamp's id wins
    and the old pane is absorbed — so the final id of any session equals
    ``wid_of(min ts in the session)`` regardless of arrival order.
    """

    def __init__(self, gap: float):
        if gap <= 0:
            raise ValueError(f"need gap > 0, got {gap}")
        self.gap = gap

    def wid_of(self, ts: float) -> int:
        return int(math.floor(ts * _WID_SCALE + 0.5))

    def start_of(self, wid: int) -> float:
        return wid / _WID_SCALE

    def end(self, wid: int) -> float:
        """Minimal possible fire deadline of a session created at this
        id's timestamp (extensions only move the true end later).  Kept
        for WindowedStatefulOp API compatibility; the session registry
        holds the live end."""
        return self.start_of(wid) + self.gap

    def overlapping(self, sessions: List[dict], ts: float) -> List[dict]:
        hi = ts + self.gap
        return [s for s in sessions if ts < s["end"] and hi > s["start"]]

    def survivor(self, sessions: List[dict], ts: float):
        """Pure preview of ``fold``: the sessions a tuple at ``ts``
        overlaps, in (start, wid) order, and the one that survives the
        fold — None when the tuple starts a session of its own (nothing
        overlaps, or it PREDATES every overlapping session, so the
        canonical id is its own)."""
        ov = self.overlapping(sessions, ts)
        ov.sort(key=lambda s: (s["start"], s["wid"]))
        if not ov or ts < ov[0]["start"]:
            return ov, None
        return ov, ov[0]

    def survivor_wid(self, sessions: List[dict], ts: float) -> int:
        """The id of the session a tuple at ``ts`` will fold into."""
        _, surv = self.survivor(sessions, ts)
        return self.wid_of(ts) if surv is None else surv["wid"]

    def fold(self, sessions: List[dict], ts: float):
        """Fold one tuple into a key's session list (mutating it).

        Returns ``(sess, absorbed, extended, created)``: the surviving
        session, the sessions merged into it (removed from the list),
        whether the surviving end moved, and whether the survivor is a
        brand-new session dict.
        """
        ov, surv = self.survivor(sessions, ts)
        if surv is None:
            surv = _new_session(ts, self.wid_of(ts), self.gap)
            sessions.append(surv)
            if not ov:
                return surv, [], True, True
            absorbed, created = ov, True
        else:
            absorbed, created = ov[1:], False
        old_end = surv["end"]
        surv["start"] = min(surv["start"], ts)
        surv["end"] = max([surv["end"], ts + self.gap]
                          + [a["end"] for a in absorbed])
        for a in absorbed:
            sessions.remove(a)
        return surv, absorbed, created or surv["end"] > old_end, created


class SessionWindowedOp(WindowedStatefulOp):
    """Keyed session-window aggregation with pane merging (DESIGN.md §15).

    ``merge_fn(a, b)`` combines two pane accumulators (either may be
    ``None``); it must be commutative/associative so the merged result is
    independent of merge order — the session-structure canonicalization
    (``SessionWindowAssigner.fold``) guarantees the same for ids.

    Results are a function of the operator's input order alone, not of
    how fast it drains its queue or when fetches land — the property
    that lets the fused plane (§14) and the interpreted one agree, and a
    plain reference check either:

      * registry decisions (fold, fire, purge) happen where the input
        puts them: a fold when its tuple is dequeued, the fires and the
        registry purge of a watermark when it is;
      * a watermark's FIREs take its place in the queue (as windows.py
        does), and every access to a pane keeps its order through
        parking, so a FIRE reads exactly the tuples folded before its
        watermark and always emits, carrying its own end and id;
      * a merge's drain and absorb take the bridging tuple's place; an
        access to a pane whose session still waits on absorbs (its drain
        parked on a fetch) is HELD and resumes, in order, once they
        have landed;
      * a purged session leaves the registry at its watermark; its pane
        is dropped once no parked or held access needs it.

    Fires come off a lazy per-subtask heap of ``(end, base, wid)``
    candidates pushed on every extension; stale entries (extended,
    absorbed, or already fired since) are skipped on pop.  With
    ``latency_from_end`` a fire carries its session's end as
    ``ingest_t``, so sink latency runs from the end to delivery.

    Fused (a ``FusedSpec`` that aggregates): data and fire accesses ride
    device lanes; a merge's drain and absorb are control steps that run
    here, through the plane's host API, at a batch boundary.
    """

    def __init__(self, engine, name, parallelism,
                 assigner: SessionWindowAssigner,
                 agg_fn: Callable[[Tuple_, Any], Any],
                 emit_fn: Callable[[Any, int, float, Any], Any],
                 backend_model, cache_capacity: int,
                 merge_fn: Optional[Callable[[Any, Any], Any]] = None,
                 **kw):
        super().__init__(engine, name, parallelism, assigner, agg_fn,
                         emit_fn, backend_model, cache_capacity, **kw)
        if self.fused_spec is not None and self.fused_spec.kind == "read":
            raise ValueError("session panes aggregate: a read-only fused "
                             "spec has no update to fold (DESIGN.md §15)")
        self.merge_fn = merge_fn or (lambda a, b: b if a is None else a)
        # base -> [session dict], per subtask (durable: rides snapshots)
        self.sess: List[Dict[Any, List[dict]]] = \
            [dict() for _ in range(parallelism)]
        # (base, absorbed wid) -> {"surv": wid, "drained": bool}: where
        # traffic for a drained pane goes (entries are a few bytes each
        # and kept for the run — see _resolve)
        self.absorbed: List[Dict[Tuple[Any, int], dict]] = \
            [dict() for _ in range(parallelism)]
        # pane key -> absorbs in flight into it (durable), and the
        # accesses held until they have landed
        self.pending: List[Dict[WindowKey, int]] = \
            [dict() for _ in range(parallelism)]
        self.held: List[Dict[WindowKey, List[Tuple_]]] = \
            [dict() for _ in range(parallelism)]
        self.fire_heap: List[List] = [[] for _ in range(parallelism)]
        self.purge_heap: List[List] = [[] for _ in range(parallelism)]
        self.sessions_created = 0
        self.sessions_merged = 0
        self.sessions_reopened = 0
        self.fires_superseded = 0
        self.fires_absorbed = 0
        self.merge_drains = 0
        self.merge_absorbs = 0
        self.merge_holds = 0
        self.merge_settles = 0
        self._span_fold = f"stream.{name}.session_fold"
        self._span_merge = f"stream.{name}.merge"

    # ----------------------------------------------------------- registry
    def _find(self, sub: int, base: Any, wid: int) -> Optional[dict]:
        for s in self.sess[sub].get(base, ()):
            if s["wid"] == wid:
                return s
        return None

    def _resolve(self, sub: int, wk: WindowKey) -> WindowKey:
        """Chain-resolve a pane key through the DRAINED absorbed panes:
        replayed traffic (migration, recovery) addressed to a pane whose
        state moved lands on the surviving pane.  An absorbed pane not
        drained yet still holds its state and takes its traffic."""
        amap = self.absorbed[sub]
        wid = wk.wid
        for _ in range(64):               # defensive: merges form a DAG
            entry = amap.get((wk.base, wid))
            if entry is None or not entry["drained"]:
                break
            wid = entry["surv"]
        return wk if wid == wk.wid else WindowKey(wk.base, wid)

    def _arm_fire(self, sub: int, sess: dict, base: Any) -> None:
        heapq.heappush(self.fire_heap[sub],
                       (sess["end"], base, sess["wid"]))

    def _hold(self, sub: int, tup: Tuple_) -> bool:
        """Hold pane access ``tup`` while its session waits on absorbs:
        it resumes after them (``_apply``), so a held FIRE reads the
        merged state and later accesses stay behind it."""
        if tup.key not in self.pending[sub]:
            return False
        self.merge_holds += 1
        self.held[sub].setdefault(tup.key, []).append(tup)
        return True

    def _session_fold(self, sub: int, tup: Tuple_):
        """Fold base tuple ``tup`` into its key's sessions: counts,
        re-opens and fire arming.  Returns ``(pane access, survivor,
        absorbed sessions)``, or None when the tuple drops as late.
        The caller makes the access, then ``_merge`` starts the drains
        (one implementation for both planes)."""
        spans = self.engine.spans
        if spans.enabled:
            spans.enter(self._span_fold)
        base, ts = tup.key, tup.ts
        sessions = self.sess[sub].get(base)
        ov = self.assigner.overlapping(sessions or (), ts)
        if (not ov and ts + self.assigner.gap + self.allowed_lateness
                < self.wm[sub]) or (self.late_policy == "drop"
                                    and any(s["fired"] for s in ov)):
            # beyond any horizon (unjoinable), or it would touch a fired
            # session under the drop policy
            self.late_dropped += 1
            self._trace_absorbed(tup.trace)
            if spans.enabled:
                spans.exit()
            return None
        if sessions is None:
            sessions = self.sess[sub][base] = []
        sess, absorbed, extended, created = self.assigner.fold(sessions, ts)
        if created:
            self.sessions_created += 1
        elif extended:
            self.fires_superseded += 1    # the fire deadline moved later
        reopen = sess["fired"] or any(a["fired"] for a in absorbed)
        if reopen:
            # Aion-style late-side re-open: the refreshed session
            # re-fires at its (possibly extended) end
            sess["fired"] = False
            self.sessions_reopened += 1
        if extended or reopen:
            self._arm_fire(sub, sess, base)
        if spans.enabled:
            spans.exit()
        return (Tuple_(ts, WindowKey(base, sess["wid"]), tup.payload,
                       tup.size, tup.ingest_t, trace=tup.trace),
                sess, absorbed)

    def _merge(self, sub: int, ptup: Tuple_, sess: dict,
               absorbed: List[dict]) -> None:
        """Two-step merge through the keyed machinery: each absorbed
        pane gets a ``_MergeDrain`` (its read parks/prefetches like any
        access), which forwards its state to the survivor as a
        ``_MergeAbsorb``.  The drains take the bridging tuple's place in
        the queue; until the absorbs land, the survivor's accesses hold
        (``_hold``)."""
        if not absorbed:
            return
        base = ptup.key.base
        drains = []
        for a in absorbed:
            self.sessions_merged += 1
            self.merge_drains += 1
            if not a["fired"]:
                self.fires_absorbed += 1  # its fire folds into the survivor
            self.absorbed[sub][(base, a["wid"])] = {"surv": sess["wid"],
                                                    "drained": False}
            drains.append(Tuple_(ptup.ts, WindowKey(base, a["wid"]),
                                 _MergeDrain(sess["wid"]), 32,
                                 ptup.ingest_t))
        wk = ptup.key
        self.pending[sub][wk] = self.pending[sub].get(wk, 0) + len(drains)
        self.queues[sub].extendleft(reversed(drains))

    # ------------------------------------------------------------ data path
    def _on_data(self, sub: int, tup: Tuple_) -> float:
        if isinstance(tup.key, WindowKey):
            # pane-addressed traffic: a watermark's FIRE, a merge step,
            # or a replayed pane access
            if isinstance(tup.payload, (_MergeDrain, _MergeAbsorb)):
                return self._merge_step(sub, tup)
            if tup.payload is not FIRE:
                wk = self._resolve(sub, tup.key)
                if wk is not tup.key:
                    tup = Tuple_(tup.ts, wk, tup.payload, tup.size,
                                 tup.ingest_t, trace=tup.trace)
            if self._hold(sub, tup):
                return 5e-7
            return StatefulOp._on_data(self, sub, tup)
        folded = self._session_fold(sub, tup)
        if folded is None:
            return 5e-7
        ptup, sess, absorbed = folded
        svc = 5e-7 if self._hold(sub, ptup) \
            else StatefulOp._on_data(self, sub, ptup)
        self._merge(sub, ptup, sess, absorbed)
        return svc

    def _merge_step(self, sub: int, tup: Tuple_) -> float:
        """A drain or absorb, through the keyed machinery on either
        plane: on the fused plane its read is a host-shadow read, which
        settles the outstanding device step early when that step wrote
        the pane (counted in ``merge_settles``)."""
        spans = self.engine.spans
        if spans.enabled:
            spans.enter(self._span_merge)
        cache = self.caches[sub]
        settles = getattr(cache, "forced_settles", 0)
        if isinstance(tup.payload, _MergeAbsorb):
            wk = self._resolve(sub, tup.key)
            if wk is not tup.key:
                tup = Tuple_(tup.ts, wk, tup.payload, tup.size,
                             tup.ingest_t)
            svc = StatefulOp._on_data(self, sub, tup)
        elif self._hold(sub, tup):        # a drain: the pane's order holds
            svc = 5e-7
        else:
            svc = StatefulOp._on_data(self, sub, tup)
        if getattr(cache, "forced_settles", 0) > settles:
            self.merge_settles += 1
        if spans.enabled:
            spans.exit()
        return svc

    def _apply(self, sub: int, tup: Tuple_, state: Any) -> float:
        wk: WindowKey = tup.key
        base, wid = wk.base, wk.wid
        if isinstance(tup.payload, _MergeDrain):
            # absorbed pane: lift its accumulator, purge it, forward
            entry = self.absorbed[sub].get((base, wid))
            if entry is not None:
                entry["drained"] = True
            self.caches[sub].drop(wk)
            self.backends[sub].delete(wk)
            self.panes_purged += 1
            self.queues[sub].appendleft(Tuple_(
                tup.ts, WindowKey(base, tup.payload.surv),
                _MergeAbsorb(state), 32, tup.ingest_t))
            return self.service_time
        if isinstance(tup.payload, _MergeAbsorb):
            self.merge_absorbs += 1
            acc = self.merge_fn(state, tup.payload.state)
            if acc is not state:
                self.caches[sub].write(wk, acc, tup.ts,
                                       size=self.state_size)
                self._io_kick(sub)
            left = self.pending[sub].get(wk, 0) - 1
            if left > 0:
                self.pending[sub][wk] = left
            elif self.pending[sub].pop(wk, None) is not None:
                held = self.held[sub].pop(wk, None)
                if held:
                    # the held accesses resume in order, ahead of the
                    # queue (as parked tuples do)
                    self.ready[sub].extend(held)
                    self._kick(sub)
                else:
                    self._purge_if_due(sub, wk)
            return self.service_time
        if tup.payload is FIRE:
            self._emit_fire(sub, tup, state)
            return self.service_time
        # plain pane data: a drained pane's state moved to its survivor
        wk2 = self._resolve(sub, wk)
        if wk2 is not wk:
            self.deliver_batch(sub, [Tuple_(
                tup.ts, wk2, tup.payload, tup.size, tup.ingest_t,
                trace=tup.trace)])
            return self.service_time
        acc = self.agg_fn(tup, state)
        if acc is not state:
            self.caches[sub].write(wk, acc, tup.ts, size=self.state_size)
            self._io_kick(sub)
        self._trace_absorbed(tup.trace)   # folded into the pane
        return self.service_time

    def _emit_fire(self, sub: int, tup: Tuple_, state: Any) -> None:
        """A FIRE read its pane's ``state``: emit the session's result
        with the end and id it had at its watermark (``tup.ts``,
        ``tup.key``).  Shared by both planes: the fused plane calls it
        for a fire lane with the device's value."""
        wk: WindowKey = tup.key
        entry = self.absorbed[sub].get((wk.base, wk.wid))
        if entry is not None and entry["drained"]:
            # replayed after its pane was drained: the survivor holds
            # the state and fires it
            self._trace_absorbed(tup.trace)
            return
        payload = self.emit_fn(wk.base, wk.wid, tup.ts, state)
        self.fires += 1
        if self.engine.record_events:
            self.engine.log_event("fire", op=self.name, wid=wk.wid)
        if payload is not None:
            self.outputs += 1
            self.emit(sub, Tuple_(tup.ts, wk.base, payload, self.out_size,
                                  tup.ingest_t, trace=tup.trace))

    def _fused_prospect(self, sub: int, tup: Tuple_):
        """The pane a tuple's lane will touch (a base tuple's survivor,
        previewed without folding), or None for a merge step, which runs
        on the host between batches."""
        key = tup.key
        if not isinstance(key, WindowKey):
            return (WindowKey(key, self.assigner.survivor_wid(
                self.sess[sub].get(key, ()), tup.ts)),), False
        if isinstance(tup.payload, (_MergeDrain, _MergeAbsorb)):
            return None
        if tup.payload is FIRE:
            return (key,), True
        return (self._resolve(sub, key),), False

    def _fused_expand(self, sub: int, tup: Tuple_, keys):
        """Lanes of one tuple, through the decisions ``_on_data`` makes:
        the fold, the hold, the merge.  ``keys`` is the prospect's."""
        spec = self.fused_spec
        if isinstance(tup.key, WindowKey):
            fire = tup.payload is FIRE
            if keys[0] is not tup.key:
                tup = Tuple_(tup.ts, keys[0], tup.payload, tup.size,
                             tup.ingest_t, trace=tup.trace)
            if self._hold(sub, tup):
                return []
            w_raw = (0.0,) * spec.width if fire else spec.weight_raw(tup)
            return [Lane(tup.key, tup.ts, w_raw, fire, False, tup)]
        folded = self._session_fold(sub, tup)
        if folded is None:
            return []
        ptup, sess, absorbed = folded
        lanes = [] if self._hold(sub, ptup) else [
            Lane(ptup.key, ptup.ts, spec.weight_raw(ptup), False, False,
                 ptup)]
        self._merge(sub, ptup, sess, absorbed)
        return lanes

    # ---------------------------------------------------------------- firing
    def on_watermark(self, sub: int, wm: float) -> None:
        set_clock = getattr(self.caches[sub], "set_clock", None)
        if set_clock is not None:
            set_clock(wm)
        fire_batch = []
        just_fired = set()
        now = self.sim.t
        heap = self.fire_heap[sub]
        while heap and heap[0][0] <= wm:
            end, base, wid = heapq.heappop(heap)
            sess = self._find(sub, base, wid)
            if sess is None or sess["fired"] or sess["end"] != end:
                continue                  # stale candidate
            sess["fired"] = True
            just_fired.add((base, wid))
            heapq.heappush(self.purge_heap[sub],
                           (end + self.allowed_lateness, base, wid))
            fire_batch.append(Tuple_(
                end, WindowKey(base, wid), FIRE, 32,
                end if self.latency_from_end else now))
        pheap = self.purge_heap[sub]
        requeue = []
        while pheap and pheap[0][0] <= wm:
            due, base, wid = heapq.heappop(pheap)
            if (base, wid) in just_fired:
                # fired by THIS advance: the purge stays one advance
                # behind its fire, as windows.py keeps its horizon purge
                requeue.append((due, base, wid))
                continue
            sess = self._find(sub, base, wid)
            if sess is not None and sess["fired"] \
                    and sess["end"] + self.allowed_lateness <= wm:
                self._purge_session(sub, base, sess)
        for item in requeue:
            heapq.heappush(pheap, item)
        if fire_batch:
            # the FIREs take the watermark's place in the input queue
            # (see the class docstring)
            self.queues[sub].extendleft(reversed(fire_batch))
            self._kick(sub)

    def _purge_session(self, sub: int, base: Any, sess: dict) -> None:
        """Take a fired session out of the registry, and drop its pane
        now or, while an access to it is parked, held or resumed, once
        the last one has applied (``handle_parked``)."""
        lst = self.sess[sub].get(base)
        if lst is not None:
            try:
                lst.remove(sess)
            except ValueError:
                pass
            if not lst:
                del self.sess[sub][base]
        wk = WindowKey(base, sess["wid"])
        if self._pane_busy(sub, wk):
            self._purge_due[sub].add(wk)
        else:
            self._purge_pane(sub, wk)

    def _pane_busy(self, sub: int, wk: WindowKey) -> bool:
        """An access to pane ``wk`` is parked, held, resumed and not yet
        applied, or an absorb into it is in flight."""
        return wk in self.waiting[sub] or wk in self.held[sub] \
            or wk in self.pending[sub] \
            or any(t.key == wk for t in self.ready[sub])

    def _purge_if_due(self, sub: int, wk: WindowKey) -> None:
        if wk in self._purge_due[sub] and not self._pane_busy(sub, wk):
            self._purge_pane(sub, wk)     # its last access applied

    def handle_parked(self, sub: int, tup: Tuple_) -> float:
        svc = StatefulOp.handle_parked(self, sub, tup)
        self._purge_if_due(sub, tup.key)
        return svc

    # ----------------------------------------------------- purge/I-O races
    def _completion_dead(self, sub: int, req: _IOReq) -> bool:
        wk = req.key
        if not isinstance(wk, WindowKey):
            return False
        entry = self.absorbed[sub].get((wk.base, wk.wid))
        if entry is not None:
            # absorbed pane: completions stay LIVE until the drain took
            # its state (the drain may be parked on this very fetch);
            # after that the pane is purged and completions are dead
            return entry["drained"]
        if self._find(sub, wk.base, wk.wid) is not None \
                or wk in self._purge_due[sub]:
            return False                  # registered, or accesses wait
        # unregistered: a hint legitimately runs ahead of the first data
        # tuple, so only count the pane dead once even the EARLIEST
        # possible fire deadline of its creating timestamp is past the
        # lateness horizon
        return self.assigner.start_of(wk.wid) + self.assigner.gap \
            + self.allowed_lateness < self.wm[sub]

    # ------------------------------------------------------------- migration
    def migrate_shard(self, shard: int, dst_sub: int) -> None:
        plane = self.shards
        src = plane.owner[shard] if plane is not None else None
        super().migrate_shard(shard, dst_sub)
        if plane is None or src is None or src == dst_sub:
            return
        moving = [b for b in self.sess[src]
                  if plane.shard_of(b) == shard]
        for base in moving:
            sessions = self.sess[src].pop(base)
            self.sess[dst_sub].setdefault(base, []).extend(sessions)
            for s in sessions:
                # re-arm firing/purging at the new owner (the old owner's
                # heap entries go stale and skip on pop)
                if s["fired"]:
                    heapq.heappush(
                        self.purge_heap[dst_sub],
                        (s["end"] + self.allowed_lateness, base, s["wid"]))
                else:
                    heapq.heappush(self.fire_heap[dst_sub],
                                   (s["end"], base, s["wid"]))
        amap = self.absorbed[src]
        for k in [k for k in amap if plane.shard_of(k[0]) == shard]:
            self.absorbed[dst_sub][k] = amap.pop(k)
        # absorbs in flight move with their panes; held accesses replay
        # at the new owner behind the parked ones (and hold again there
        # until their absorbs land)
        pend, held = self.pending[src], self.held[src]
        for wk in [wk for wk in pend if plane.shard_of(wk) == shard]:
            self.pending[dst_sub][wk] = pend.pop(wk)
        for wk in [wk for wk in held if plane.shard_of(wk) == shard]:
            self.shard_pending.setdefault(shard, []).extend(held.pop(wk))

    # ---------------------------------------------------- snapshot / restore
    def snapshot_extra(self, sub: int) -> Dict[str, Any]:
        import copy
        out = super().snapshot_extra(sub) or {}
        out["sessions"] = copy.deepcopy(self.sess[sub])
        out["absorbed"] = copy.deepcopy(self.absorbed[sub])
        out["pending"] = dict(self.pending[sub])
        return out

    def restore_extra(self, sub: int, extra: Optional[dict]) -> None:
        super().restore_extra(sub, extra)
        if not extra or "sessions" not in extra:
            return
        self.sess[sub] = extra["sessions"]
        self.absorbed[sub] = extra.get("absorbed", {})
        # ``pending`` counts survive as snapshotted: each in-flight
        # drain/absorb rides the inflight capture exactly once (an
        # applied drain leaves the queue before its absorb enters), so
        # re-delivery decrements them back to zero; the held accesses
        # ride it too.  Heaps are derived state: rebuilt from the
        # restored registry.
        self.pending[sub] = dict(extra.get("pending", {}))
        self.fire_heap[sub] = []
        self.purge_heap[sub] = []
        for base, sessions in self.sess[sub].items():
            for s in sessions:
                if s["fired"]:
                    heapq.heappush(
                        self.purge_heap[sub],
                        (s["end"] + self.allowed_lateness, base, s["wid"]))
                else:
                    heapq.heappush(self.fire_heap[sub],
                                   (s["end"], base, s["wid"]))

    def _snapshot_inflight(self, sub: int) -> List[Any]:
        """Parked accesses, held ones, and the operator's own pane
        traffic still queued (FIREs, merge steps): none of it is
        replayed by the source."""
        out = StatefulOp._snapshot_inflight(self, sub)
        for held in self.held[sub].values():
            out.extend(held)
        out.extend(t for t in self.queues[sub]
                   if isinstance(t, Tuple_) and isinstance(t.key, WindowKey))
        return out

    def reset_volatile(self) -> None:
        super().reset_volatile()
        p = self.parallelism
        self.sess = [dict() for _ in range(p)]
        self.absorbed = [dict() for _ in range(p)]
        self.pending = [dict() for _ in range(p)]
        self.held = [dict() for _ in range(p)]
        self.fire_heap = [[] for _ in range(p)]
        self.purge_heap = [[] for _ in range(p)]

    # --------------------------------------------------------------- metrics
    def extra_metrics(self) -> Dict[str, Any]:
        out = super().extra_metrics()
        out.update({
            "sessions_created": self.sessions_created,
            "sessions_merged": self.sessions_merged,
            "sessions_reopened": self.sessions_reopened,
            "fires_superseded": self.fires_superseded,
            "fires_absorbed": self.fires_absorbed,
            "merge_drains": self.merge_drains,
            "merge_absorbs": self.merge_absorbs,
            "merge_holds": self.merge_holds,
            "merge_settles": self.merge_settles,
            "live_sessions": sum(len(lst) for sub in self.sess
                                 for lst in sub.values()),
        })
        return out

    def registry_counters(self) -> Dict[str, int]:
        """Counters mirrored into the metrics registry as
        ``engine.<op>.<name>`` (``Engine._sync_registry``)."""
        return {"sessions.created": self.sessions_created,
                "sessions.merged": self.sessions_merged,
                "sessions.reopened": self.sessions_reopened,
                "sessions.fires_superseded": self.fires_superseded,
                "sessions.fires_absorbed": self.fires_absorbed,
                "sessions.held": self.merge_holds,
                "sessions.merge_settles": self.merge_settles}


class SessionLookaheadOp(WindowedLookaheadOp):
    """Session-window Hint Extractor with MOVING deadlines (DESIGN.md
    §15).

    Mirrors the downstream session registry per key via the SAME
    ``SessionWindowAssigner.fold`` (the edge into this operator and the
    edge out of it both partition by the session key, so both sides see
    each key's tuples in one FIFO order — lockstep).  Per tuple it emits
    a deadline hint for the surviving pane; when the tuple EXTENDS or
    MERGES the session, the hint bypasses admission/dedup entirely
    (``rehints``) so ``PrefetchingManager.on_hint`` renews the resident
    pane's TAC timestamp to the new deadline — the moving-deadline path.
    Near-fire sessions burst exactly like fixed windows.
    """

    def __init__(self, engine, name, parallelism,
                 assigner: SessionWindowAssigner, key_of: Callable,
                 fn=None, hint_ts_mode: str = "deadline",
                 burst_ahead: float = 0.0, allowed_lateness: float = 0.0,
                 service_time: float = 10e-6,
                 cms_conf: Optional[dict] = None,
                 filter_conf: Optional[dict] = None):
        super().__init__(engine, name, parallelism, assigner, key_of,
                         fn=fn, hint_ts_mode=hint_ts_mode,
                         burst_ahead=burst_ahead,
                         allowed_lateness=allowed_lateness,
                         service_time=service_time, cms_conf=cms_conf,
                         filter_conf=filter_conf)
        self.sess: List[Dict[Any, List[dict]]] = \
            [dict() for _ in range(parallelism)]
        self.rehints = 0

    def _emit_hints_for(self, sub: int, o: Tuple_) -> float:
        base = self.key_of(o)
        if base is None:
            return 0.0
        ts = o.ts
        wm = self.wm[sub]
        gap = self.assigner.gap
        if ts + gap + self.allowed_lateness < wm \
                and not self.assigner.overlapping(
                    self.sess[sub].get(base, ()), ts):
            return 0.0                    # dropped downstream anyway
        sessions = self.sess[sub].setdefault(base, [])
        sess, absorbed, extended, created = self.assigner.fold(sessions, ts)
        if extended and not created:
            sess["burst"] = False         # deadline moved: burst re-arms
        wk = WindowKey(base, sess["wid"])
        deadline = self.hint_ts_mode == "deadline"
        hint_ts = sess["end"] if deadline else ts
        svc = HINT_COST
        if created:
            if self._admit(sub, wk, freq_key=base):
                self.emit_hint(sub, Hint(wk, hint_ts, origin=self.name))
        elif extended or absorbed:
            # the deadline MOVED: re-hint unconditionally so a resident
            # pane is renewed in place (admission dedup would swallow it)
            self.rehints += 1
            self.emit_hint(sub, Hint(wk, hint_ts, origin=self.name))
        elif self._admit(sub, wk, freq_key=base):
            self.emit_hint(sub, Hint(wk, hint_ts, origin=self.name))
        return svc

    def on_watermark(self, sub: int, wm: float) -> None:
        if self.hint_ts_mode != "deadline":
            return
        horizon = wm + self.burst_ahead
        registry = self.sess[sub]
        for base in list(registry):
            sessions = registry[base]
            for s in list(sessions):
                if s["end"] + self.allowed_lateness < wm:
                    sessions.remove(s)    # closed downstream: forget it
                elif s["end"] <= horizon and not s.get("burst") \
                        and self.hint_active:
                    s["burst"] = True
                    self.burst_hints += 1
                    self.emit_hint(sub, Hint(WindowKey(base, s["wid"]),
                                             s["end"], origin=self.name))
            if not sessions:
                del registry[base]

    def reset_volatile(self) -> None:
        super().reset_volatile()
        self.sess = [dict() for _ in range(self.parallelism)]

    def registry_counters(self) -> Dict[str, int]:
        return {"sessions.rehints": self.rehints}

    def extra_metrics(self) -> Dict[str, Any]:
        out = super().extra_metrics()
        out["rehints"] = self.rehints
        out["tracked_sessions"] = sum(len(lst) for sub in self.sess
                                      for lst in sub.values())
        return out
