"""Event-time windowing + watermark propagation tests (DESIGN.md §10).

Quick by design (sub-second discrete-event runs): these belong to the
tier-1 loop, unlike the full-duration sims in test_streaming.py.
"""
import math

import pytest

from repro.core.tac import TimestampAwareCache
from repro.streaming.backend import IN_MEMORY, LOCAL_NVME
from repro.streaming.engine import Engine, MapOp, SinkOp, SourceOp
from repro.streaming.events import Tuple_, Watermark, WindowKey
from repro.streaming.nexmark import NexmarkConfig, build_query
from repro.streaming.windows import (WindowAssigner, WindowedLookaheadOp,
                                     WindowedStatefulOp)


# ------------------------------------------------------------- assigner
def test_window_assigner_tumbling():
    a = WindowAssigner(2.0)
    assert a.assign(3.5) == [1]
    assert a.assign(0.0) == [0]
    assert a.end(1) == 4.0 and a.start(1) == 2.0


def test_window_assigner_sliding():
    a = WindowAssigner(4.0, 1.0)
    assert a.assign(3.5) == [3, 2, 1, 0]
    assert a.end(3) == 7.0 and a.start(3) == 3.0
    with pytest.raises(ValueError):
        WindowAssigner(1.0, 2.0)          # slide > size


# ------------------------------------------- deadline-aware TAC eviction
def test_tac_deadline_aware_eviction_order():
    """Stale entries (ts behind the watermark clock) evict oldest-first;
    among live deadlines the FARTHEST goes first (Belady), so the pane
    firing next stays resident."""
    c = TimestampAwareCache(3, deadline_aware=True)
    c.set_clock(10.0)
    c.insert("stale", 1, 5.0)
    c.insert("soon", 1, 12.0)
    c.insert("far", 1, 20.0)
    c.insert("x", 1, 15.0)               # needs room: stale goes first
    assert not c.contains("stale")
    c.insert("y", 1, 13.0)               # all live: farthest (20) goes
    assert not c.contains("far")
    assert c.contains("soon") and c.contains("x") and c.contains("y")


def test_tac_default_order_unchanged():
    c = TimestampAwareCache(2)
    c.insert("a", 1, 10.0)
    c.insert("b", 1, 20.0)
    c.insert("c", 1, 15.0)               # min-ts (a) evicted, paper §IV-D
    assert not c.contains("a")
    assert c.contains("b") and c.contains("c")


def test_tac_drop_removes_without_writeback():
    c = TimestampAwareCache(10)
    c.write("k", {"v": 1}, 1.0)          # dirty
    assert c.drop("k") and not c.contains("k")
    assert c.pop_writeback() is None     # nothing staged for write-back
    assert not c.drop("k")


# --------------------------------------------------- watermark propagation
def _noop_gen(now):
    return (0, {"v": 1}, 100)


def test_watermark_min_of_inputs():
    """A multi-input operator advances to the MINIMUM of its inputs'
    watermarks, only after every input has reported."""
    eng = Engine()
    a = eng.add(SourceOp(eng, "a", 1, 2000.0, _noop_gen,
                         watermark_interval=0.02, oo_bound=0.05))
    b = eng.add(SourceOp(eng, "b", 1, 2000.0, _noop_gen,
                         watermark_interval=0.02, oo_bound=0.30))
    m = eng.add(MapOp(eng, "m", 2))
    sink = eng.add(SinkOp(eng, "sink", 1))
    eng.connect(a, m)
    eng.connect(b, m)
    eng.connect(m, sink)
    eng.run(duration=1.0)
    for s in range(m.parallelism):
        # bounded by the laggard input (oo_bound=0.30), not the fast one
        assert m.wm[s] > float("-inf")
        assert m.wm[s] <= 1.0 - 0.30 + 0.001
        assert m.wm[s] >= 0.5 - 0.30
    # and it propagates downstream (min-of-inputs again at the sink)
    assert sink.wm[0] > float("-inf")
    assert sink.wm[0] <= m.wm[0]


def test_watermark_held_back_until_all_inputs_report():
    """An input that never emits watermarks pins downstream at -inf."""
    eng = Engine()
    a = eng.add(SourceOp(eng, "a", 1, 2000.0, _noop_gen,
                         watermark_interval=0.02))
    b = eng.add(SourceOp(eng, "b", 1, 2000.0, _noop_gen))   # no watermarks
    m = eng.add(MapOp(eng, "m", 1))
    eng.connect(a, m)
    eng.connect(b, m)
    eng.run(duration=0.5)
    assert m.wm[0] == float("-inf")


# ----------------------------------------------------- windowed correctness
class _CollectSink(SinkOp):
    def __init__(self, *a, **kw):
        super().__init__(*a, **kw)
        self.got = []

    def process(self, sub, tup):
        self.got.append((tup.key, tup.payload))
        return super().process(sub, tup)


def _count_pipeline(eng, assigner, emitted, rate=2000.0, lateness=0.0,
                    late_policy="drop", gen=None):
    def default_gen(now):
        k = int(now * 1000) % 5
        emitted.append((now, k))
        return (k, {"k": k}, 100)

    src = eng.add(SourceOp(eng, "src", 1, rate, gen or default_gen,
                           watermark_interval=0.05, oo_bound=0.0))
    win = eng.add(WindowedStatefulOp(
        eng, "win", 1, assigner,
        agg_fn=lambda tup, acc: (acc or 0) + 1,
        emit_fn=lambda key, wid, end, acc: ("count", key, wid, acc),
        backend_model=IN_MEMORY, cache_capacity=1_000_000,
        allowed_lateness=lateness, late_policy=late_policy,
        policy="tac", mode="sync", state_size=100))
    sink = eng.add(_CollectSink(eng, "sink", 1))
    eng.connect(src, win)
    eng.connect(win, sink, partition=lambda k, n: 0)
    return win, sink


def test_tumbling_fire_counts_are_exact():
    """Every fired pane's count equals the number of source tuples whose
    event time fell in that (key, window)."""
    eng = Engine()
    assigner = WindowAssigner(0.2)
    emitted = []
    win, sink = _count_pipeline(eng, assigner, emitted)
    eng.run(duration=1.2)
    fired = {(k, wid): n for k, (_, _, wid, n) in
             ((key, payload) for key, payload in sink.got)}
    assert fired, "no windows fired"
    expected = {}
    for ts, k in emitted:
        wid = math.floor(ts / 0.2)
        expected[(k, wid)] = expected.get((k, wid), 0) + 1
    for (k, wid), n in fired.items():
        assert expected.get((k, wid)) == n, (k, wid)
    # zero lateness: every fired pane purged, state fully reclaimed
    assert win.panes_purged == win.fires == len(sink.got)
    assert len(win.caches[0].entries) <= 5 * 2   # only unfired panes left


def test_late_tuples_dropped_and_counted():
    eng = Engine()
    assigner = WindowAssigner(0.1)
    emitted = []
    state = {"n": 0}

    def gen(now):
        state["n"] += 1
        ts = now - 0.5 if state["n"] % 40 == 0 else now   # 2.5% very late
        k = state["n"] % 5
        emitted.append((ts, k))
        return (k, {"k": k}, 100, ts)

    win, sink = _count_pipeline(eng, assigner, emitted, gen=gen)
    eng.run(duration=1.0)
    assert win.late_dropped > 0
    assert win.fires > 0


def test_late_tuples_update_path_re_emits():
    eng = Engine()
    assigner = WindowAssigner(0.1)
    emitted = []
    state = {"n": 0}

    def gen(now):
        state["n"] += 1
        # late by 0.15: within allowed_lateness=0.3 of recent windows
        ts = now - 0.15 if state["n"] % 20 == 0 else now
        k = state["n"] % 5
        emitted.append((ts, k))
        return (k, {"k": k}, 100, ts)

    win, sink = _count_pipeline(eng, assigner, emitted, lateness=0.3,
                                late_policy="update", gen=gen)
    eng.run(duration=1.0)
    assert win.late_updates > 0
    assert win.late_dropped == 0
    # late-side updates add outputs beyond one-per-fire
    assert len(sink.got) > win.fires - 10
    assert win.panes_purged > 0          # horizon purge pass ran


def test_update_policy_requires_lateness():
    eng = Engine()
    with pytest.raises(ValueError):
        WindowedStatefulOp(eng, "w", 1, WindowAssigner(1.0),
                           lambda t, a: a, lambda *a: None,
                           IN_MEMORY, 100, allowed_lateness=0.0,
                           late_policy="update")


# --------------------------------------------- hints + prefetch integration
def test_deadline_hints_drive_prefetch_and_burst():
    cfg = NexmarkConfig(rate=3000, active_window=1.0, oo_bound=0.2, seed=7)
    eng = build_query("q7", "tac", "prefetch", cfg, cache_entries=256,
                      parallelism=2, source_parallelism=1, io_workers=4,
                      buffer_timeout=0.002, window_size=0.5)
    m = eng.run(duration=1.5, warmup=0.5)
    assert m["stateful_hints_received"] > 0
    assert m["stateful_fires"] > 0
    assert m["win_lookahead_burst_hints"] > 0
    assert m["stateful_prefetch_hits"] > 0
    assert m["n_outputs"] > 0
    # hint keys are panes: the windowed lookahead is the active candidate
    assert eng.controller.active["stateful"] == "win_lookahead"


def test_windowed_query_requires_out_of_orderness():
    cfg = NexmarkConfig(rate=1000, oo_bound=0.0)
    with pytest.raises(ValueError):
        build_query("q5", "tac", "prefetch", cfg)


# ------------------------------------------------------------- shard plane
def test_watermark_forwarding_on_shard_plane():
    """Watermarks broadcast to every subtask of a shard-routed windowed
    operator, and windows fire on all owners."""
    cfg = NexmarkConfig(rate=3000, active_window=1.0, oo_bound=0.2, seed=7)
    eng = build_query("q7", "tac", "prefetch", cfg, cache_entries=256,
                      parallelism=2, source_parallelism=1, io_workers=4,
                      buffer_timeout=0.002, window_size=0.5, n_shards=8)
    m = eng.run(duration=1.5, warmup=0.5)
    st = eng.operators["stateful"]
    assert all(w > float("-inf") for w in st.wm)
    assert m["stateful_fires"] > 0
    plane = m["stateful_shard_plane"]
    assert sum(plane["tuples_routed"]) > 0
    assert sum(plane["hints_routed"]) > 0
    assert m["n_outputs"] > 0


def test_windowed_migration_moves_live_windows():
    """Mid-run shard migration on a windowed operator: pane state AND the
    live-window registrations move, so fires continue at the new owner."""
    cfg = NexmarkConfig(rate=3000, active_window=1.0, oo_bound=0.2, seed=7)
    eng = build_query("q7", "tac", "prefetch", cfg, cache_entries=256,
                      parallelism=2, source_parallelism=1, io_workers=4,
                      buffer_timeout=0.002, window_size=0.5, n_shards=8)
    eng.migrate_shard("stateful", 0, 1, at=0.9)
    m = eng.run(duration=1.6, warmup=0.5)
    st = eng.operators["stateful"]
    assert st.shards.migrations == 1
    assert m["stateful_fires"] > 0
    assert m["n_outputs"] > 0


def test_parked_tuple_resuming_after_fire_does_not_duplicate_output():
    """An on-time tuple that parked on a state fetch across its window's
    fire must not take the late-update emit path under drop policy (it
    would duplicate the pane result); under update policy it emits one
    late-side refresh."""
    eng = Engine()

    def mk(name, **kw):
        win = WindowedStatefulOp(
            eng, name, 1, WindowAssigner(1.0),
            lambda t, a: (a or 0) + 1,
            lambda k, wid, end, acc: ("c", k, acc),
            IN_MEMORY, 10_000, policy="tac", mode="async",
            state_size=100, **kw)
        outs = []
        win.emit = lambda sub, msg: outs.append(msg)
        win.windows[0][0] = {"keys": {7}, "fired": True,
                             "fired_keys": {7}}
        return win, outs

    wk = WindowKey(7, 0)
    drop, outs = mk("w_drop")
    drop._apply(0, Tuple_(0.5, wk, {"k": 7}, 100, 0.4), 1)
    assert outs == [] and drop.late_dropped == 1

    upd, outs = mk("w_upd", allowed_lateness=0.5, late_policy="update")
    upd._apply(0, Tuple_(0.5, wk, {"k": 7}, 100, 0.4), 1)
    assert len(outs) == 1 and upd.late_updates == 1


def test_migration_merges_fired_state_per_key():
    """Watermark skew across a migration can merge fired and unfired pane
    populations of the SAME window: the moved unfired keys must still
    fire at the destination, and already-fired keys must not refire."""
    from repro.streaming.shards import ShardPlane
    eng = Engine()
    plane = ShardPlane(4, 2)
    win = WindowedStatefulOp(
        eng, "w", 2, WindowAssigner(1.0),
        lambda t, a: (a or 0) + 1, lambda k, wid, end, acc: ("c", k, acc),
        IN_MEMORY, 10_000, policy="tac", mode="sync", shards=plane)
    # keys 0/4 live in shard 0 (owner sub 0), key 1 in shard 1 (sub 1)
    win.windows[0][5] = {"keys": {0, 4}, "fired": False,
                         "fired_keys": set()}
    win.windows[1][5] = {"keys": {1}, "fired": True, "fired_keys": {1}}
    win.migrate_shard(0, 1)
    assert 5 not in win.windows[0]
    d = win.windows[1][5]
    assert d["keys"] == {0, 1, 4}
    assert d["fired_keys"] == {1}        # moved keys stay fire-eligible
    win._kick = lambda sub: None         # keep the FIREs queued
    win.on_watermark(1, 6.0)             # dst watermark crosses end(5)=6
    fired = {t.key.base for t in win.queues[1]}
    assert fired == {0, 4}               # key 1 not refired
    assert d["fired_keys"] == {0, 1, 4}


def test_hash_partition_unwraps_window_keys():
    from repro.streaming.shards import hash_partition
    assert hash_partition(WindowKey(42, 7), 8) == hash_partition(42, 8)
    assert hash_partition(WindowKey(("a", 1), 3), 4) == \
        hash_partition(("a", 1), 4)


def test_fire_takes_the_watermark_position_in_the_input():
    """A pane fires at its watermark's place in the input queue: a tuple
    already queued BEHIND the watermark is a late update after the fire,
    so what a fire counts does not depend on how far the operator lags
    its input (the fused and interpreted planes run at different
    speeds)."""
    eng = Engine()
    win = eng.add(WindowedStatefulOp(
        eng, "win", 1, WindowAssigner(1.0),
        agg_fn=lambda tup, acc: (acc or 0) + 1,
        emit_fn=lambda key, wid, end, acc: ("count", key, wid, acc),
        backend_model=IN_MEMORY, cache_capacity=1_000_000,
        allowed_lateness=5.0, late_policy="update", policy="tac",
        mode="sync", state_size=100))
    sink = eng.add(_CollectSink(eng, "sink", 1))
    eng.connect(win, sink, partition=lambda k, n: 0)
    win.deliver_batch(0, [Tuple_(0.5, 7, None, 100, 0.0), Watermark(1.0),
                          Tuple_(0.6, 7, None, 100, 0.0)])
    eng.sim.run_until(1.0)
    assert [p for _, p in sink.got] == [("count", 7, 0, 1),
                                         ("count", 7, 0, 2)]
    assert win.fires == 1 and win.late_updates == 1


def _async_count_op(eng, lateness):
    win = eng.add(WindowedStatefulOp(
        eng, "win", 1, WindowAssigner(1.0),
        agg_fn=lambda tup, acc: (acc or 0) + 1,
        emit_fn=lambda key, wid, end, acc: ("count", key, wid, acc),
        backend_model=LOCAL_NVME, cache_capacity=1_000_000,
        allowed_lateness=lateness, late_policy="update", policy="tac",
        mode="async", state_size=100))
    sink = eng.add(_CollectSink(eng, "sink", 1))
    eng.connect(win, sink, partition=lambda k, n: 0)
    return win, sink


def test_tuple_parked_across_the_fire_is_counted_by_it():
    """Lateness is the tuple's place in the input, fixed at window
    assignment: an on-time tuple still parked on its pane fetch when the
    watermark fires the window is counted by the fire (which parks behind
    it) and emits no late refresh of its own."""
    eng = Engine()
    win, sink = _async_count_op(eng, lateness=5.0)
    win.deliver_batch(0, [Tuple_(0.5, 7, None, 100, 0.0), Watermark(1.0)])
    eng.sim.run_until(1.0)
    assert [p for _, p in sink.got] == [("count", 7, 0, 1)]
    assert win.late_updates == 0 and win.fires == 1


def test_horizon_purge_waits_for_tuples_parked_on_the_pane():
    """A watermark that passes a pane's lateness horizon while an on-time
    tuple (and the FIRE behind it) is parked on the pane's fetch purges
    the pane only after both applied — not depending on when the fetch
    lands."""
    eng = Engine()
    win, sink = _async_count_op(eng, lateness=0.5)
    win.deliver_batch(0, [Tuple_(0.9, 7, None, 100, 0.0), Watermark(1.2),
                          Watermark(2.0)])
    eng.sim.run_until(1.0)
    assert [p for _, p in sink.got] == [("count", 7, 0, 1)]
    assert win.panes_purged == 1 and WindowKey(7, 0) not in \
        win.caches[0].entries
    assert win.late_dropped == 0 and win.fires_lost == 0
