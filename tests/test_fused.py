"""Differential parity: the fused device hot path vs the interpreted
engine (DESIGN.md §14).

The same tuple stream is driven through a fused and an interpreted
operator under the QUIESCED protocol — deliver a data batch, run the
simulator until all I/O lands, deliver a watermark, quiesce again.
Batching compresses simulated time (that is the latency win), so under
CONCURRENT async I/O backend completions land at different points of
the event timeline and eviction-order counters may diverge; state and
emitted tuples match regardless.  Quiescing pins the interleaving, and
then EVERYTHING must match bit-exactly: final backend state, emitted
tuples, and the §12 counter totals (hits/misses/evictions by reason,
writebacks, late drops/updates, parked-tuple demand fetches) — in
``sync`` mode the counters that hang on eviction choice excepted
(``PER_TUPLE_COUNTERS`` below says why).
"""
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(__file__))
from _hypothesis_compat import HAVE_HYPOTHESIS, given, settings, st

os.environ.setdefault("JAX_PLATFORMS", "cpu")

from repro.streaming.backend import LOCAL_NVME
from repro.streaming.engine import Engine, SinkOp, StatefulOp
from repro.streaming.events import Tuple_, Watermark
from repro.streaming.fused import FusedPlane, FusedSpec, Lane
from repro.streaming.windows import WindowAssigner, WindowedStatefulOp


def count_spec():
    return FusedSpec(kind="sum", width=1,
                     weight_of=lambda tup: 1.0,
                     encode=lambda s: None if s is None else [float(s)],
                     decode=lambda v: int(round(float(v[0]))))


def max_spec():
    return FusedSpec(kind="max", width=1,
                     weight_of=lambda tup: float(tup.payload),
                     encode=lambda s: None if s is None else [float(s)],
                     decode=lambda v: int(round(float(v[0]))))


class Collect(SinkOp):
    def __init__(self, *a, **kw):
        super().__init__(*a, **kw)
        self.got = []

    def process(self, sub, tup):
        self.got.append((tup.ts, tup.key, tup.payload))
        return super().process(sub, tup)


def _record_emits(op):
    """Every tuple ``op`` emits, as (ts, key, payload, emission sim time)."""
    out, emit = [], op.emit

    def recording(sub, msg):
        out.append((msg.ts, msg.key, msg.payload, op.sim.t))
        return emit(sub, msg)
    op.emit = recording
    return out


def _source_tuple(eng, ts, key, payload=None):
    """A data tuple as a source emits it: sampled for a trace when the
    engine's tracing is on."""
    tup = Tuple_(ts, key, payload, 64, 0.0)
    if eng.tracer.sample_every:
        tup.trace = eng.tracer.maybe_start(eng.sim.t)
    return tup


TRACE_COUNTERS = ("sampled", "finished", "probe_hits", "probe_misses")
# the counters no eviction choice moves.  In sync mode a lane's fetch
# can evict a key that a later lane of the same device batch already
# hit, where the interpreted plane evicts that key and then restores it
# from the eviction buffer: hit, miss and eviction counts then differ
# between the planes, while state and emits agree
PER_TUPLE_COUNTERS = ("processed", "outputs", "sampled", "finished",
                      "fires", "late_dropped", "late_updates", "purged")


def _counters(op):
    cache = op.caches[0]
    out = dict(hits=cache.hits, misses=cache.misses,
               evictions=cache.evictions, writebacks=cache.writebacks,
               by_reason=cache.eviction_block(), processed=op.processed,
               outputs=op.outputs, pf_demand=op.pf_demand.value)
    tracer = op.engine.tracer
    if tracer.active:
        s = tracer.summary()
        out.update({k: s[k] for k in TRACE_COUNTERS})
    return out


def _assert_counters_match(ci, cf, mode):
    if mode == "sync":
        # each plane alone: every miss of these runs is one synchronous
        # fetch (none is served from the write-back memtable)
        for c in (ci, cf):
            assert c["pf_demand"] == c["misses"], c
        def per_tuple(c):
            out = {k: c[k] for k in PER_TUPLE_COUNTERS if k in c}
            out["accesses"] = c["hits"] + c["misses"]
            return out
        ci, cf = per_tuple(ci), per_tuple(cf)
    assert ci == cf, f"counter mismatch\ninterp={ci}\nfused={cf}"


def _assert_emits_match(ei, ef):
    """Both planes emit the same tuples.  Emission sim times are not
    compared across planes: a fused batch emits at its drain's time,
    the interpreted plane spaces tuples by their service time."""
    assert sorted(e[:3] for e in ei) == sorted(e[:3] for e in ef), \
        f"emit mismatch\ninterp={ei}\nfused={ef}"


def _assert_trace_moves_nothing(traced, untraced):
    """A plane's traced run emits the same tuples at the same sim times,
    with the same counters, as its untraced run: a trace changes no
    decision.  On the fused plane the untraced run takes the vectorised
    plain-hit path and the traced one the per-lane path."""
    (_, c, e), (_, c0, e0) = traced, untraced
    assert e == e0
    assert {k: v for k, v in c.items() if k not in TRACE_COUNTERS} == c0


PLANES_MODES = [pytest.param(mode, traced, id=f"{mode}-{tr}")
                for mode in ("sync", "async")
                for traced, tr in ((False, "untraced"), (True, "traced"))]


def _final_state(op, state_size):
    for e in op.caches[0].flush_dirty():
        op.backends[0].write(e.key, e.state, state_size)
    return dict(op.backends[0].data)


def assert_shadow_is_pool(plane):
    """The host value shadow holds, bit for bit, the pool row of every
    occupied slot once the queued admissions have landed; every step
    has settled, and its device hits were the host's."""
    import numpy as np
    plane._sync()
    assert plane.deferred + plane.forced_settles == \
        plane.calls["fused_step"]
    assert plane.hit_mismatches == 0
    pool = np.asarray(plane.pages)
    occ = np.asarray(sorted(plane._slot_by_key.values()), np.int64)
    if not len(occ):
        return
    assert np.array_equal(plane._sval[occ].view(np.uint32),
                          pool[occ, 0, 1:].view(np.uint32))
    assert np.array_equal(plane._spres[occ], pool[occ, 0, 0] > 0.5)
    assert np.isin(pool[occ, 0, 0], (0.0, 1.0)).all()


# ------------------------------------------------------------ base operator
def run_base(keys, fused, cache_entries=8, batch=8, mode="async",
             traced=False, emits=False):
    """Count-per-key through a bare StatefulOp under the quiesced
    protocol; returns (state, counters, emits).  ``emits`` makes the
    operator emit each tuple's new count (``emit_of`` on the fused
    plane), ``traced`` samples every tuple for a trace."""
    eng = Engine()
    if traced:
        eng.enable_tracing(sample_every=1)
    kw = dict(policy="tac", mode=mode, cache_capacity=cache_entries * 64,
              state_size=64, io_workers=2)

    def out_of(tup, n):
        return [Tuple_(tup.ts, tup.key, n, 64, tup.ingest_t)] if emits \
            else []
    if fused:
        spec = count_spec()
        if emits:
            spec.emit_of = out_of
        kw["fused"] = spec
        kw["fused_batch"] = batch

    def apply_count(tup, state):
        n = (state or 0) + 1
        return n, out_of(tup, n)

    op = StatefulOp(eng, "agg", 1, apply_count, LOCAL_NVME, **kw)
    eng.add(op)
    sink = eng.add(Collect(eng, "sink", 1))
    eng.connect(op, sink)
    emitted = _record_emits(op)
    t = 0.0
    for i in range(0, len(keys), 6):
        op.deliver_batch(0, [_source_tuple(eng, float(j), keys[j])
                             for j in range(i, min(i + 6, len(keys)))])
        t += 0.05
        eng.sim.run_until(t)
        if fused:
            assert_shadow_is_pool(op.caches[0])
    eng.sim.run_until(t + 1.0)
    if fused:
        assert_shadow_is_pool(op.caches[0])
    assert sorted(sink.got) == sorted(e[:3] for e in emitted)
    return _final_state(op, 64), _counters(op), emitted


def assert_base_parity(keys, mode="async", traced=False, **kw):
    runs = [run_base(keys, fused, mode=mode, traced=traced, **kw)
            for fused in (False, True)]
    (si, ci, ei), (sf, cf, ef) = runs
    assert si == sf, f"state mismatch\ninterp={si}\nfused={sf}"
    _assert_counters_match(ci, cf, mode)
    _assert_emits_match(ei, ef)
    if traced:
        for fused, run in enumerate(runs):
            _assert_trace_moves_nothing(
                run, run_base(keys, bool(fused), mode=mode, **kw))
    return ci


@pytest.mark.parametrize("emits", [False, True], ids=["absorbed", "emits"])
@pytest.mark.parametrize("mode,traced", PLANES_MODES)
def test_base_parity_with_evictions_and_parking(mode, traced, emits):
    keys = [1, 2, 3, 1, 1, 4, 2, 9, 9, 1, 5, 6, 7, 8, 10, 11, 1, 2, 12, 1]
    ci = assert_base_parity(keys, mode=mode, traced=traced, emits=emits)
    # the workload must actually exercise the cold paths it claims to
    assert ci["evictions"] > 0
    assert ci["pf_demand"] > 0          # misses parked or sync-fetched
    assert ci["outputs"] == (len(keys) if emits else 0)
    if traced:
        assert ci["finished"] == ci["sampled"] == len(keys)
        assert ci["probe_misses"] > 0


def test_base_parity_single_hot_key():
    # duplicate keys in one batch: the device composes the run in-lane
    assert_base_parity([7] * 23)


def test_base_parity_all_distinct():
    assert_base_parity(list(range(30)))


@settings(max_examples=15, deadline=None)
@given(st.lists(st.integers(min_value=0, max_value=12),
                min_size=1, max_size=48))
def test_base_parity_property(keys):
    assert_base_parity(keys)


# -------------------------------------------------------- windowed operator
def run_windowed(keys_ts, fused, lateness, late_policy, size=10.0,
                 cache_entries=6, batch=8, wm_lag=6.0, spec=None,
                 agg=None, emit=None, payload_of=None, mode="async",
                 traced=False):
    """Windowed count per (key, window) with a mid-stream watermark after
    every quiesced data batch; returns (sink tuples, state, counters,
    emits with their sim times)."""
    eng = Engine()
    if traced:
        eng.enable_tracing(sample_every=1)
    kw = dict(policy="tac", mode=mode, cache_capacity=cache_entries * 64,
              state_size=64, io_workers=2, allowed_lateness=lateness,
              late_policy=late_policy)
    if fused:
        kw["fused"] = spec or count_spec()
        kw["fused_batch"] = batch
    if agg is None:
        def agg(tup, state):
            return (state or 0) + 1

        def emit(base, wid, end, acc):
            return ("count", base, acc) if acc else None
    op = WindowedStatefulOp(eng, "win", 1, WindowAssigner(size), agg, emit,
                            LOCAL_NVME, **kw)
    sink = Collect(eng, "sink", 1)
    eng.add(op)
    eng.add(sink)
    eng.connect(op, sink)
    emitted = _record_emits(op)
    batches = []                         # the fence-invariant check below
    if fused:
        plane = op.caches[0]
        orig = plane.batch_step

        def recording(lanes):
            batches.append(list(lanes))
            return orig(lanes)
        plane.batch_step = recording
    t = 0.0
    hi = 0.0
    for i in range(0, len(keys_ts), 6):
        chunk = keys_ts[i:i + 6]
        op.deliver_batch(0, [
            _source_tuple(eng, ts, k, payload_of(k, ts) if payload_of
                          else None) for k, ts in chunk])
        hi = max([hi] + [ts for _, ts in chunk])
        t += 0.05
        eng.sim.run_until(t)             # quiesce: all I/O lands
        op.deliver_batch(0, [Watermark(hi - wm_lag)])
        t += 0.05
        eng.sim.run_until(t)
        if fused:
            assert_shadow_is_pool(op.caches[0])
    op.deliver_batch(0, [Watermark(hi + 1000.0)])
    eng.sim.run_until(t + 2.0)
    if fused:
        assert_shadow_is_pool(op.caches[0])
    for lanes in batches:
        fires = {ln.key for ln in lanes if ln.fire}
        upds = {ln.key for ln in lanes if not ln.fire}
        assert not (fires & upds), \
            "fire and update of the same pane shared a device batch"
    ctr = _counters(op)
    ctr.update(fires=op.fires, late_dropped=op.late_dropped,
               late_updates=op.late_updates, purged=op.panes_purged)
    assert sorted(sink.got) == sorted(e[:3] for e in emitted)
    return sorted(sink.got), _final_state(op, 64), ctr, emitted


def assert_windowed_parity(keys_ts, lateness, late_policy, mode="async",
                           traced=False, **kw):
    runs = [run_windowed(keys_ts, fused, lateness, late_policy, mode=mode,
                         traced=traced, **kw) for fused in (False, True)]
    (gi, si, ci, _), (gf, sf, cf, _) = runs
    assert gi == gf, f"emit mismatch\ninterp={gi}\nfused={gf}"
    assert si == sf, f"state mismatch\ninterp={si}\nfused={sf}"
    _assert_counters_match(ci, cf, mode)
    if traced:
        for fused, run in enumerate(runs):
            _assert_trace_moves_nothing(
                run[1:], run_windowed(keys_ts, bool(fused), lateness,
                                      late_policy, mode=mode, **kw)[1:])
    return ci


def _steady_stream():
    keys = [1, 2, 3, 1, 1, 4, 2, 9, 9, 1, 5, 6, 7, 8, 10, 11, 1, 2, 12, 1,
            3, 3, 5, 1, 2, 7, 9, 4, 4, 1]
    return [(k, i * 1.7) for i, k in enumerate(keys)]


def test_windowed_parity_no_lateness():
    ci = assert_windowed_parity(_steady_stream(), 0.0, "drop")
    assert ci["fires"] > 0               # mid-stream watermarks fired panes
    assert ci["evictions"] > 0


@pytest.mark.parametrize("mode,traced", PLANES_MODES)
def test_windowed_parity_update_policy_with_late_tuples(mode, traced):
    # watermark trails by 6s; tuples jumping 30s back are LATE on fired
    # panes (within the 40s horizon -> late-side re-aggregation)
    stream = _steady_stream()
    late = [(1, 3.0), (2, 5.0), (1, 12.0), (9, 14.0)]
    keys_ts = stream[:18] + late + stream[18:]
    ci = assert_windowed_parity(keys_ts, 40.0, "update", mode=mode,
                                traced=traced)
    assert ci["late_updates"] > 0
    assert ci["fires"] > 0 and ci["evictions"] > 0
    if traced:
        assert ci["finished"] == ci["sampled"] == len(keys_ts)


def test_windowed_parity_drop_policy_drops_late():
    stream = _steady_stream()
    late = [(1, 3.0), (2, 5.0), (1, 0.5)]
    keys_ts = stream[:18] + late + stream[18:]
    ci = assert_windowed_parity(keys_ts, 40.0, "drop")
    assert ci["late_dropped"] > 0


def test_windowed_parity_horizon_drop():
    # beyond watermark - lateness: dropped in BOTH policies
    stream = _steady_stream()
    keys_ts = stream + [(5, 0.1), (6, 0.2)]
    ci = assert_windowed_parity(keys_ts, 0.0, "drop")
    assert ci["late_dropped"] >= 2


def test_windowed_parity_max_kind():
    stream = [(k, i * 1.7) for i, k in enumerate(
        [1, 2, 1, 3, 1, 2, 4, 1, 5, 2, 1, 3, 6, 1, 2, 7, 1, 1])]

    def agg(tup, state):
        p = tup.payload
        return p if state is None or p > state else state

    def emit(base, wid, end, acc):
        return ("max", base, acc) if acc is not None else None

    # payload must be a pure function of (k, ts): both runs see it
    assert_windowed_parity(
        stream, 0.0, "drop", spec=max_spec(), agg=agg, emit=emit,
        payload_of=lambda k, ts: (k * 7919 + int(ts * 10)) % 9973 + 1)


if HAVE_HYPOTHESIS:
    _streams = st.lists(
        st.tuples(st.integers(min_value=0, max_value=9),
                  st.floats(min_value=0.0, max_value=60.0, width=16,
                            allow_nan=False)),
        min_size=1, max_size=36)

    @settings(max_examples=10, deadline=None)
    @given(_streams, st.sampled_from([(0.0, "drop"), (25.0, "update"),
                                      (25.0, "drop")]))
    def test_windowed_parity_property(keys_ts, pol):
        lateness, policy = pol
        assert_windowed_parity(keys_ts, lateness, policy)


# ------------------------------------------------- chaos-schedule parity
def run_chaos_count(fused, events=True, seed=29, t_cut=0.9):
    """Count-per-key through a LIVE engine run (free-running async I/O,
    not the quiesced protocol): replayable source, periodic checkpoints,
    and a chaos-style failure + load-shift schedule on the sim clock.
    The generator is cut on the source's logical clock, so recovery
    replay and the load shift change when records arrive but never which
    records exist — final state must be a pure function of the seed.

    Migration is the one chaos kind excluded here: the fused plane
    forbids the shard plane (test_fused_forbids_shards), so parity runs
    over the remaining kinds.
    """
    import numpy as np

    from repro.streaming.engine import SourceOp
    from repro.streaming.recovery import CheckpointCoordinator

    eng = Engine()
    rng = np.random.Generator(np.random.PCG64(seed))

    def gen(lt):
        if lt >= t_cut:
            return None
        return int(rng.integers(20)), None, 64

    def apply_count(tup, state):
        return ((state or 0) + 1, [])

    kw = dict(policy="tac", mode="async", cache_capacity=8 * 64,
              state_size=64, io_workers=2)
    if fused:
        kw["fused"] = count_spec()
        kw["fused_batch"] = 8
    src = eng.add(SourceOp(eng, "src", 1, 4000.0, gen, replayable=True))
    op = eng.add(StatefulOp(eng, "agg", 1, apply_count, LOCAL_NVME, **kw))
    eng.connect(src, op)

    coord = CheckpointCoordinator(eng, interval=0.2)
    coord.start()
    if events:
        def fire_failure():
            if coord.in_recovery:
                eng.sim.after(0.05, fire_failure)
                return
            coord.fail(mode="warmed", down_time=0.05, replay_speedup=4.0)

        eng.sim.at(0.45, fire_failure)
        eng.sim.at(0.60, setattr, src, "rate_scale", 2.5)
        eng.sim.at(0.80, setattr, src, "rate_scale", 1.0)

    src.start()
    eng.sim.after(eng.marker_interval, eng._inject_marker)
    t = 0.0
    while True:
        t += 0.25
        eng.sim.run_until(t)
        log_end = src.log_base[0] + len(src.log[0])
        if (src.logical_t[0] >= t_cut and src.replay_pos[0] >= log_end
                and not coord.in_recovery):
            break
        assert t < 30.0, "chaos parity run failed to quiesce"
    eng.sim.run_until(t + 0.5)               # drain in-flight I/O
    src.stopped = True
    state = {k: v for k, v in _final_state(op, 64).items()
             if v is not None}
    return state, coord.failures


def test_chaos_schedule_parity_interpreted_vs_fused():
    """Across a failure + load-shift schedule, the fused device path and
    the interpreted path land on bit-identical final keyed state — and
    both equal the unperturbed run (exactly-once state effects)."""
    perturbed_interp, f1 = run_chaos_count(fused=False)
    perturbed_fused, f2 = run_chaos_count(fused=True)
    golden, _ = run_chaos_count(fused=False, events=False)
    assert f1 >= 1 and f2 >= 1               # the failure actually fired
    assert golden and sum(golden.values()) > 0
    assert perturbed_interp == golden
    assert perturbed_fused == golden


# -------------------------------------------------------------- unit layer
def test_fused_requires_tac_policy():
    eng = Engine()
    with pytest.raises(ValueError):
        StatefulOp(eng, "x", 1, lambda t, s: (s, []), LOCAL_NVME,
                   cache_capacity=64, policy="lru", mode="async",
                   fused=count_spec())


def test_fused_forbids_shards():
    from repro.streaming.shards import ShardPlane
    eng = Engine()
    with pytest.raises(ValueError):
        StatefulOp(eng, "x", 1, lambda t, s: (s, []), LOCAL_NVME,
                   cache_capacity=64, policy="tac", mode="async",
                   fused=count_spec(), shards=ShardPlane(2, 1))


def test_fused_spec_rejects_unknown_kind():
    with pytest.raises(ValueError):
        FusedSpec(kind="median")


def test_fusedplane_single_key_ops():
    plane = FusedPlane(4 * 8, 8, count_spec(), batch=4)
    assert plane.lookup("a", 1.0) is None        # miss
    plane.insert("a", 3, 1.0, dirty=True)
    assert plane.lookup("a", 2.0) == 3
    plane.write("a", 5, 3.0)
    assert plane.lookup("a", 3.0) == 5
    assert plane.contains("a")
    assert len(plane) == 1
    assert plane.drop("a")
    assert not plane.contains("a")
    assert plane.hits == 2 and plane.misses == 1


def test_fusedplane_eviction_and_writeback():
    plane = FusedPlane(2 * 8, 8, count_spec(), batch=4)
    plane.insert("a", 1, 1.0, dirty=True)
    plane.insert("b", 2, 2.0, dirty=True)
    plane.insert("c", 3, 3.0, dirty=True)       # evicts "a" (min ts)
    assert plane.evictions == 1
    assert plane.eviction_block() == {"capacity.demand": 1}
    assert "a" in plane.evict_buffer            # dirty victim staged
    assert plane.lookup("a", 4.0) == 1          # restore from the buffer
    assert plane.evictions == 2                 # ...which evicted again
    wb = plane.pop_writeback()
    assert wb is not None and plane.writebacks == 1


def test_fusedplane_batch_step_composes_duplicates():
    import numpy as np
    spec = count_spec()
    plane = FusedPlane(4 * 8, 8, spec, batch=8)
    plane.insert("k", 10, 1.0, dirty=False)
    lanes = [Lane("k", 2.0, spec.weight(None), False, False, None)
             for _ in range(3)]
    res = plane.batch_step(lanes)
    assert res.hit.all()
    # prefix composition: lane i sees the value AFTER its own update
    assert [plane.decode_lane(res, i) for i in range(3)] == [11, 12, 13]
    assert plane.lookup("k", 3.0) == 13
    assert plane.device_hits == 3 and plane.lanes == 3
    assert 0.0 < plane.fill_ratio <= 1.0
    miss = plane.batch_step(
        [Lane("nope", 4.0, spec.weight(None), False, False, None)])
    assert not miss.hit.any() and plane.device_misses == 1
    assert isinstance(res.new_vals, np.ndarray)


def test_batch_step_is_one_transfer_each_way():
    """Each device call hands the device one host array (the lane slab),
    and a batch step reads one back: queued drops and admissions land in
    their own calls first, one array each."""
    spec = count_spec()
    plane = FusedPlane(4 * 8, 8, spec, batch=8)
    for i, k in enumerate("abc"):
        plane.insert(k, i, 1.0, dirty=False)
    plane.drop("c")
    lanes = [Lane(k, 2.0, spec.weight(None), False, False, None)
             for k in "aab"]
    for expect in ({"fused_step": 1, "fused_admit": 1, "drop_slots": 1},
                   {"fused_step": 1}):
        calls, moved = dict(plane.calls), dict(plane.transfers)
        plane.batch_step(lanes)
        d_calls = {p: n - calls[p] for p, n in plane.calls.items()
                   if n != calls[p]}
        assert d_calls == expect
        assert plane.transfers["to_device"] - moved["to_device"] == \
            sum(expect.values())
        assert plane.transfers["to_host"] - moved["to_host"] == 1


def test_value_shadow_takes_the_last_update_lane_to_the_victim():
    """Two update lanes of one key in one batch, then that key's
    eviction: the victim written back carries the LAST lane's value
    (the device scatter's last-write-wins), as the interpreted TAC's
    lane-by-lane loop does, and no row is read from the device."""
    from repro.core.tac import TimestampAwareCache
    spec = count_spec()
    plane = FusedPlane(2 * 8, 8, spec, batch=8)
    ref = TimestampAwareCache(2)
    for c in (plane, ref):
        c.insert("k", 10, 1.0, dirty=False)
        c.insert("j", 1, 1.5, dirty=False)
    lanes = [Lane("k", 2.0, (2.0,), False, False, None),
             Lane("k", 2.5, (3.0,), False, False, None)]
    res = plane.batch_step(lanes)
    assert res.hit.all()
    for ln in lanes:
        ref.write("k", ref.lookup("k", ln.ts) + int(ln.weight[0]), ln.ts)
    assert_shadow_is_pool(plane)
    for c in (plane, ref):
        c.renew("j", 3.0)
        c.insert("m", 0, 4.0, dirty=False)        # evicts "k", dirty
    assert plane.evict_buffer["k"].state == ref.evict_buffer["k"].state \
        == 15
    assert plane.victim_reads == plane.shadow_reads == 1
    assert plane.calls["gather_rows"] == 0
    assert_shadow_is_pool(plane)


def _query_engine(query, seed):
    if query.startswith("ysb"):
        from repro.streaming.ysb import YSBConfig, build_ysb
        if query == "ysb-campaign":       # YSB as published
            cfg = YSBConfig(rate=2_000.0, n_ads=5_000, seed=seed,
                            watermark_interval=0.05, oo_bound=0.0)
            return build_ysb("tac", "prefetch", cfg, fused=True,
                             fused_batch=64, cache_entries=256,
                             parallelism=1, source_parallelism=1,
                             campaign_window_s=0.5)
        cfg = YSBConfig(rate=2_000.0, n_ads=5_000, seed=seed)
        return build_ysb("tac", "prefetch", cfg, fused=True, fused_batch=64,
                         cache_entries=256, parallelism=1,
                         source_parallelism=1)
    from repro.streaming.nexmark import NexmarkConfig, build_query
    cfg = NexmarkConfig(rate=2_000.0, active_window=60.0, oo_bound=0.3,
                        seed=seed)
    return build_query(query, "tac", "prefetch", cfg, fused=True,
                       fused_batch=64, cache_entries=256, parallelism=1,
                       source_parallelism=1)


@pytest.mark.parametrize("query,kind", [("q5", "sum"), ("q7", "max"),
                                        ("ysb", "read")])
def test_value_shadow_is_the_pool_in_query_runs(query, kind):
    """Small fused runs of windowed counts with evictions (q5), a
    windowed max (q7) and the read-only join (YSB): before and after
    every device batch, and after the drain, the value shadow equals
    the pool at every occupied slot."""
    from repro.streaming.engine import SourceOp
    eng = _query_engine(query, 11)
    op = eng.operators["stateful"]
    plane = op.caches[0]
    assert isinstance(plane, FusedPlane) and plane.spec.kind == kind
    step, checked = plane.batch_step, [0]

    def checked_step(lanes):
        assert_shadow_is_pool(plane)
        res = step(lanes)
        assert_shadow_is_pool(plane)
        checked[0] += 1
        return res
    plane.batch_step = checked_step
    eng.run(duration=2.0)
    for src in eng.operators.values():
        if isinstance(src, SourceOp):
            src.stopped = True
    eng.sim.run_until(20.0)
    assert_shadow_is_pool(plane)
    assert checked[0] > 10
    assert plane.calls["gather_rows"] == 0
    if query == "q5":                 # dirty panes evicted and read back
        assert plane.victim_reads > 0
    if query == "ysb":
        assert plane.victim_reads == 0
    assert plane.shadow_reads >= plane.victim_reads


def test_fusedplane_flush_and_export_roundtrip():
    plane = FusedPlane(4 * 8, 8, count_spec(), batch=4)
    plane.insert("a", 1, 1.0, dirty=True)
    plane.insert("b", 2, 2.0, dirty=False)
    dirty = plane.flush_dirty()
    assert [e.key for e in dirty] == ["a"]
    ents = plane.export_entries(lambda k: True)
    assert {e.key for e in ents} == {"a", "b"}
    assert len(plane) == 0
    plane.import_entries(ents)
    assert plane.lookup("a", 5.0) == 1 and plane.lookup("b", 5.0) == 2


@pytest.mark.parametrize("fused", [False, True])
def test_fetch_completion_never_rolls_back_a_resident_entry(fused):
    """A fetch that lands after its key became resident again (served
    from the write-back memtable while the fetch was in flight) read an
    OLDER state than the resident one: it renews the entry, it must not
    overwrite it."""
    from repro.streaming.engine import _IOReq
    eng = Engine()
    kw = dict(fused=count_spec(), fused_batch=8) if fused else {}
    op = StatefulOp(eng, "agg", 1, lambda t, s: ((s or 0) + 1, []),
                    LOCAL_NVME, cache_capacity=8 * 64, policy="tac",
                    mode="async", state_size=64, **kw)
    eng.add(op)
    op.backends[0].write("k", 5, 64)          # what the fetch read
    op.caches[0].insert("k", 6, 1.0, dirty=True, size=64)   # newer
    op.in_flight[0].add("k")
    op._io_done(0, _IOReq("prefetch", "k", 4.0), 1e-4)
    assert op.caches[0].lookup("k", 2.0) == 6
    assert op.caches[0].flush_dirty()[0].state == 6


@pytest.mark.parametrize("query", ["q5", "ysb", "ysb-campaign"])
def test_one_transfer_each_way_per_device_call_in_query_runs(query):
    """Small fused runs of each deployment the benchmark runs (q5's
    windowed count, YSB's read join, YSB as published with its campaign
    count): every plane hands the device one array per call and reads
    one back per batch step."""
    eng = _query_engine(query, 13)
    planes = [c for op in eng.operators.values()
              if isinstance(op, StatefulOp) for c in op.caches
              if isinstance(c, FusedPlane)]
    assert len(planes) == (2 if query == "ysb-campaign" else 1)
    eng.run(duration=2.0)
    for plane in planes:
        assert plane.calls["fused_step"] > 10
        assert plane.transfers == {
            "to_device": sum(plane.calls.values()),
            "to_host": plane.calls["fused_step"]}
    # rolled into the §12 registry beside the calls, per operator
    eng._sync_registry()
    reg = eng.registry.snapshot()
    for name, op in eng.operators.items():
        fp = [c for c in getattr(op, "caches", ()) if c in planes]
        for d in FusedPlane.TRANSFERS:
            if fp:
                assert reg[f"engine.{name}.fused.transfers.{d}"] == \
                    sum(c.transfers[d] for c in fp)


# ------------------------------------------------------ deferred readback
def _plane_with_outstanding_update():
    """A plane of two slots holding "k" (10, clean) and "j" (1), with one
    step outstanding that adds 1 to "k" twice."""
    spec = count_spec()
    plane = FusedPlane(2 * 8, 8, spec, batch=8)
    plane.insert("k", 10, 1.0, dirty=False)
    plane.insert("j", 1, 1.5, dirty=False)
    res = plane.batch_step([Lane("k", 2.0, (1.0,), False, False, None)
                            for _ in range(2)])
    return plane, res


def test_plain_update_step_defers_its_readback():
    """A plain update step returns without reading its output slab; the
    host's hits stand in for the device's, and the slab is read once,
    at the plane's next step, which counts it as deferred."""
    import numpy as np
    plane, res = _plane_with_outstanding_update()
    reads, out = [0], plane._step.out
    real_read = out.read

    def counting_read():
        reads[0] += 1
        return real_read()
    out.read = counting_read
    assert res.hit.tolist() == [True, True]
    assert plane.device_hits == 2 and plane.hits == 2
    assert plane._sdirty[plane._slot_by_key["k"]]
    assert plane.lookup("j", 2.0) == 1      # a slot the step left alone
    assert reads[0] == 0 and plane.deferred == plane.forced_settles == 0
    plane.batch_step([Lane("j", 3.0, (1.0,), False, False, None)])
    assert reads[0] == 1
    assert plane.deferred == 1 and plane.forced_settles == 0
    assert [plane.decode_lane(res, i) for i in range(2)] == [11, 12]
    assert plane.forced_settles == 0 and reads[0] == 1
    assert isinstance(res.new_vals, np.ndarray)
    assert_shadow_is_pool(plane)
    assert plane.deferred == 2              # the second step, at _sync


@pytest.mark.parametrize("field", ["hit", "slots"])
def test_settle_counts_a_device_that_differs_from_the_host(field):
    """A device whose answer is not the host directory's is a fault under
    the plane: the settle counts it and hands on the device's values."""
    import numpy as np
    plane, res = _plane_with_outstanding_update()
    step = plane._step
    wrong = ~step.hit if field == "hit" else np.zeros_like(step.slots) + 1
    plane._step = step._replace(**{field: wrong})
    plane._sync()
    assert plane.hit_mismatches == 1 and plane.deferred == 1
    assert [plane.decode_lane(res, i) for i in range(2)] == [11, 12]
    plane.batch_step([Lane("j", 3.0, (1.0,), False, False, None)])
    plane._sync()
    assert plane.hit_mismatches == 1        # the next step agrees


def _evict_k(plane):
    plane.renew("j", 3.0)
    plane.insert("m", 0, 4.0, dirty=False)   # evicts "k", dirty
    return plane.evict_buffer["k"].state


def _drop_k_insert_n(plane):
    slot = plane._slot_by_key["k"]
    plane.drop("k")
    plane.insert("n", 7, 3.0, dirty=True)    # takes k's freed slot
    assert plane._slot_by_key["n"] == slot
    return plane.lookup("n", 3.0)


def _overwrite_k(plane):
    plane.write("k", 50, 3.0)
    return plane.lookup("k", 3.0)


@pytest.mark.parametrize("access,want", [
    (_evict_k, 12), (lambda p: p.lookup("k", 3.0), 12),
    (_overwrite_k, 50), (_drop_k_insert_n, 7)],
    ids=["dirty_victim", "lookup", "overwrite", "drop_then_insert"])
def test_slot_access_settles_the_outstanding_step_first(access, want):
    """A dirty victim, a slot read, an overwriting write and a new key in
    a slot the step updated each settle the step before touching the
    slot: they see its value, and the settle never lands on the newer
    occupant."""
    plane, _ = _plane_with_outstanding_update()
    assert access(plane) == want
    assert plane.forced_settles == 1 and plane.deferred == 0
    assert plane._step is None
    assert_shadow_is_pool(plane)
    assert plane.calls["gather_rows"] == 0


@pytest.mark.parametrize("fire,late", [(True, False), (False, True)],
                         ids=["fire", "late_update"])
def test_fire_and_late_lanes_decode_the_device_values(fire, late):
    import numpy as np
    spec = count_spec()
    plane = FusedPlane(4 * 8, 8, spec, batch=8)
    plane.insert("p", 5, 1.0, dirty=False)
    w = (0.0,) if fire else (1.0,)
    res = plane.batch_step([Lane("p", 2.0, w, fire, late, None)])
    out = plane._step.out
    assert plane.decode_lane(res, 0) == (5 if fire else 6)
    assert plane.forced_settles == 1 and plane.deferred == 0
    dev = out.read()
    assert np.array_equal(res.new_vals, dev["new_vals"][:1])
    assert np.array_equal(res.present, dev["present"][:1])
    assert_shadow_is_pool(plane)


@pytest.mark.parametrize("query", ["q5", "ysb", "ysb-campaign"])
def test_host_predicts_the_device_hits_in_query_runs(query):
    """Every step of each deployment the benchmark runs settles, and so
    has its device hits, slots and tallies held to the host directory's.  q5's plain updates defer; every lane of the
    read join emits, so its steps settle at once."""
    eng = _query_engine(query, 17)
    planes = [c for op in eng.operators.values()
              if isinstance(op, StatefulOp) for c in op.caches
              if isinstance(c, FusedPlane)]
    eng.run(duration=2.0)
    for plane in planes:
        plane._sync()
        steps = plane.calls["fused_step"]
        assert steps > 10
        assert plane.deferred + plane.forced_settles == steps
        assert plane.hit_mismatches == 0
        share = plane.deferred / steps
        if plane.spec.kind == "read":
            assert share < 0.1
        else:
            assert share >= 0.7, (query, share)
    eng._sync_registry()
    reg = eng.registry.snapshot()
    for name, op in eng.operators.items():
        fp = [c for c in getattr(op, "caches", ()) if c in planes]
        if fp:
            for k in ("deferred", "forced_settles", "hit_mismatches"):
                assert reg[f"engine.{name}.fused.{k}"] == \
                    sum(getattr(c, k) for c in fp)


def test_admissions_that_never_land_are_counted_not_fatal(monkeypatch):
    """A device whose admissions never land answers misses where the host
    directory holds the keys: every such step counts in
    ``hit_mismatches`` and the run goes on to its end, its results the
    device's, for a comparison downstream to judge."""
    from repro.core import tac_jax
    real_admit = tac_jax.fused_admit

    def admit(state, pages, *a):
        return state, pages, real_admit(state, pages, *a)[2]
    monkeypatch.setattr(tac_jax, "fused_admit", admit)
    eng = _query_engine("q5", 17)
    planes = [c for op in eng.operators.values()
              if isinstance(op, StatefulOp) for c in op.caches
              if isinstance(c, FusedPlane)]
    eng.run(duration=1.0)
    for plane in planes:
        plane._sync()
    assert sum(p.hit_mismatches for p in planes) > 0
    assert all(p.deferred + p.forced_settles == p.calls["fused_step"]
               for p in planes)
