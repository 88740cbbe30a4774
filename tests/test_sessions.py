"""Session-window semantics: merge canonicalization, bridging-tuple state
preservation, Aion-style late re-open, moving-deadline hints (DESIGN.md
§15).

The Hypothesis properties pin the assigner's one canonical merge rule
(``SessionWindowAssigner.fold``): the final session registry of a key is
a pure function of the SET of event timestamps — independent of arrival
order — which is exactly what the chaos oracle (streaming/chaos.py)
differentially compares across perturbed runs.  The engine-level tests
then check the same guarantees end to end through the keyed two-step
merge protocol (drain -> absorb), where a pane may be parked on a
backend fetch mid-merge.
"""
import numpy as np
import pytest

from _hypothesis_compat import given, settings, st
from repro.streaming.backend import IN_MEMORY, LOCAL_NVME
from repro.streaming.engine import Engine, SinkOp, SourceOp
from repro.streaming.events import Tuple_, Watermark, WindowKey
from repro.streaming.fused import FusedSpec
from repro.streaming.nexmark import NexmarkConfig, build_query
from repro.streaming.sessions import (SessionWindowAssigner,
                                      SessionWindowedOp)

GAP = 0.1


# ------------------------------------------------------- reference model
def _reference(ts_list, gap):
    """Gap-split over the SORTED timestamps: the textbook session
    definition the incremental fold must agree with."""
    out = []
    for ts in sorted(ts_list):
        if out and ts < out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], ts + gap))
        else:
            out.append((ts, ts + gap))
    return out


def _fold_all(assigner, ts_list):
    sessions = []
    for ts in ts_list:
        assigner.fold(sessions, ts)
    return sessions


def _check_order_independence(values, perm_seed):
    """fold(any permutation) == gap-split reference, with canonical ids."""
    assigner = SessionWindowAssigner(GAP)
    ts_list = [v * 0.03 for v in values]
    rng = np.random.Generator(np.random.PCG64(perm_seed))
    shuffled = list(ts_list)
    rng.shuffle(shuffled)
    sessions = _fold_all(assigner, shuffled)
    got = sorted((s["start"], s["end"], s["wid"]) for s in sessions)
    want = [(a, b, assigner.wid_of(a)) for a, b in
            _reference(ts_list, GAP)]
    assert got == want
    # registry invariants: disjoint, gap-separated, every ts covered
    for (_, e0, _), (s1, _, _) in zip(got, got[1:]):
        assert s1 >= e0
    for ts in ts_list:
        assert sum(1 for s, e, _ in got if s <= ts < e) == 1


# ------------------------------------------------- Hypothesis properties
@settings(max_examples=200, deadline=None)
@given(st.lists(st.integers(0, 120), min_size=1, max_size=40),
       st.integers(0, 2**32 - 1))
def test_fold_is_order_independent(values, perm_seed):
    _check_order_independence(values, perm_seed)


@settings(max_examples=200, deadline=None)
@given(st.lists(st.integers(0, 120), min_size=2, max_size=20),
       st.integers(0, 2**32 - 1))
def test_fold_merge_is_associative(values, perm_seed):
    """Folding the same multiset in two different interleavings (split
    into halves folded in either order) lands on the same registry."""
    assigner = SessionWindowAssigner(GAP)
    ts_list = [v * 0.03 for v in values]
    rng = np.random.Generator(np.random.PCG64(perm_seed))
    half = int(rng.integers(1, len(ts_list)))
    a, b = ts_list[:half], ts_list[half:]
    reg1 = {(s["start"], s["end"], s["wid"])
            for s in _fold_all(assigner, a + b)}
    reg2 = {(s["start"], s["end"], s["wid"])
            for s in _fold_all(assigner, b + a)}
    assert reg1 == reg2


def test_fold_order_independence_fixed_cases():
    """The property logic itself, exercised without Hypothesis so tier-1
    covers it even when the dev extra is absent."""
    for seed, values in [(1, [0, 1, 2]), (2, [0, 40, 20]),
                         (3, [5, 5, 5]), (4, [0, 3, 6, 9, 40, 43, 80]),
                         (5, list(range(0, 120, 4)))]:
        _check_order_independence(values, seed)


# ----------------------------------------------------- assigner unit tests
def test_assigner_canonical_wid_roundtrip():
    a = SessionWindowAssigner(0.5)
    wid = a.wid_of(1.234567)
    assert abs(a.start_of(wid) - 1.234567) < 1e-6
    assert a.end(wid) == pytest.approx(a.start_of(wid) + 0.5)
    with pytest.raises(ValueError):
        SessionWindowAssigner(0.0)


def test_fold_bridging_tuple_absorbs_later_session():
    a = SessionWindowAssigner(0.1)
    sessions = []
    a.fold(sessions, 0.10)                # A: [0.10, 0.20)
    a.fold(sessions, 0.25)                # B: [0.25, 0.35)
    sess, absorbed, extended, created = a.fold(sessions, 0.16)   # bridge
    assert len(sessions) == 1 and not created and extended
    assert [x["wid"] for x in absorbed] == [a.wid_of(0.25)]
    assert sess["wid"] == a.wid_of(0.10)  # earliest ts keeps the id
    assert sess["start"] == 0.10 and sess["end"] == pytest.approx(0.35)


def test_fold_predating_tuple_creates_new_survivor():
    """A tuple EARLIER than every overlapping session owns the canonical
    id: a fresh session absorbs the old pane(s)."""
    a = SessionWindowAssigner(0.1)
    sessions = []
    a.fold(sessions, 0.30)
    sess, absorbed, extended, created = a.fold(sessions, 0.25)
    assert created and extended and len(sessions) == 1
    assert sess["wid"] == a.wid_of(0.25)
    assert [x["wid"] for x in absorbed] == [a.wid_of(0.30)]
    assert sess["end"] == pytest.approx(0.40)


def test_session_op_rejects_fused_plane():
    """Session panes aggregate: a fused plane whose spec only reads
    (the join's) has no update to fold a bid into."""
    eng = Engine()
    with pytest.raises(ValueError):
        SessionWindowedOp(
            eng, "s", 1, SessionWindowAssigner(1.0),
            lambda t, a: (a or 0) + 1, lambda *a: None,
            IN_MEMORY, 10_000, fused=FusedSpec(kind="read"),
            policy="tac", state_size=100)


# ------------------------------------------------- engine-level pipelines
class _CollectSink(SinkOp):
    def __init__(self, *a, **kw):
        super().__init__(*a, **kw)
        self.got = []

    def process(self, sub, tup):
        self.got.append((tup.key, tup.payload))
        return super().process(sub, tup)


def _session_pipeline(eng, gen, oo_bound, lateness=0.0,
                      late_policy="drop", gap=GAP, rate=2000.0):
    src = eng.add(SourceOp(eng, "src", 1, rate, gen,
                           watermark_interval=0.05, oo_bound=oo_bound))
    win = eng.add(SessionWindowedOp(
        eng, "win", 1, SessionWindowAssigner(gap),
        agg_fn=lambda tup, acc: (acc or 0) + 1,
        emit_fn=lambda key, wid, end, acc: ("count", key, wid, acc),
        merge_fn=lambda a, b: (a or 0) + (b or 0),
        backend_model=IN_MEMORY, cache_capacity=1_000_000,
        allowed_lateness=lateness, late_policy=late_policy,
        policy="tac", mode="sync", state_size=100))
    sink = eng.add(_CollectSink(eng, "sink", 1))
    eng.connect(src, win)
    eng.connect(win, sink, partition=lambda k, n: 0)
    return win, sink


def test_bridging_tuple_never_loses_either_sides_state():
    """Two fired-apart clusters merged by a late bridging tuple: the
    surviving pane's count equals ALL five contributions — the two-step
    drain/absorb protocol preserved the absorbed pane's accumulator."""
    eng = Engine()
    script = [0.10, 0.15, 0.30, 0.35, 0.22]      # bridge arrives LAST
    state = {"n": 0}

    def gen(now):
        i = state["n"]
        state["n"] += 1
        if i < len(script):
            return (0, {}, 100, script[i])
        return (1, {}, 100, now)                 # filler drives the wm

    win, sink = _session_pipeline(eng, gen, oo_bound=0.25)
    eng.run(duration=1.0)
    a = SessionWindowAssigner(GAP)
    fired = {(k, wid): n for k, (_, _, wid, n) in sink.got}
    assert fired[(0, a.wid_of(0.10))] == len(script)
    assert (0, a.wid_of(0.30)) not in fired      # absorbed pane never fired
    assert win.sessions_merged == 1
    assert win.merge_drains == win.merge_absorbs == 1
    assert win.late_dropped == 0


def test_late_tuple_inside_lateness_reopens_session():
    """Aion-style late-side update: a tuple landing in a FIRED session
    within the lateness horizon re-opens it, and the re-fire carries the
    refreshed accumulator."""
    eng = Engine()
    state = {"n": 0, "late_sent": False}

    def gen(now):
        i = state["n"]
        state["n"] += 1
        if i == 0:
            return (0, {}, 100, 0.10)
        if i == 1:
            return (0, {}, 100, 0.15)            # session [0.10, 0.25)
        if now > 0.35 and not state["late_sent"]:
            state["late_sent"] = True
            return (0, {}, 100, 0.20)            # late, inside lateness
        # filler ts runs AHEAD of the wm it drives, so key 1's own
        # session never fires and adds no reopen/drop noise
        return (1, {}, 100, now + 0.15)

    win, sink = _session_pipeline(eng, gen, oo_bound=0.0, lateness=0.3,
                                  late_policy="update")
    eng.run(duration=1.0)
    a = SessionWindowAssigner(GAP)
    wid = a.wid_of(0.10)
    emits = [n for k, (_, _, w, n) in sink.got if k == 0 and w == wid]
    assert emits == [2, 3]                       # fire, then refreshed refire
    assert win.sessions_reopened == 1
    assert win.late_dropped == 0


def test_drop_policy_discards_late_tuple_on_fired_session():
    eng = Engine()
    state = {"n": 0, "late_sent": False}

    def gen(now):
        i = state["n"]
        state["n"] += 1
        if i == 0:
            return (0, {}, 100, 0.10)
        if now > 0.35 and not state["late_sent"]:
            state["late_sent"] = True
            return (0, {}, 100, 0.12)
        return (1, {}, 100, now + 0.15)

    win, sink = _session_pipeline(eng, gen, oo_bound=0.0, lateness=0.0,
                                  late_policy="drop")
    eng.run(duration=1.0)
    a = SessionWindowAssigner(GAP)
    emits = [n for k, (_, _, w, n) in sink.got
             if k == 0 and w == a.wid_of(0.10)]
    assert emits == [1]
    assert win.late_dropped >= 1
    assert win.sessions_reopened == 0


# ------------------------------------------- q11 + moving-deadline hints
def test_q11_session_query_moving_deadline_hints():
    """The NEXMark session query end to end under prefetching: sessions
    merge, the lookahead RE-HINTS moved deadlines (bypassing admission),
    and panes prefetch ahead of their fires."""
    cfg = NexmarkConfig(rate=3000, oo_bound=0.2, seed=7,
                        watermark_interval=0.05)
    eng = build_query("q11", "tac", "prefetch", cfg, cache_entries=512,
                      parallelism=2, source_parallelism=1, io_workers=4,
                      buffer_timeout=0.002, session_gap=0.4)
    m = eng.run(duration=1.5, warmup=0.5)
    assert m["stateful_fires"] > 0
    assert m["stateful_sessions_created"] > 0
    assert m["sess_lookahead_rehints"] > 0       # deadlines MOVED
    assert m["stateful_hints_received"] > 0
    assert m["stateful_prefetch_hits"] > 0
    assert m["n_outputs"] > 0
    # both mirrored registries fold the same rule in lockstep
    st_op = eng.operators["stateful"]
    assert st_op.late_dropped == 0


def test_q11_requires_out_of_orderness():
    cfg = NexmarkConfig(rate=1000, oo_bound=0.0)
    with pytest.raises(ValueError):
        build_query("q11", "tac", "prefetch", cfg)


# --------------------------------------- the fused plane (DESIGN.md §15)
def _count_spec():
    return FusedSpec(kind="sum", width=1, weight_of=lambda tup: 1.0,
                     encode=lambda s: None if s is None else [float(s)],
                     decode=lambda v: int(round(float(v[0]))))


# the session counters that are registry decisions, made in input order
# (merge holds and settles depend on when fetches land)
SESSION_COUNTERS = ("sessions_created", "sessions_merged",
                    "sessions_reopened", "fires_superseded",
                    "fires_absorbed", "merge_drains", "merge_absorbs",
                    "fires", "late_dropped", "panes_purged")


def _observed(op, got):
    """Sink results, final keyed state and session counters of a run."""
    state = {}
    for sub in range(op.parallelism):
        for e in op.caches[sub].flush_dirty():
            op.backends[sub].write(e.key, e.state, op.state_size)
        state.update({k: v for k, v in op.backends[sub].data.items()
                      if v is not None})
    m = op.extra_metrics()
    return sorted(got), state, {k: m[k] for k in SESSION_COUNTERS}


def _run_q11(fused, gap, seed=11):
    """NEXMark q11 live at 2,000 events/s over 2 subtasks, 48 slots a
    subtask (panes evict and park), stream cut at gap + 2.5 s, then a
    final watermark fires every session and one more purges them."""
    cfg = NexmarkConfig(rate=2000.0, oo_bound=0.3, seed=seed,
                        watermark_interval=0.05)
    eng = build_query("q11", "tac", "prefetch", cfg, cache_entries=48,
                      parallelism=2, source_parallelism=1, io_workers=4,
                      buffer_timeout=0.002, session_gap=gap,
                      allowed_lateness=1.0, fused=fused, fused_batch=32)
    src, op = eng.operators["source"], eng.operators["stateful"]
    t_cut = gap + 2.5
    inner = src.gen
    src.gen = lambda now: None if now >= t_cut else inner(now)
    sink, got = eng.operators["sink"], []
    process = sink.process
    sink.process = lambda sub, tup: (
        got.append((tup.ts, tup.key, tup.payload, tup.ingest_t)),
        process(sub, tup))[1]
    eng.run(duration=t_cut + 0.5)
    for wm in (t_cut + gap + 5.0, t_cut + gap + 10.0):
        src.wm[0] = wm
        src.emit_watermark(0, wm)
        eng.sim.run_until(eng.sim.t + 1.0)
    src.stopped = True
    eng.sim.run_until(eng.sim.t + 1.0)
    return op, _observed(op, got)


@pytest.mark.parametrize("gap", [1.0, 10.0])
def test_fused_q11_equals_interpreted(gap):
    """The same seed through both planes: the same sink results, final
    keyed state and session counters, with panes evicted, parked,
    merged and re-opened on the way."""
    op_i, interp = _run_q11(False, gap)
    op_f, fused = _run_q11(True, gap)
    assert fused[0] == interp[0]
    assert fused[1] == interp[1]
    assert fused[2] == interp[2]
    c = fused[2]
    assert c["fires"] > 100 and c["sessions_merged"] > 0
    assert c["panes_purged"] > 0
    plane = op_f.caches[0]
    assert plane.evictions > 0 and plane.calls["fused_step"] > 10
    assert plane.hit_mismatches == 0
    # a session fire carries its end as ingest_t (latency_from_end)
    assert all(ingest == ts for ts, _, _, ingest in fused[0])


def _session_op(eng, fused, slots, gap=1.0, lateness=1.0):
    kw = dict(policy="tac", mode="async", cache_capacity=slots * 64,
              state_size=64, io_workers=2, allowed_lateness=lateness,
              late_policy="update", latency_from_end=True)
    if fused:
        kw.update(fused=_count_spec(), fused_batch=8)
    op = eng.add(SessionWindowedOp(
        eng, "win", 1, SessionWindowAssigner(gap),
        agg_fn=lambda tup, acc: (acc or 0) + 1,
        emit_fn=lambda key, wid, end, acc: ("session", key, wid, acc),
        merge_fn=lambda a, b: (a or 0) + (b or 0),
        backend_model=LOCAL_NVME, **kw))
    sink = eng.add(_CollectSink(eng, "sink", 1))
    eng.connect(op, sink)
    return op, sink


def _drive(fused, steps, slots=3):
    """Hand-built input straight into one session operator, quiescing
    after each step: ``("t", [(key, ts), ...])`` delivers tuples, ``("w",
    wm)`` a watermark, ``("tw", [...], wm)`` both in one batch, so the
    watermark queues behind the tuples' merge steps, and ``("wt", wm,
    [...])`` the watermark with tuples queued behind it."""
    eng = Engine()
    op, sink = _session_op(eng, fused, slots)
    got, emit = [], op.emit

    def recording(sub, msg):
        got.append((msg.ts, msg.key, msg.payload, msg.ingest_t))
        return emit(sub, msg)
    op.emit = recording
    t, probes = 0.0, []
    for step in steps:
        if step[0] == "probe":             # which panes are resident
            probes.append({(k, w): op.caches[0].contains(WindowKey(k, w))
                           for k, w in step[1]})
            continue
        kind, wm = step[0], None
        tuples = {"t": step[1], "tw": step[1], "wt": step[-1]}.get(kind, ())
        if kind != "t":
            wm = step[-1] if kind == "tw" else step[1]
        batch = [Tuple_(ts, k, None, 64, 0.0) for k, ts in tuples]
        if wm is not None:
            batch.insert(len(batch) if kind == "tw" else 0, Watermark(wm))
        op.deliver_batch(0, batch)
        t += 0.05
        eng.sim.run_until(t)
    eng.sim.run_until(t + 1.0)
    return op, _observed(op, got), probes


_A = SessionWindowAssigner(1.0)
# key 0: session A at 0.0 and session B at 1.5 ([0, 1) and [1.5, 2.5)),
# then a bid at 0.8 bridges them: A survives, B is drained into it
# three panes of later deadlines, of three slots: A and B are evicted
_FILL = [(1, 1.6), (2, 1.7), (3, 1.8)]
MERGE_CASES = {
    "absorbed_resident": [
        ("t", [(0, 0.0), (0, 1.5)]),
        ("probe", [(0, _A.wid_of(1.5))]),
        ("t", [(0, 0.8)]), ("w", 3.0), ("w", 10.0)],
    "absorbed_parked": [
        ("t", [(0, 0.0), (0, 1.5)]), ("t", _FILL),
        ("probe", [(0, _A.wid_of(1.5))]),
        ("t", [(0, 0.8)]), ("w", 3.0), ("w", 10.0)],
    "fire_held": [
        ("t", [(0, 0.0), (0, 1.5)]), ("t", _FILL),
        ("probe", [(0, _A.wid_of(1.5))]),
        ("tw", [(0, 0.8)], 3.0), ("w", 10.0)],
    "late_reopen": [
        ("t", [(0, 0.0), (0, 1.5)]), ("probe", [(0, _A.wid_of(1.5))]),
        ("t", [(0, 0.8)]), ("w", 3.0),
        ("t", [(0, 2.0)]), ("w", 3.1), ("w", 10.0)],
    # the late bid already queued behind the watermark: the fire still
    # counts what came before its watermark, and the bid re-fires
    "reopen_behind_fire": [
        ("t", [(0, 0.0), (0, 1.5)]), ("probe", [(0, _A.wid_of(1.5))]),
        ("t", [(0, 0.8)]), ("wt", 3.0, [(0, 2.0)]), ("w", 3.1),
        ("w", 10.0)],
}


@pytest.mark.parametrize("case", sorted(MERGE_CASES))
def test_fused_merge_cases_equal_interpreted(case):
    """Each merge case, forced by hand, gives the same emits, state and
    session counters on both planes, and the counts are exact: A's bid,
    B's and the bridge's in one session fire, and a late bid re-opens
    it for a re-fire."""
    runs = [_drive(fused, MERGE_CASES[case]) for fused in (False, True)]
    (op_i, obs_i, probes_i), (op_f, obs_f, probes_f) = runs
    assert obs_f == obs_i
    assert probes_f == probes_i
    got, state, ctr = obs_f
    wid = _A.wid_of(0.0)
    fires = [(ts, p[3]) for ts, k, p, _ in got if k == 0 and p[2] == wid]
    assert ctr["sessions_merged"] == 1 and ctr["merge_absorbs"] == 1
    assert not any(p[2] == _A.wid_of(1.5) for _, k, p, _ in got if k == 0)
    reopens = case in ("late_reopen", "reopen_behind_fire")
    if reopens:
        assert fires == [(2.5, 3), (3.0, 4)]
        assert ctr["sessions_reopened"] == 1
    else:
        assert fires == [(2.5, 3)]
    resident = probes_f[0][(0, _A.wid_of(1.5))]
    assert resident == (case == "absorbed_resident" or reopens)
    for op in (op_i, op_f):
        # the held fire waited for the drain's fetch, then read all three
        assert (op.merge_holds > 0) == (case == "fire_held")
        assert not op.held[0] and not op.pending[0]
    assert all(ingest == ts for ts, _, _, ingest in got)


@pytest.mark.parametrize("fused", [False, True], ids=["interp", "fused"])
@pytest.mark.parametrize("from_end", [True, False], ids=["end", "fire"])
def test_session_fires_carry_their_end_as_ingest_t(fused, from_end):
    """With ``latency_from_end`` a fire's ``ingest_t`` is its session's
    end, so sink latency runs from the end to delivery; without it, the
    simulated time the fire was scheduled."""
    eng = Engine()
    op, _ = _session_op(eng, fused, 8)
    op.latency_from_end = from_end
    got, emit = [], op.emit
    op.emit = lambda sub, msg: (got.append(msg), emit(sub, msg))[1]
    op.deliver_batch(0, [Tuple_(0.0, 0, None, 64, 0.0),
                         Tuple_(0.4, 0, None, 64, 0.0)])
    eng.sim.run_until(0.5)
    op.deliver_batch(0, [Watermark(2.0)])
    eng.sim.run_until(1.0)
    (msg,) = got
    assert msg.payload == ("session", 0, _A.wid_of(0.0), 2)
    assert msg.ts == pytest.approx(1.4)
    assert msg.ingest_t == pytest.approx(1.4 if from_end else 0.5)
