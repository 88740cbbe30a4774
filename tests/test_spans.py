"""Wall-clock spans and device-call counters of the host path
(``repro.obs.spans``, DESIGN.md §12).

The recorder's arithmetic under a fake clock; that a disabled recorder
costs the event loop nothing; and small fused q5 and YSB runs on the
CPU with spans on, against an outside count of the same device calls,
the same runs with spans off, and the wall clock.
"""
import collections
import os
import re
import time

import pytest

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import jax  # noqa: E402

from repro.obs import NULL_SPANS, SpanRecorder, matches_catalog  # noqa: E402
from repro.obs.spans import callback_name  # noqa: E402
from repro.streaming.engine import Sim, SourceOp  # noqa: E402
from repro.streaming.fused import FusedPlane  # noqa: E402

PROGRAMS = FusedPlane.PROGRAMS
NAME = re.compile(r"stream\.[a-z_]+\.[a-z_]+")


class FakeClock:
    def __init__(self):
        self.t = 0

    def __call__(self):
        return self.t


class CountingAnnotation:
    """Stands in for ``jax.profiler.TraceAnnotation`` with a profiler
    session collecting: counts constructions, enters and exits."""
    active = True
    built = 0
    open = 0

    def __init__(self, name):
        type(self).built += 1
        self.name = name

    @classmethod
    def is_enabled(cls):
        return cls.active

    def __enter__(self):
        type(self).open += 1

    def __exit__(self, *exc):
        type(self).open -= 1


@pytest.fixture
def annotations(monkeypatch):
    monkeypatch.setattr(CountingAnnotation, "built", 0)
    monkeypatch.setattr(CountingAnnotation, "open", 0)
    monkeypatch.setattr(CountingAnnotation, "active", True)
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", CountingAnnotation)
    return CountingAnnotation


# ------------------------------------------------------------ the recorder
def test_nested_self_times_under_a_fake_clock(annotations):
    clock = FakeClock()
    sp = SpanRecorder(clock)
    sp.enable()
    s0 = sp.snapshot()
    clock.t = 5                       # 5 ns outside any span
    sp.enter("stream.a.outer")
    clock.t = 15
    sp.enter("stream.b.inner")
    clock.t = 45
    sp.switch("stream.b.next")        # inner: 30
    clock.t = 50
    sp.exit()                         # next: 5
    clock.t = 60
    sp.exit()                         # outer: 10 + 10
    clock.t = 61
    s1 = sp.snapshot()
    assert s1["spans"] == {"stream.a.outer": [1, 20],
                           "stream.b.inner": [1, 30],
                           "stream.b.next": [1, 5]}
    assert s1["unspanned_ns"] - s0["unspanned_ns"] == 6
    assert sum(ns for _, ns in s1["spans"].values()) \
        + s1["unspanned_ns"] - s0["unspanned_ns"] \
        == s1["wall_ns"] - s0["wall_ns"]
    assert annotations.built == 3 and annotations.open == 0


def test_callback_span_excludes_nested_spans_and_survives_a_raise():
    clock = FakeClock()
    sp = SpanRecorder(clock)
    sp.enable()
    sim = Sim(sp)

    class Op:
        name = "parser"

        def _finish(self, n):
            clock.t += 7
            sp.enter("stream.fused.stage")
            clock.t += n
            sp.exit()
            clock.t += 1

        def _boom(self):
            sp.enter("stream.fused.dispatch")
            clock.t += 2
            raise KeyError("x")

    op = Op()
    sim.at(1.0, op._finish, 100)
    sim.at(2.0, op._finish, 50)
    sim.run_until(2.0)
    sim.at(3.0, op._boom)
    with pytest.raises(KeyError):
        sim.run_until(3.0)
    assert sp.counts == {"stream.sim.run_until": 2,
                         "stream.parser.finish": 2,
                         "stream.fused.stage": 2,
                         "stream.parser.boom": 1,
                         "stream.fused.dispatch": 1}
    self_ns = {n: ns for n, (_, ns) in sp.snapshot()["spans"].items()}
    assert self_ns["stream.parser.finish"] == 16
    assert self_ns["stream.fused.stage"] == 150
    assert self_ns["stream.fused.dispatch"] == 2
    assert self_ns["stream.sim.run_until"] == 0
    assert sp._stack == []            # the raise closed every span


def test_callback_names_follow_the_owner_not_a_closure():
    class Op:
        name = "stateful"

        def deliver_batch(self, sub, batch, origin=None):
            return None

    class Channel:
        def _timeout_flush(self, s, d):
            return None

    op = Op()
    inner = op.deliver_batch

    def deliver_batch(sub, batch, origin=None):   # a benchmark's wrapper
        return inner(sub, batch, origin)

    assert callback_name(op.deliver_batch) == "stream.stateful.deliver_batch"
    assert callback_name(deliver_batch) == "stream.stateful.deliver_batch"
    assert callback_name(Channel()._timeout_flush) \
        == "stream.channel.timeout_flush"
    assert callback_name(lambda: None) == "stream.call.lambda"


def test_null_spans_stays_off():
    with pytest.raises(RuntimeError):
        NULL_SPANS.enable()
    assert not NULL_SPANS.enabled
    assert Sim().spans is NULL_SPANS


# ------------------------------------------------- small fused runs (CPU)
def _build(query: str, seed: int):
    if query == "q5":
        from repro.streaming.nexmark import NexmarkConfig, build_query
        cfg = NexmarkConfig(rate=2_000.0, active_window=60.0, oo_bound=0.3,
                            seed=seed)
        return build_query("q5", "tac", "prefetch", cfg, fused=True,
                           fused_batch=64, cache_entries=256, parallelism=1,
                           source_parallelism=1)
    from repro.streaming.ysb import YSBConfig, build_ysb
    cfg = YSBConfig(rate=2_000.0, n_ads=5_000, seed=seed)
    return build_ysb("tac", "prefetch", cfg, fused=True, fused_batch=64,
                     cache_entries=256, parallelism=1, source_parallelism=1)


class OutsideCalls:
    """Device calls counted from outside the program, the way the
    benchmark's host spans count them: a proxy in place of ``plane._tj``
    that wraps each program."""

    def __init__(self, planes):
        self.calls = collections.Counter()
        for p in planes:
            tj = p._tj
            proxy = type("TacJaxCount", (), {})()
            for n in dir(tj):
                if not n.startswith("__"):
                    setattr(proxy, n, getattr(tj, n))
            for n in PROGRAMS:
                setattr(proxy, n, self._wrap(n, getattr(tj, n)))
            p._tj = proxy

    def _wrap(self, name, fn):
        def call(*a, **k):
            self.calls[name] += 1
            return fn(*a, **k)
        return call


def _run(query: str, seed: int, spans: bool):
    """Warm up, time one stretch of ``run_until``, drain; return what
    spans must not change and what they measured."""
    eng = _build(query, seed)
    if spans:
        eng.enable_spans()
    op, sink = eng.operators["stateful"], eng.operators["sink"]
    planes = [c for c in op.caches if isinstance(c, FusedPlane)]
    outside = OutsideCalls(planes)
    # dirty victims with no queued row, counted from outside
    dirty_reads = [0]
    for p in planes:
        def account(slot, reason, p=p, inner=p._account_eviction):
            dirty_reads[0] += bool(p._sdirty[slot]) \
                and slot not in p._pending_admits
            return inner(slot, reason)
        p._account_eviction = account
    # the benchmark's kind of wrapper on the keyed operator's input
    deliver, arrived = op.deliver_batch, [0]

    def deliver_batch(sub, batch, origin=None):
        arrived[0] += len(batch)
        return deliver(sub, batch, origin)
    op.deliver_batch = deliver_batch
    emits = []
    process = sink.process

    def sink_process(sub, tup):
        emits.append((tup.ts, repr(tup.key), repr(tup.payload)))
        return process(sub, tup)
    sink.process = sink_process

    eng.run(duration=1.0)             # starts the sources: 1 s warm-up
    s0 = eng.spans.snapshot()
    w0 = time.perf_counter_ns()
    eng.sim.run_until(3.0)
    wall = time.perf_counter_ns() - w0
    s1 = eng.spans.snapshot()
    for src in eng.operators.values():
        if isinstance(src, SourceOp):
            src.stopped = True
    eng.sim.run_until(20.0)
    state = {}
    for e in op.caches[0].flush_dirty():
        op.backends[0].write(e.key, e.state, op.state_size)
    state.update(op.backends[0].data)
    state.update({k: e.state for k, e in op.caches[0].entries.items()})
    metrics = eng.metrics(3.0, 0.0)
    return {"eng": eng, "planes": planes, "outside": outside.calls,
            "dirty_reads": dirty_reads[0], "arrived": arrived[0],
            "emits": sorted(emits), "state": repr(sorted(state.items())),
            "s0": s0, "s1": s1, "wall_ns": wall, "metrics": metrics}


_RUNS = {}


def _runs(query):
    if query not in _RUNS:
        with pytest.MonkeyPatch.context() as mp:
            mp.setattr(jax.profiler, "TraceAnnotation", CountingAnnotation)
            mp.setattr(CountingAnnotation, "built", 0)
            mp.setattr(CountingAnnotation, "active", True)

            def plain_loop_only(self, sim, t_end):
                raise AssertionError("spans are off")
            mp.setattr(SpanRecorder, "dispatch", plain_loop_only)
            off = _run(query, 7, spans=False)
            off["annotations"] = CountingAnnotation.built
        on = _run(query, 7, spans=True)
        _RUNS[query] = (off, on)
    return _RUNS[query]


@pytest.mark.parametrize("query", ["q5", "ysb"])
def test_spans_off_record_nothing_and_change_nothing(query):
    off, on = _runs(query)
    assert off["annotations"] == 0
    assert off["eng"].spans.snapshot()["spans"] == {}
    assert "spans" not in off["metrics"]
    # the always-on counters count either way
    assert sum(p.calls["fused_step"] for p in off["planes"]) > 0
    # same seed: same sink results and final keyed state
    assert len(on["emits"]) > 100
    assert on["emits"] == off["emits"]
    assert on["state"] == off["state"]


@pytest.mark.parametrize("query", ["q5", "ysb"])
def test_program_calls_match_an_outside_count(query):
    _, on = _runs(query)
    calls = {n: sum(p.calls[n] for p in on["planes"]) for n in PROGRAMS}
    assert calls == {n: on["outside"][n] for n in PROGRAMS}
    assert calls["fused_step"] > 0 and calls["fused_admit"] > 0
    assert on["metrics"]["stateful_fused"]["calls"] == calls
    spans = on["eng"].spans.counts
    assert spans["stream.fused.dispatch"] == calls["fused_step"]
    assert spans["stream.fused.readback"] == calls["fused_step"]
    assert spans["stream.fused.admit"] <= calls["fused_admit"]


@pytest.mark.parametrize("query", ["q5", "ysb"])
def test_victim_reads_are_the_dirty_evictions_read_back(query):
    _, on = _runs(query)
    victims = sum(p.victim_reads for p in on["planes"])
    assert victims == on["dirty_reads"]
    assert on["eng"].spans.counts.get("stream.fused.victim_read", 0) \
        == victims
    if query == "q5":                 # counts are written back dirty
        assert victims > 0
    else:                             # the join's state is read-only
        assert victims == 0


@pytest.mark.parametrize("query", ["q5", "ysb"])
def test_rows_are_read_from_the_shadow(query):
    """Dirty victims and slot reads come from the host value shadow:
    no row crosses the bus, and every victim read is a shadow read."""
    off, on = _runs(query)
    for run in (off, on):
        assert sum(p.calls["gather_rows"] for p in run["planes"]) == 0
        assert run["outside"]["gather_rows"] == 0
    victims = sum(p.victim_reads for p in on["planes"])
    shadow = sum(p.shadow_reads for p in on["planes"])
    assert shadow >= victims
    assert on["metrics"]["stateful_fused"]["shadow_reads"] == shadow
    if query == "q5":
        assert victims > 0
    else:                             # the join's state is read-only
        assert victims == 0
    snap = on["eng"].registry.snapshot()
    name = "engine.stateful.fused.shadow_reads"
    assert snap[name] == shadow and matches_catalog(name)


@pytest.mark.parametrize("query", ["q5", "ysb"])
def test_self_times_and_unspanned_time_add_up_to_the_wall(query):
    _, on = _runs(query)
    s0, s1 = on["s0"], on["s1"]
    self_ns = sum(ns - s0["spans"].get(n, [0, 0])[1]
                  for n, (_, ns) in s1["spans"].items())
    unspanned = s1["unspanned_ns"] - s0["unspanned_ns"]
    assert self_ns > 0 and unspanned >= 0
    assert self_ns + unspanned == s1["wall_ns"] - s0["wall_ns"]
    assert abs(self_ns + unspanned - on["wall_ns"]) <= 0.01 * on["wall_ns"]
    # the loop's span covers the heap work between callbacks
    assert unspanned < 0.01 * on["wall_ns"]


@pytest.mark.parametrize("query", ["q5", "ysb"])
def test_span_names_are_the_programs_own(query):
    _, on = _runs(query)
    names = set(on["eng"].spans.counts)
    assert all(NAME.fullmatch(n) for n in names), names
    assert {"stream.source.tick", "stream.parser.finish",
            "stream.stateful.deliver_batch", "stream.stateful.drain",
            "stream.stateful.adjudicate", "stream.channel.timeout_flush",
            "stream.fused.stage", "stream.fused.dispatch",
            "stream.fused.readback", "stream.fused.shadow",
            "stream.fused.admit", "stream.sink.finish",
            "stream.fused.pool_read", "stream.sim.run_until"} <= names
    assert on["arrived"] > 0          # the wrapper ran, under its owner


@pytest.mark.parametrize("query", ["q5", "ysb"])
def test_spans_and_counters_reach_the_registry(query):
    _, on = _runs(query)
    eng = on["eng"]
    snap = eng.registry.snapshot()
    assert all(matches_catalog(n) for n in snap), \
        [n for n in snap if not matches_catalog(n)]
    calls = on["metrics"]["stateful_fused"]["calls"]
    for n in PROGRAMS:
        assert snap[f"engine.stateful.fused.calls.{n}"] == calls[n]
    assert snap["engine.stateful.fused.victim_reads"] == \
        on["metrics"]["stateful_fused"]["victim_reads"]
    drain = on["metrics"]["spans"]["stream.stateful.drain"]
    assert snap["engine.span.stateful.drain.count"] == drain["count"]
    assert snap["engine.span.stateful.drain.self_s"] == drain["self_s"] > 0


def test_annotations_open_and_close_with_the_spans(annotations):
    """With a profiler session collecting, every span is one
    annotation of its name, entered and exited in order."""
    eng = _build("ysb", 3)
    eng.enable_spans()
    eng.run(duration=1.5)
    closed = sum(eng.spans.counts.values())
    assert closed > 1000
    assert annotations.built == closed
    assert annotations.open == 0
