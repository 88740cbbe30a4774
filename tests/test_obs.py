"""Observability-plane tests (DESIGN.md §12): registry/sketch
exactness, zero-cost disabled handles, deterministic per-hint outcome
accounting, TAC eviction-reason splits, critical-path tracing, and the
live-name-vs-catalog contract.

Quick by design: the only engine run is a sub-second q5 smoke.
"""
import json
import math

import pytest

from repro.core.tac import TimestampAwareCache
from repro.obs import (METRIC_CATALOG, MetricsRegistry, NULL_COUNTER,
                       NULL_GAUGE, NULL_HISTOGRAM, PrefetchRecorder,
                       QuantileSketch, STAGES, Tracer, TupleTrace,
                       matches_catalog)


# ------------------------------------------------------------ sketch
def test_sketch_exact_moments():
    sk = QuantileSketch()
    vals = [0.001, 0.002, 0.004, 0.008, 0.5, 1.0, -0.25, 0.0]
    for v in vals:
        sk.observe(v)
    assert sk.count == len(vals)
    assert sk.total == pytest.approx(sum(vals))
    assert sk.vmin == -0.25 and sk.vmax == 1.0
    assert sk.mean == pytest.approx(sum(vals) / len(vals))


def test_sketch_quantile_relative_error():
    sk = QuantileSketch()
    n = 5000
    for i in range(1, n + 1):
        sk.observe(i / 1000.0)              # 1ms .. 5s uniform
    for q in (0.5, 0.9, 0.99):
        exact = q * n / 1000.0
        assert sk.quantile(q) == pytest.approx(exact, rel=0.03)
    # quantiles clamp to observed extremes
    assert sk.quantile(0.0) >= sk.vmin
    assert sk.quantile(1.0) <= sk.vmax


def test_sketch_signed_values_and_merge():
    a, b = QuantileSketch(), QuantileSketch()
    for v in (-0.010, -0.002, 0.003):
        a.observe(v)
    for v in (0.050, 0.200):
        b.observe(v)
    a.merge(b)
    assert a.count == 5
    assert a.vmin == -0.010 and a.vmax == 0.200
    assert a.quantile(0.01) < 0 < a.quantile(0.99)


# ---------------------------------------------------------- registry
def test_registry_typed_instruments_and_snapshot():
    r = MetricsRegistry()
    r.counter("engine.sink.count").inc(3)
    r.gauge("engine.cpu.util").set(0.5)
    r.histogram("engine.sink.latency").observe(0.004)
    assert r.counter("engine.sink.count").value == 3     # memoized handle
    snap = r.snapshot()
    assert snap["engine.sink.count"] == 3
    assert snap["engine.cpu.util"] == 0.5
    assert snap["engine.sink.latency"]["count"] == 1


def test_registry_disabled_hands_out_shared_noops():
    r = MetricsRegistry(enabled=False)
    assert r.counter("x.y") is NULL_COUNTER
    assert r.gauge("x.y") is NULL_GAUGE
    assert r.histogram("x.y") is NULL_HISTOGRAM
    NULL_COUNTER.inc()
    NULL_GAUGE.set(1.0)
    NULL_HISTOGRAM.observe(2.0)             # all no-ops, no state
    assert r.snapshot() == {}


def test_registry_export_jsonl(tmp_path):
    r = MetricsRegistry()
    r.counter("engine.sink.count").inc()
    path = tmp_path / "snap.jsonl"
    r.export_jsonl(str(path), t=1.0)
    r.counter("engine.sink.count").inc()
    r.export_jsonl(str(path), t=2.0)
    lines = [json.loads(x) for x in path.read_text().splitlines()]
    assert [x["t"] for x in lines] == [1.0, 2.0]
    assert lines[1]["metrics"]["engine.sink.count"] == 2


def test_catalog_template_matching():
    assert matches_catalog("engine.sink.latency")
    assert matches_catalog("engine.stateful.prefetch.lead")
    assert matches_catalog("engine.join.evict.capacity.prefetched")
    assert matches_catalog("engine.stateful.shard.7.hints_routed")
    assert matches_catalog("trace.stage.park_wait")
    assert matches_catalog("engine.stateful.fused.calls.gather_rows")
    assert matches_catalog("engine.stateful.fused.victim_reads")
    assert matches_catalog("engine.stateful.fused.transfers.to_device")
    assert matches_catalog("engine.join.fused.transfers.to_host")
    assert matches_catalog("engine.stateful.fused.shadow_reads")
    assert matches_catalog("engine.span.fused.readback.self_s")
    assert matches_catalog("engine.span.source.tick.count")
    assert not matches_catalog("engine.nope")
    assert not matches_catalog("engine.stateful.evict.capacity")  # arity
    assert not matches_catalog("made.up.metric")


# ------------------------------------------- hint outcomes (recorder)
def test_recorder_outcomes_and_signed_leads():
    clock = [0.0]
    r = MetricsRegistry()
    rec = PrefetchRecorder(r, "engine.op", lambda: clock[0])
    cache = TimestampAwareCache(capacity=2)
    cache.recorder = rec

    # staged at t=1.0, first read at t=1.5 -> used, lead +0.5
    clock[0] = 1.0
    cache.insert("a", "A", ts=1.0, prefetched=True)
    clock[0] = 1.5
    assert cache.lookup("a", 1.5) == "A"
    # second read must NOT double-count the use
    cache.lookup("a", 1.6)
    # staged, never read, evicted by capacity -> wasted
    clock[0] = 2.0
    cache.insert("b", "B", ts=0.5, prefetched=True)
    cache.insert("c", "C", ts=3.0)          # demand; evicts min-ts "b"
    cache.insert("d", "D", ts=4.0)          # evicts "a" (used, not wasted)
    # late staging: the tuple parked at t=5.0, staging completed at 5.4
    clock[0] = 5.4
    rec.on_late(first_need_t=5.0)

    assert rec.staged.value == 2
    assert rec.used.value == 1
    assert rec.wasted.value == 1
    assert rec.late.value == 1
    sk = rec.lead.sketch
    assert sk.count == 2                    # one used + one late
    assert sk.vmax == pytest.approx(0.5)    # timely lead
    assert sk.vmin == pytest.approx(-0.4)   # late lead is negative

    q = rec.quality_block(prefetch_hits=3, demand_fetches=1,
                          duplicates=2, late_wm=1)
    assert q["staged"] == 2 and q["used"] == 1 and q["wasted"] == 1
    assert q["late"] == 1 and q["duplicate"] == 2
    assert q["late_watermark"] == 1
    assert q["precision"] == pytest.approx(1 / 3)   # used/(staged+late)
    assert q["recall"] == pytest.approx(3 / 4)
    assert q["lead_min"] == pytest.approx(-0.4)
    assert q["lead_max"] == pytest.approx(0.5)


def test_eviction_reason_split_capacity():
    cache = TimestampAwareCache(capacity=2)
    cache.insert("a", 1, ts=1.0, prefetched=True)
    cache.insert("b", 2, ts=2.0)
    cache.insert("c", 3, ts=3.0)            # evicts "a" (prefetched)
    cache.insert("d", 4, ts=4.0)            # evicts "b" (demand)
    assert cache.eviction_block() == {"capacity.demand": 1,
                                      "capacity.prefetched": 1}


def test_eviction_reason_split_deadline_and_stale():
    cache = TimestampAwareCache(capacity=2, deadline_aware=True)
    cache.set_clock(5.0)
    cache.insert("stale", 1, ts=1.0)        # behind the clock
    cache.insert("near", 2, ts=6.0, prefetched=True)
    cache.insert("far", 3, ts=9.0)          # evicts "stale" first
    assert cache.eviction_block() == {"stale.demand": 1}
    cache.insert("mid", 4, ts=7.0)          # all live: farthest ("far") goes
    assert cache.eviction_block() == {"stale.demand": 1,
                                      "deadline.demand": 1}


# ------------------------------------------------------------ tracer
def test_trace_stage_decomposition():
    tr = TupleTrace(t0=0.0)
    tr.mark_state("op", 0.010)
    tr.mark_park(0.010)
    tr.mark_resume(0.014)
    tr.fetch_s += 0.002
    tr.mark_apply(0.015)
    st = tr.stages(t_sink=0.020)
    assert st["upstream"] == pytest.approx(0.010)
    assert st["park_wait"] == pytest.approx(0.004)
    assert st["sync_fetch"] == pytest.approx(0.002)
    assert st["downstream"] == pytest.approx(0.005)
    assert set(st) == set(STAGES)


def test_tracer_sampling_and_summary():
    r = MetricsRegistry()
    t = Tracer(r)
    assert not t.active
    assert t.maybe_start(0.0) is None       # disabled: never samples
    t.enable(sample_every=4)
    traces = [t.maybe_start(i * 0.1) for i in range(8)]
    live = [x for x in traces if x is not None]
    assert len(live) == 2                   # exactly 1 in 4
    for tr in live:
        tr.mark_state("op", tr.t0 + 0.001)
        tr.hit = True
        t.finish(tr, tr.t0 + 0.003)
        t.finish(tr, tr.t0 + 9.9)           # double-finish is a no-op
    s = t.summary()
    assert s["sampled"] == 2 and s["finished"] == 2
    assert s["probe_hits"] == 2 and s["probe_misses"] == 0
    assert s["dominant_stage"] in STAGES
    assert sum(s[x]["share"] for x in STAGES) == pytest.approx(1.0)
    assert len(t.spans) == 2


# ---------------------------------------------- device-side counters
def test_tac_probe_counted_matches_host_tally():
    jnp = pytest.importorskip("jax.numpy")
    import numpy as np
    from repro.kernels.tac_probe.ops import (bucket_of, tac_probe_counted)

    n_buckets, ways = 8, 2
    keys = jnp.full((n_buckets, ways), -1, jnp.int32)
    vals = jnp.zeros((n_buckets, ways, 1), jnp.int32)
    resident = jnp.asarray([3, 7, 11, 19], jnp.int32)
    b = np.asarray(bucket_of(resident, n_buckets))
    keys_np = np.asarray(keys).copy()
    for i, k in enumerate(np.asarray(resident)):
        w = int(np.argmax(keys_np[b[i]] == -1))
        keys_np[b[i], w] = k
    keys = jnp.asarray(keys_np)
    queries = jnp.asarray([3, 7, 5, 19, 23, 11], jnp.int32)
    _, hit, _, counts = tac_probe_counted(queries, keys, vals)
    hit = np.asarray(hit).astype(bool)
    qb = np.asarray(bucket_of(queries, n_buckets))
    full = np.all(keys_np[qb] != -1, axis=1)
    assert int(counts[0]) == int(hit.sum())
    assert int(counts[1]) == int((~hit & full).sum())


# ------------------------------------------- live engine integration
@pytest.fixture(scope="module")
def q5_metrics():
    from repro.streaming.backend import LOCAL_NVME
    from repro.streaming.nexmark import NexmarkConfig, build_query
    cfg = NexmarkConfig(rate=2_000.0, active_window=1.0, oo_bound=0.3,
                        seed=7)
    eng = build_query("q5", "tac", "prefetch", cfg, cache_entries=128,
                      backend=LOCAL_NVME, parallelism=2,
                      source_parallelism=1, io_workers=4,
                      buffer_timeout=0.002, hint_ts="deadline",
                      window_size=0.5, window_slide=0.25)
    eng.enable_tracing(sample_every=8)
    m = eng.run(duration=1.2, warmup=0.4)
    return eng, m


def test_live_names_all_catalogued(q5_metrics):
    eng, _ = q5_metrics
    uncatalogued = [n for n in eng.registry.names()
                    if not matches_catalog(n)]
    assert uncatalogued == [], uncatalogued


def test_live_hint_quality_block(q5_metrics):
    _, m = q5_metrics
    hq = m["stateful_hint_quality"]
    assert hq["staged"] > 0
    assert hq["used"] > 0
    assert 0.0 < hq["precision"] <= 1.0
    assert 0.0 < hq["recall"] <= 1.0
    # outcomes partition issued stagings
    assert hq["used"] + hq["wasted"] + hq["resident_unused"] \
        == hq["staged"]
    assert "lead_p50" in hq and "lead_p99" in hq
    assert m["stateful_hints_duplicate"] >= 0
    assert m["stateful_access_p99"] >= m["stateful_access_p50"] >= 0.0


def test_live_trace_and_eviction_split(q5_metrics):
    _, m = q5_metrics
    tr = m["trace"]
    assert tr["finished"] > 0
    assert tr["dominant_stage"] in STAGES
    assert sum(tr[s]["share"] for s in STAGES) == pytest.approx(1.0)
    ev = m["stateful_evictions"]
    assert ev and all("." in k for k in ev)
    for k in ev:
        reason, adm = k.split(".")
        assert reason in ("capacity", "deadline", "stale")
        assert adm in ("prefetched", "demand")
    assert m["stateful_watermark_lag"] >= 0.0


def test_live_sink_percentiles_from_sketch(q5_metrics):
    eng, m = q5_metrics
    # percentiles come from the uncapped sketch, not the recent window
    assert 0.0 < m["p50"] <= m["p99"] <= m["p999"] <= m["max"]
    assert m["n_outputs"] == eng._sink_count.value
    assert eng._sink_hist.sketch.count == m["n_outputs"]
    assert math.isfinite(m["throughput"]) and m["throughput"] > 0


def test_catalog_descriptions_nonempty():
    assert len(METRIC_CATALOG) >= 40
    for tmpl, desc in METRIC_CATALOG.items():
        assert desc.strip(), tmpl
