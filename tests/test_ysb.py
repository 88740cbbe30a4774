"""YSB as published (DESIGN.md §10, §14): the ad -> campaign join, then
views counted per campaign in tumbling event-time windows, and the
watermark hold that keeps a keyed operator's watermarks behind the
tuples it has parked on store fetches."""
import collections
import functools
import hashlib
import math

import numpy as np
import pytest

from repro.streaming.events import Tuple_, Watermark
from repro.streaming.ysb import YSBConfig, build_ysb

MODES = [pytest.param(False, id="interpreted"),
         pytest.param(True, id="fused")]


def _chain(fused, seed=3, window=1.0, n_campaigns=100):
    """A small YSB chain: uniform ads over more ads than the join's
    slots, so views park on store fetches."""
    cfg = YSBConfig(rate=4000.0, n_ads=5000, zipf_alpha=0.0, seed=seed,
                    n_campaigns=n_campaigns, watermark_interval=0.05,
                    oo_bound=0.0)
    return build_ysb("tac", "prefetch", cfg, cache_entries=256,
                     parallelism=1, source_parallelism=1, fused=fused,
                     fused_batch=64, campaign_window_s=window,
                     window_cache_entries=256)


def _watch(op):
    """Views ``op`` receives at or behind the last watermark it was
    sent, and all it receives."""
    seen = {"wm": -math.inf, "behind": 0, "views": 0}
    real = op.deliver_batch

    def deliver(sub, batch, origin=None):
        for m in batch:
            if type(m) is Watermark:
                seen["wm"] = max(seen["wm"], m.ts)
            elif type(m) is Tuple_:
                seen["views"] += 1
                seen["behind"] += m.ts <= seen["wm"]
        return real(sub, batch, origin)
    op.deliver_batch = deliver
    return seen


@pytest.mark.parametrize("fused", MODES)
def test_join_holds_watermark_behind_parked_fetches(fused):
    eng = _chain(fused)
    seen = _watch(eng.operators["win_lookahead"])
    eng.run(duration=3.0)
    join = eng.operators["join"]
    assert seen["views"] > 3000
    assert seen["behind"] == 0
    # the hold did its work: misses parked views across watermarks
    assert join.wm_held > 0 and join.wm_hold_s > 0
    assert join.caches[0].misses > 50
    assert eng.operators["stateful"].late_dropped == 0


def test_without_the_hold_views_overtake_the_watermark(monkeypatch):
    """The same chain with the join sending each watermark on as it
    arrives: parked views reach the count behind it."""
    from repro.streaming.engine import Operator, StatefulOp
    monkeypatch.setattr(StatefulOp, "emit_watermark",
                        Operator.emit_watermark)
    eng = _chain(False)
    seen = _watch(eng.operators["win_lookahead"])
    eng.run(duration=3.0)
    assert seen["behind"] > 0


def test_watermark_goes_at_once_with_nothing_parked():
    """Zipf(1) keys over slots that hold them all after the first
    touches, sync mode: nothing parks, so nothing is held."""
    cfg = YSBConfig(rate=4000.0, n_ads=200, zipf_alpha=1.0, seed=4,
                    watermark_interval=0.05, n_campaigns=10)
    eng = build_ysb("tac", "sync", cfg, cache_entries=256, parallelism=1,
                    source_parallelism=1, campaign_window_s=0.5)
    seen = _watch(eng.operators["win_lookahead"])
    eng.run(duration=1.0)
    assert eng.operators["join"].wm_held == 0
    assert seen["behind"] == 0 and seen["wm"] > 0.9


@functools.lru_cache(maxsize=None)
def _counted(fused):
    """Run the chain, stop the source, fire every window; returns (the
    sink's counts, the plain count over the generator's views, the
    count operator's keyed state before the final fire, metrics)."""
    eng = _chain(fused, seed=9, window=0.5)
    src = eng.operators["source"]
    views = []
    gen = src.gen

    def logged(now):
        rec = gen(now)
        if rec[1]["etype"] == "view":
            views.append((rec[0] % 100, now))
        return rec
    src.gen = logged
    sink = eng.operators["sink"]
    counts = collections.Counter()
    real = sink.process

    def process(sub, tup):
        kind, campaign, n = tup.payload
        counts[(tup.ts, campaign)] += n
        return real(sub, tup)
    sink.process = process
    metrics = eng.run(duration=2.2)
    src.stopped = True
    t = eng.sim.t
    eng.sim.run_until(t + 1.0)
    op = eng.operators["stateful"]
    state = {}
    for sub in range(op.parallelism):
        for e in op.caches[sub].flush_dirty():
            op.backends[sub].write(e.key, e.state, op.state_size)
        state.update(op.backends[sub].data)
        entries = op.caches[sub].entries
        state.update({k: e.state for k, e in entries.items()})
    state = {(k.base, k.wid): v for k, v in state.items() if v is not None}
    for s in range(src.parallelism):
        src.emit_watermark(s, 1e6)
    eng.sim.run_until(t + 2.0)
    plain = collections.Counter()
    for campaign, ts in views:
        wid = math.floor(ts / 0.5)
        plain[(wid * 0.5 + 0.5, campaign)] += 1
    return counts, plain, state, metrics


@pytest.mark.parametrize("fused", MODES)
def test_ysb_campaign_chain_matches_plain_counts(fused):
    counts, plain, state, metrics = _counted(fused)
    assert len(plain) > 400 and sum(plain.values()) > 2500
    assert counts == plain
    assert metrics["stateful_late_dropped"] == 0
    if fused:
        # both keyed operators ran on the device plane
        assert metrics["join_fused"]["batches"] > 0
        assert metrics["stateful_fused"]["batches"] > 0
        assert state == _counted(False)[2]


@pytest.mark.parametrize("fused", MODES)
def test_campaign_counts_carry_their_window_end_as_ingest_time(fused):
    """YSB's latency runs from a window's end to its count's delivery:
    each count leaves the operator stamped with its window's end (the
    source stamps events with the simulated clock), so the sink's
    latency takes in the watermark's way through the chain."""
    eng = _chain(fused, window=0.5)
    sink = eng.operators["sink"]
    got = []
    real = sink.process

    def process(sub, tup):
        got.append((eng.sim.t, tup.ts, tup.ingest_t))
        return real(sub, tup)
    sink.process = process
    eng.run(duration=1.6)
    assert len(got) >= 200
    assert all(ingest == end for _, end, ingest in got)
    # the watermark that passes an end leaves the source at most one
    # interval later, then crosses the chain and the sink's channel
    assert all(0.05 < t - end < 0.5 for t, end, _ in got)


def test_ysb_campaign_needs_watermarks():
    with pytest.raises(ValueError, match="watermarks"):
        build_ysb("tac", "prefetch", YSBConfig(), campaign_window_s=10.0)


def test_campaign_table_follows_n_campaigns():
    for n, ad in ((1000, 4321), (100, 4321)):
        cfg = YSBConfig(n_campaigns=n)
        eng = build_ysb("tac", "sync", cfg, parallelism=1,
                        source_parallelism=1)
        state, _ = eng.operators["stateful"].backends[0].fetch(ad, 64)
        assert state == {"campaign": ad % n}


# a run of the pipeline without a window, recorded at the sink (emission
# time, event time, ad, campaign) before the window was added
GOLDEN = {False: (1223, "62b6ebbd8ec89821"),
          True: (1223, "bc56e0f54ab21637")}


@pytest.mark.parametrize("fused", MODES)
def test_build_ysb_without_window_is_unchanged(fused):
    cfg = YSBConfig(rate=4000.0, n_ads=5000, zipf_alpha=0.0, seed=5)
    eng = build_ysb("tac", "prefetch", cfg, cache_entries=256,
                    parallelism=2, source_parallelism=2, fused=fused,
                    fused_batch=32)
    topo = [(n, type(o).__name__, o.parallelism,
             [c.dst.name for c in o.out_data],
             [c.dst.name for c in o.out_hint])
            for n, o in eng.operators.items()]
    assert topo == [
        ("source", "SourceOp", 2, ["parser"], []),
        ("parser", "MapOp", 2, ["project"], ["stateful"]),
        ("project", "MapOp", 2, ["stateful"], ["stateful"]),
        ("stateful", "StatefulOp", 2, ["sink"], []),
        ("sink", "SinkOp", 1, [], [])]
    assert eng.operators["source"].watermark_interval == 0
    sink = eng.operators["sink"]
    out = []
    real = sink.process

    def process(sub, tup):
        ev, st = tup.payload
        out.append((round(eng.sim.t, 9), tup.ts, tup.key, ev["ad"],
                    st["campaign"]))
        return real(sub, tup)
    sink.process = process
    eng.run(duration=1.0)
    assert all(c == ad % 1000 for _, _, _, ad, c in out)
    digest = hashlib.sha256(repr(out).encode()).hexdigest()[:16]
    assert (len(out), digest) == GOLDEN[fused]


def test_fused_step_module_named_by_kind():
    import jax.numpy as jnp
    from repro.core import tac_jax
    B, W = 8, 16
    st = tac_jax.init(1, W, 1)
    pages = jnp.zeros((W + 1, 1, 2), jnp.float32)
    keys = np.arange(B, dtype=np.int32) % 3
    args = (st, pages, keys, np.ones(B, np.float32),
            np.ones((B, 1), np.float32), np.zeros(B, bool),
            np.ones(B, bool))
    for kind in ("sum", "max", "read"):
        text = tac_jax.fused_step.lower(*args, kind=kind).as_text()
        assert f"module @jit_fused_step_{kind} " in text
        # the one entry point runs the kind's program
        out = tac_jax.fused_step(*args, kind=kind)
        assert np.asarray(out.tallies).tolist() == [0, B]
