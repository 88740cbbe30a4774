"""Yahoo Streaming Benchmark (paper §VI): ad-analytics enrichment against a
DISAGGREGATED key-value store (the paper uses remote Redis).  Events are
114 B; ad ids follow Zipf(alpha=1); the join key is ad_id -> campaign.
With a campaign window the pipeline runs on as YSB publishes it: views
counted per campaign in tumbling event-time windows after the join."""
from __future__ import annotations

from dataclasses import dataclass
from typing import Optional

import numpy as np

from repro.streaming.backend import DISAGGREGATED, LOCAL_NVME
from repro.streaming.engine import (Engine, MapOp, SinkOp, SourceOp,
                                    StatefulOp)
from repro.streaming.events import Tuple_


@dataclass
class YSBConfig:
    rate: float = 50_000.0
    n_ads: int = 100_000
    zipf_alpha: float = 1.0
    seed: int = 11
    n_campaigns: int = 1000           # the table: campaign = ad mod this
    # event-time watermarks (DESIGN.md §10), which a campaign window
    # needs: every interval the source sends max emitted ts - oo_bound;
    # 0 sends none
    watermark_interval: float = 0.0
    oo_bound: float = 0.0


class YSBGen:
    def __init__(self, cfg: YSBConfig):
        self.cfg = cfg
        # counter-based generator: replays bit-exactly from the seed
        # (chaos-oracle determinism contract, DESIGN.md §15)
        self.rng = np.random.Generator(np.random.PCG64(cfg.seed))
        # Zipf(alpha=1) over n_ads via inverse-CDF table
        ranks = np.arange(1, cfg.n_ads + 1, dtype=np.float64)
        w = 1.0 / ranks ** cfg.zipf_alpha
        self.cdf = np.cumsum(w) / w.sum()

    def __call__(self, now: float):
        u = self.rng.random()
        ad = int(np.searchsorted(self.cdf, u))
        etype = self.rng.random()
        return (ad, {"ad": ad, "etype": "view" if etype < 0.33 else "other"},
                114)


def build_ysb(policy: str, mode: str, cfg: YSBConfig,
              cache_entries: int = 4096, parallelism: int = 3,
              source_parallelism: int = 2, io_workers: int = 8,
              cms_conf=None, replayable: bool = False,
              fused: bool = False, fused_batch: int = 64,
              campaign_window_s: Optional[float] = None,
              window_cache_entries: int = 1024) -> Engine:
    """``replayable=True`` runs the source against a durable log so the
    failure/recovery scenarios (DESIGN.md §7) can rewind and replay it.

    ``fused=True`` runs the enrichment join's hot path on the device
    plane (DESIGN.md §14): the campaign record is a 1-wide read-only row
    and each batch probes + gathers + emits in one jitted program.

    Without ``campaign_window_s`` the join, named ``stateful``, emits
    each view with its campaign to the sink.  With it the join is named
    ``join`` and YSB's count follows: ``win_lookahead`` rekeys each view
    by its campaign and hints the campaign's pane (one hop of slack, as
    the campaign is known only once the join has read it), and
    ``stateful`` counts views per (campaign, tumbling window of
    ``campaign_window_s``) over ``window_cache_entries`` slots, firing
    each pane's count when the watermark passes the window's end; a view
    behind a fired window is dropped as late.  With ``fused`` both keyed
    operators run on the device plane, the count as a ``sum``."""
    windowed = campaign_window_s is not None
    if windowed and cfg.watermark_interval <= 0:
        raise ValueError("a campaign window needs event-time watermarks "
                         "(cfg.watermark_interval > 0)")
    eng = Engine()
    gen = YSBGen(cfg)
    state_size = 64                        # campaign metadata
    n_campaigns = cfg.n_campaigns

    def key_of(tup: Tuple_):
        return tup.payload["ad"]

    def vfilter(tup: Tuple_):
        return tup if tup.payload["etype"] == "view" else None

    def project(tup: Tuple_):
        return tup

    def apply_fn(tup, state):
        return state, [Tuple_(tup.ts, tup.key, (tup.payload, state), 130,
                              tup.ingest_t)]

    fused_kw = {}
    if fused:
        from repro.streaming.fused import FusedSpec
        spec = FusedSpec(
            kind="read", width=1,
            encode=lambda s: [float(s["campaign"])],
            decode=lambda v: {"campaign": int(round(float(v[0])))},
            emit_of=lambda tup, state: [
                Tuple_(tup.ts, tup.key, (tup.payload, state), 130,
                       tup.ingest_t)])
        fused_kw = dict(fused=spec, fused_batch=fused_batch)

    src = eng.add(SourceOp(eng, "source", source_parallelism, cfg.rate, gen,
                           watermark_interval=cfg.watermark_interval,
                           oo_bound=cfg.oo_bound, replayable=replayable))
    parse = eng.add(MapOp(eng, "parser", parallelism, fn=vfilter,
                          service_time=20e-6, key_of=key_of,
                          cms_conf=cms_conf))
    proj = eng.add(MapOp(eng, "project", parallelism, fn=project,
                         service_time=8e-6, key_of=key_of,
                         cms_conf=cms_conf))
    join = eng.add(StatefulOp(
        eng, "join" if windowed else "stateful", parallelism, apply_fn,
        DISAGGREGATED, cache_entries * state_size, policy=policy, mode=mode,
        io_workers=io_workers, state_size=state_size, read_only=True,
        default_state=lambda k: {"campaign": k % n_campaigns},
        dense_backend=True, **fused_kw))
    eng.connect(src, parse)
    eng.connect(parse, proj)
    eng.connect(proj, join)
    if windowed:
        _add_campaign_count(eng, join, policy, mode, cfg, parallelism,
                            io_workers, cms_conf, fused, fused_batch,
                            campaign_window_s, window_cache_entries)
    else:
        sink = eng.add(SinkOp(eng, "sink", 1))
        eng.connect(join, sink, partition=lambda k, n: 0)
    if mode == "prefetch":
        eng.register_prefetching(join, [parse, proj])
    return eng


def _add_campaign_count(eng, join, policy, mode, cfg, parallelism,
                        io_workers, cms_conf, fused, fused_batch,
                        window_s, window_entries) -> None:
    """YSB's last stage after the join: views counted per campaign in
    tumbling event-time windows, as q5 counts bids (DESIGN.md §10)."""
    from repro.streaming.windows import (WindowAssigner, WindowedLookaheadOp,
                                         WindowedStatefulOp)
    assigner = WindowAssigner(window_s)
    state_size = 96                        # a counter + pane metadata

    def campaign_of(tup: Tuple_):
        return tup.payload[1]["campaign"]

    def by_campaign(tup: Tuple_):
        return Tuple_(tup.ts, campaign_of(tup), tup.payload, tup.size,
                      tup.ingest_t)

    def agg_fn(tup, acc):
        return (acc or 0) + 1

    def emit_fn(key, wid, end, acc):
        return ("count", key, acc) if acc else None

    fused_kw = {}
    if fused:
        from repro.streaming.fused import FusedSpec
        fused_kw = dict(fused=FusedSpec(
            kind="sum", width=1, weight_of=lambda tup: 1.0,
            encode=lambda s: None if s is None else [float(s)],
            decode=lambda v: int(round(float(v[0])))),
            fused_batch=fused_batch)
    winla = eng.add(WindowedLookaheadOp(
        eng, "win_lookahead", parallelism, assigner, campaign_of,
        fn=by_campaign, burst_ahead=2 * cfg.watermark_interval,
        service_time=10e-6, cms_conf=cms_conf))
    count = eng.add(WindowedStatefulOp(
        eng, "stateful", parallelism, assigner, agg_fn, emit_fn, LOCAL_NVME,
        window_entries * state_size, late_policy="drop",
        latency_from_end=True, policy=policy,
        mode=mode, io_workers=io_workers, state_size=state_size,
        miss_threshold=1.01, deadline_aware=True, **fused_kw))
    sink = eng.add(SinkOp(eng, "sink", 1))
    eng.connect(join, winla)
    eng.connect(winla, count)
    eng.connect(count, sink, partition=lambda k, n: 0)
    if mode == "prefetch":
        eng.register_prefetching(count, [winla])
