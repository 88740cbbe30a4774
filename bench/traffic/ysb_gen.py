"""The benchmark's own Yahoo Streaming Benchmark event generator.

A copy of the program's ``YSBGen``: 114-byte ad events whose ad id is
drawn by inverse CDF from Zipf(``zipf_alpha``) over ``n_ads`` ads
(``zipf_alpha = 0`` is the Yahoo generator's uniform draw), a third of
them views.  With the same seed the record sequence equals
``YSBGen``'s (tests/test_generators.py).

Every view is also logged as ``(ad, ingest time)`` in ``keyed_log``:
the events that must reach the join.  Events carry no event time of
their own, so ``watermark_limit()`` is the latest ingest time.
"""
from __future__ import annotations

import numpy as np


class YSBSource:
    def __init__(self, rate: float, seed: int, n_ads: int = 100_000,
                 zipf_alpha: float = 1.0, view_share: float = 0.33,
                 event_bytes: int = 114):
        self.rate = float(rate)
        self.n_ads = int(n_ads)
        self.view_share = view_share
        self.event_bytes = event_bytes
        self.rng = np.random.Generator(np.random.PCG64(seed))
        ranks = np.arange(1, self.n_ads + 1, dtype=np.float64)
        w = 1.0 / ranks ** zipf_alpha
        self.cdf = np.cumsum(w) / w.sum()
        self.n = 0
        self.keyed_log = []
        self.max_ts = float("-inf")

    def __call__(self, now: float):
        self.n += 1
        self.max_ts = now
        u = self.rng.random()
        ad = int(np.searchsorted(self.cdf, u))
        view = self.rng.random() < self.view_share
        if view:
            self.keyed_log.append((ad, now))
        return (ad, {"ad": ad, "etype": "view" if view else "other"},
                self.event_bytes)

    def watermark_limit(self) -> float:
        return self.max_ts


def make(traffic: dict, config: dict, seed: int) -> YSBSource:
    dep = config["deployment"]
    return YSBSource(rate=traffic["rate"], seed=seed, n_ads=dep["n_ads"],
                     zipf_alpha=traffic["zipf_alpha"],
                     view_share=dep["view_share"],
                     event_bytes=dep["event_bytes"])
