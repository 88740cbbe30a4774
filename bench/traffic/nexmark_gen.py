"""The benchmark's own NEXMark event generator.

A copy of the program's ``NexmarkGen`` (Person 2% / Auction 6% / Bid 92%,
hot auction 50%, hot bidder 75%, bid wars repeating recent pairs), kept
here so that a change to the program's generator cannot move the
yardstick.  Only NEXMark's own key process is kept (the program's
other ``key_dist`` choices are not copied).  One addition:
``offset_s``.  The key distribution (active auction and bidder ranges,
the hot auction of the second, new ids) is evaluated at stream time
``now + offset_s`` while event time stays at ``now``, so a run can
start at the active range of a stream that has been running for
``offset_s`` seconds without generating that prefix.  With
``offset_s = 0`` the record sequence equals ``NexmarkGen``'s with
``key_dist="nexmark"`` for the same seed (tests/test_generators.py).

Every bid is also logged as ``(auction, event ts)`` in ``keyed_log``:
the events that must reach the keyed operator, which the correctness
check holds the operator's input to.  ``watermark_limit()`` is the
latest watermark the source may have issued over the records made so
far: their latest event time less the out-of-orderness bound.
"""
from __future__ import annotations

import numpy as np

BID, AUCTION, PERSON = "bid", "auction", "person"
SIZES = {BID: 200, AUCTION: 500, PERSON: 200}


class NexmarkSource:
    def __init__(self, rate: float, seed: int, active_window: float = 60.0,
                 hot_auction_prob: float = 0.5,
                 hot_bidder_prob: float = 0.75, oo_bound: float = 0.0,
                 late_prob: float = 0.02, offset_s: float = 0.0):
        self.rate = float(rate)
        self.active_window = float(active_window)
        self.hot_auction_prob = hot_auction_prob
        self.hot_bidder_prob = hot_bidder_prob
        self.auctions_per_s = 0.06 * self.rate
        self.persons_per_s = max(0.02 * self.rate, 1.0)
        self.oo_bound = float(oo_bound)
        self.late_prob = late_prob
        self.offset_s = float(offset_s)
        self.rng = np.random.Generator(np.random.PCG64(seed))
        self.n = 0
        self.recent_pairs = []
        self.repeat_pair_prob = 0.4
        self.keyed_log = []
        self.max_ts = float("-inf")

    def active_range(self, now: float, per_s: float):
        hi = max(1, int(now * per_s))
        lo = max(0, int((now - self.active_window) * per_s))
        return lo, hi

    def _auction_id(self, kt: float) -> int:
        lo, hi = self.active_range(kt, self.auctions_per_s)
        rng = self.rng
        if rng.random() < self.hot_auction_prob:
            return min(hi - 1, int(int(kt) * self.auctions_per_s))
        return int(rng.integers(lo, max(lo, hi - 1) + 1))

    def _bidder_id(self, kt: float) -> int:
        lo, hi = self.active_range(kt, self.persons_per_s)
        if self.rng.random() < self.hot_bidder_prob:
            return min(hi - 1, int(int(kt) * self.persons_per_s))
        return int(self.rng.integers(lo, max(lo, hi - 1) + 1))

    def _event_ts(self, now: float) -> float:
        b = self.oo_bound
        if self.rng.random() < self.late_prob:
            delay = b * (1.0 + self.rng.random())
        else:
            delay = b * self.rng.random()
        return max(0.0, now - delay)

    def __call__(self, now: float):
        rec = self._gen(now + self.offset_s)
        if self.oo_bound > 0:
            rec = rec + (self._event_ts(now),)
        ts = rec[3] if len(rec) > 3 else now
        if ts > self.max_ts:
            self.max_ts = ts
        if rec[1]["type"] == BID:
            self.keyed_log.append((rec[0], ts))
        return rec

    def watermark_limit(self) -> float:
        return self.max_ts - self.oo_bound

    def _gen(self, kt: float):
        self.n += 1
        rng = self.rng
        r = rng.random()
        if r < 0.92:
            if self.recent_pairs and rng.random() < self.repeat_pair_prob:
                a, b = self.recent_pairs[
                    int(rng.integers(len(self.recent_pairs)))]
            else:
                a = self._auction_id(kt)
                b = self._bidder_id(kt)
                self.recent_pairs.append((a, b))
                if len(self.recent_pairs) > 4096:
                    del self.recent_pairs[:2048]
            price = int(rng.integers(1, 10_001))
            return (a, {"type": BID, "auction": a, "bidder": b,
                        "price": price}, SIZES[BID])
        if r < 0.98:
            _, hi = self.active_range(kt, self.auctions_per_s)
            cat = 10 if rng.random() < 0.25 else int(rng.integers(10))
            plo, phi = self.active_range(kt, self.persons_per_s)
            seller = int(rng.integers(plo, max(plo, phi - 1) + 1))
            return (hi, {"type": AUCTION, "auction": hi, "category": cat,
                         "seller": seller}, SIZES[AUCTION])
        _, hi = self.active_range(kt, self.persons_per_s)
        return (hi, {"type": PERSON, "person": hi,
                     "state": int(rng.integers(50))}, SIZES[PERSON])


def make(traffic: dict, config: dict, seed: int) -> NexmarkSource:
    """The generator of a cell: rates and skew from the traffic file,
    the deployment's active window and out-of-orderness from the
    configuration file."""
    dep = config["deployment"]
    return NexmarkSource(
        rate=traffic["rate"], seed=seed,
        active_window=dep["active_window_s"],
        hot_auction_prob=traffic.get("hot_auction_prob", 0.5),
        hot_bidder_prob=traffic.get("hot_bidder_prob", 0.75),
        oo_bound=dep["oo_bound_s"],
        late_prob=traffic.get("late_prob", 0.02),
        offset_s=traffic.get("offset_s", 0.0))
