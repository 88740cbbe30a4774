"""The benchmark's NEXMark generator keyed by bidder (NEXMark q11).

The events, their order and their times are ``nexmark_gen.py``'s, drawn
by its key process unchanged; only the keyed log differs: every bid is
logged as ``(bidder, event ts)``, the key the session query partitions
and counts by, where ``nexmark_gen.py`` logs the auction.
"""
from __future__ import annotations

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from nexmark_gen import BID, NexmarkSource  # noqa: E402


class NexmarkBidderSource(NexmarkSource):
    def __call__(self, now: float):
        rec = super().__call__(now)
        if rec[1]["type"] == BID:
            self.keyed_log[-1] = (rec[1]["bidder"], self.keyed_log[-1][1])
        return rec


def make(traffic: dict, config: dict, seed: int) -> NexmarkBidderSource:
    """The generator of a cell, configured as ``nexmark_gen.make``
    configures its own."""
    dep = config["deployment"]
    return NexmarkBidderSource(
        rate=traffic["rate"], seed=seed,
        active_window=dep["active_window_s"],
        hot_auction_prob=traffic.get("hot_auction_prob", 0.5),
        hot_bidder_prob=traffic.get("hot_bidder_prob", 0.75),
        oo_bound=dep["oo_bound_s"],
        late_prob=traffic.get("late_prob", 0.02),
        offset_s=traffic.get("offset_s", 0.0))
