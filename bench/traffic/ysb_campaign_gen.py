"""The benchmark's generator for YSB as published: the YSB event stream
of ``ysb_gen.YSBSource``, record for record, with each view logged as
``(campaign, event time)``, the key it must reach the campaign count
with (``campaign = ad mod n_campaigns``, computed here, not by the
program).  Events are stamped at creation by one in-order producer, so
the latest watermark the source may issue is the newest event time
less ``oo_bound_s``."""
from __future__ import annotations

import importlib.util
import os

_spec = importlib.util.spec_from_file_location(
    "bench_traffic_ysb_gen_base",
    os.path.join(os.path.dirname(os.path.abspath(__file__)), "ysb_gen.py"))
_base = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_base)


class YSBCampaignSource(_base.YSBSource):
    def __init__(self, n_campaigns: int, oo_bound: float, **kw):
        super().__init__(**kw)
        self.n_campaigns = int(n_campaigns)
        self.oo_bound = float(oo_bound)

    def __call__(self, now: float):
        rec = super().__call__(now)
        if rec[1]["etype"] == "view":
            ad, ts = self.keyed_log[-1]
            self.keyed_log[-1] = (ad % self.n_campaigns, ts)
        return rec

    def watermark_limit(self) -> float:
        return self.max_ts - self.oo_bound


def make(traffic: dict, config: dict, seed: int) -> YSBCampaignSource:
    dep = config["deployment"]
    return YSBCampaignSource(
        n_campaigns=dep["n_campaigns"], oo_bound=dep["oo_bound_s"],
        rate=traffic["rate"], seed=seed, n_ads=dep["n_ads"],
        zipf_alpha=traffic["zipf_alpha"], view_share=dep["view_share"],
        event_bytes=dep["event_bytes"])
