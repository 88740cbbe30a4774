"""YSB as published through the program's normal entry point,
``repro.streaming.ysb.build_ysb`` with a campaign window: the ad ->
campaign join, then views counted per campaign in tumbling event-time
windows, both keyed operators on the fused plane.  Reads what it
produces in the plain form that ``references/campaign_window_count.py``
produces too."""
from __future__ import annotations


def build(config: dict, traffic: dict, seed: int):
    from repro.streaming.ysb import YSBConfig, build_ysb
    dep = config["deployment"]
    cfg = YSBConfig(rate=traffic["rate"], n_ads=dep["n_ads"],
                    zipf_alpha=traffic["zipf_alpha"], seed=seed,
                    n_campaigns=dep["n_campaigns"],
                    watermark_interval=dep["watermark_interval_s"],
                    oo_bound=dep["oo_bound_s"])
    return build_ysb(config["policy"], config["mode"], cfg,
                     fused=config["fused"], fused_batch=config["batch"],
                     cache_entries=config["slots"],
                     parallelism=config["stateful_parallelism"],
                     source_parallelism=config["source_parallelism"],
                     campaign_window_s=dep["window_size_s"],
                     window_cache_entries=config["window_slots"])


def result_of(tup) -> tuple:
    """A sink tuple as ``(window end, campaign, count)``."""
    kind, key, value = tup.payload
    if kind != "count" or key != tup.key:
        return ("malformed", repr(tup.payload))
    return (float(tup.ts), int(key), int(value))


def state_of(key, value):
    """A pane of the final keyed state as ``((campaign, wid), count)``."""
    return (int(key[0]), int(key[1])), int(value)
