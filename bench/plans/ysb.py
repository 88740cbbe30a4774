"""The YSB ad -> campaign enrichment join through the program's normal
entry point, ``repro.streaming.ysb.build_ysb``, and the reading of what
it produces, in the plain form that ``references/campaign_join.py``
produces too."""
from __future__ import annotations


def build(config: dict, traffic: dict, seed: int):
    from repro.streaming.ysb import YSBConfig, build_ysb
    dep = config["deployment"]
    cfg = YSBConfig(rate=traffic["rate"], n_ads=dep["n_ads"],
                    zipf_alpha=traffic["zipf_alpha"], seed=seed)
    return build_ysb(config["policy"], config["mode"], cfg,
                     fused=config["fused"], fused_batch=config["batch"],
                     cache_entries=config["slots"],
                     parallelism=config["stateful_parallelism"],
                     source_parallelism=config["source_parallelism"])


def result_of(tup) -> tuple:
    """A sink tuple as ``(ts, ad, campaign)``."""
    event, state = tup.payload
    if event.get("ad") != tup.key or event.get("etype") != "view":
        return ("malformed", repr(tup.payload))
    campaign = None if state is None else state.get("campaign")
    return (float(tup.ts), int(tup.key), campaign)


def state_of(key, value):
    return int(key), value.get("campaign")
