"""NEXMark q11 (user sessions) through the program's normal entry
point, ``repro.streaming.nexmark.build_query``, and the reading of what
it produces: sink results and final keyed state, in the plain form that
``references/session_count.py`` produces too."""
from __future__ import annotations


def build(config: dict, traffic: dict, seed: int):
    from repro.streaming.nexmark import NexmarkConfig, build_query
    dep = config["deployment"]
    cfg = NexmarkConfig(rate=traffic["rate"],
                        active_window=dep["active_window_s"],
                        oo_bound=dep["oo_bound_s"],
                        late_prob=traffic.get("late_prob", 0.02),
                        watermark_interval=dep["watermark_interval_s"],
                        seed=seed)
    return build_query(
        config["query"], config["policy"], config["mode"], cfg,
        fused=config["fused"], fused_batch=config["batch"],
        cache_entries=config["slots"],
        parallelism=config["stateful_parallelism"],
        source_parallelism=config["source_parallelism"],
        session_gap=dep["session_gap_s"],
        allowed_lateness=dep["allowed_lateness_s"])


def result_of(tup) -> tuple:
    """A sink tuple as ``(session end, bidder, session id, count)``."""
    kind, key, wid, value = tup.payload
    if kind != "session" or key != tup.key:
        return ("malformed", repr(tup.payload))
    return (float(tup.ts), int(key), int(wid), int(value))


def state_of(key, value):
    """A pane of the final keyed state as ``((bidder, session id),
    count)``."""
    return (int(key[0]), int(key[1])), int(value)
