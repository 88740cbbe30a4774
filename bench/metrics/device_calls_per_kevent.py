"""Calls to the fused plane's device programs (``fused_step``,
``fused_admit``, ``gather_rows``, ``drop_slots``) per 1,000 keyed
events the stateful operator took up, over the traced run's whole window."""


def read(run):
    calls = run.get("calls") or {}
    if not calls or run.get("events", 0) <= 0:
        return None
    return sum(calls.values()) / (run["events"] / 1e3)
