"""Plain reference of the YSB enrichment join: every view event is
emitted once, enriched with its ad's campaign.

Input is the join's input as it arrived: ``("t", ad, ts, subtask)``
per view.
The ad -> campaign table is the deployment's (``campaign = ad mod
n_campaigns``), stored in the precision the configuration states
(``float32``); the control stores it in the next lower one
(``bfloat16``).  Nothing here imports the program.
"""
from __future__ import annotations

from precision import rounder

# the program's final state holds what it fetched: a subset of the ads
STATE_RULE = "subset"


def run(inputs, cut: int, config: dict, dtype: str = "float32"):
    """Returns (results, state): results as ``(ts, ad, campaign)``, state
    as the campaign of every ad that was looked up; the program's final
    keyed state may hold any subset of those ads (the cache and the
    store hold what was fetched), each with this campaign."""
    n = config["deployment"]["n_campaigns"]
    store = rounder(dtype)
    table = {}
    results = []
    for msg in inputs:
        if msg[0] != "t":
            continue
        _, ad, ts, _ = msg
        c = table.get(ad)
        if c is None:
            c = table[ad] = int(store(ad % n))
        results.append((ts, ad, c))
    return results, table
