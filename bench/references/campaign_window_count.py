"""Plain reference of YSB's campaign count: views counted per campaign
in tumbling event-time windows after the ad -> campaign join.

Input is the count operator's input as it arrived, in order: ``("t",
campaign, ts, subtask)`` per view and ``("w", ts, subtask, input)`` per
watermark.  Each subtask counts its own campaigns.

* every recorded view counts in its (campaign, window), whatever
  watermark came before it: the source is in order with bound 0, so a
  view behind the watermark is a fault upstream, not late data, and a
  program that drops it reads as a results mismatch here;
* a subtask's watermark is the least of the last watermarks of its
  inputs, once every input (``stateful_parallelism`` of them) has sent
  one;
* when a subtask's watermark reaches a window's end, each campaign
  with views in that window fires once with its count (result
  timestamp = window end), and the pane is gone (no allowed lateness);
* the state at input position ``cut`` is the panes not yet fired, each
  with the views recorded before ``cut``.

Counts are kept in the precision the configuration states
(``float32``); the control keeps them in the next lower one
(``bfloat16``).  Nothing here imports the program.
"""
from __future__ import annotations

import math

from precision import rounder


def _windows(ts: float, size: float):
    """The tumbling windows ``[wid * size, wid * size + size)`` that hold
    ``ts`` (one)."""
    wid = math.floor(ts / size)
    while wid * size > ts - size:
        yield wid
        wid -= 1


def run(inputs, cut: int, config: dict, dtype: str = "float32"):
    """Returns (results, state at input position ``cut``): results as
    ``(window end, campaign, count)``, state as ``{(campaign, wid):
    count}``."""
    size = config["deployment"]["window_size_s"]
    n_inputs = config["stateful_parallelism"]
    store = rounder(dtype)
    total = {}                     # (campaign, wid) -> every view
    panes = {}                     # subtask -> wid -> campaigns
    for msg in inputs:
        if msg[0] == "t":
            _, c, ts, sub = msg
            for wid in _windows(ts, size):
                total[(c, wid)] = store(total.get((c, wid), 0.0) + 1.0)
                panes.setdefault(sub, {}).setdefault(wid, set()).add(c)
    acc = {}                       # (subtask, campaign, wid) -> views
    fired = {}                     # subtask -> fired wids
    wm, wm_in = {}, {}
    results, state = [], None
    for i, msg in enumerate(inputs):
        if i == cut:
            state = _unfired(acc, fired)
        if msg[0] == "t":
            _, c, ts, sub = msg
            for wid in _windows(ts, size):
                key = (sub, c, wid)
                acc[key] = store(acc.get(key, 0.0) + 1.0)
            continue
        _, ts, sub, origin = msg
        last = wm_in.setdefault(sub, {})
        last[origin] = max(last.get(origin, -math.inf), ts)
        if len(last) < n_inputs or min(last.values()) <= \
                wm.get(sub, -math.inf):
            continue
        w = wm[sub] = min(last.values())
        done = fired.setdefault(sub, set())
        for wid in sorted(panes.get(sub, {})):
            end = wid * size + size
            if wid in done or end > w:
                continue
            done.add(wid)
            for c in panes[sub][wid]:
                results.append((end, c, int(total[(c, wid)])))
    if state is None:
        state = _unfired(acc, fired)
    return results, state


def _unfired(acc, fired) -> dict:
    return {(c, wid): int(v) for (sub, c, wid), v in acc.items()
            if wid not in fired.get(sub, ())}
