"""Plain reference of a keyed sliding-window aggregation under
event-time watermarks (NEXMark q5: bids counted per auction per window).

Input is the keyed operator's input as it arrived, in order: ``("t",
key, ts, subtask)`` for a record and ``("w", ts, subtask, input)`` for
a watermark.  Each subtask aggregates its own keys.  The semantics are
the usual event-time ones (Flink, Beam):

* a subtask's watermark is the least of the last watermarks of its
  inputs, once every input has sent one (every operator of the plan runs
  ``stateful_parallelism`` subtasks, so a subtask has that many inputs);

* a record belongs to every window ``[wid * slide, wid * slide + size)``
  that holds its timestamp;
* a record for a window whose end plus the allowed lateness is behind
  the watermark is dropped;
* when the watermark reaches a window's end, each of its keys fires
  once with its aggregate (result timestamp = window end);
* a record for a window that has fired, within the allowed lateness,
  updates the aggregate and re-emits it at once (result timestamp = the
  record's own);
* one watermark after a fired window's end plus the lateness has
  passed, its panes are discarded.

The aggregate is kept in the precision the configuration states
(``float32``); the control keeps it in the next lower one
(``bfloat16``).  Nothing here imports the program.
"""
from __future__ import annotations

import math

from precision import rounder


def _windows(ts: float, size: float, slide: float):
    wid = math.floor(ts / slide)
    while wid * slide > ts - size:
        yield wid
        wid -= 1


def run(inputs, cut: int, config: dict, dtype: str = "float32"):
    """Returns (results, state at input position ``cut``): results as
    ``(ts, key, aggregate)``, state as ``{(key, wid): aggregate}``."""
    dep = config["deployment"]
    size, slide = dep["window_size_s"], dep["window_slide_s"]
    late = dep["allowed_lateness_s"]
    n_inputs = config["stateful_parallelism"]
    store = rounder(dtype)
    acc = {}                       # (key, wid) -> aggregate
    win = {}                       # subtask -> wid -> [keys, fired, fired_keys]
    wm = {}                        # subtask -> watermark
    wm_in = {}                     # subtask -> input -> last watermark
    results = []
    state = None
    for i, msg in enumerate(inputs):
        if i == cut:
            state = dict(acc)
        if msg[0] == "w":
            _, ts, sub, origin = msg
            last = wm_in.setdefault(sub, {})
            last[origin] = max(last.get(origin, -math.inf), ts)
            if len(last) < n_inputs or min(last.values()) <= \
                    wm.get(sub, -math.inf):
                continue
            w = wm[sub] = min(last.values())
            windows = win.setdefault(sub, {})
            for wid in sorted(windows):
                keys, fired, fired_keys = windows[wid]
                end = wid * slide + size
                due = keys - fired_keys if end <= w else None
                if due:
                    windows[wid][1] = True
                    fired_keys |= due
                    for k in due:
                        results.append((end, k, int(acc[(k, wid)])))
                elif not fired and end <= w:
                    windows[wid][1] = True
                elif fired and late > 0 and end + late < w:
                    for k in keys:
                        acc.pop((k, wid), None)
                    del windows[wid]
            continue
        _, key, ts, sub = msg
        w = wm.get(sub, -math.inf)
        windows = win.setdefault(sub, {})
        for wid in _windows(ts, size, slide):
            end = wid * slide + size
            if end + late < w:
                continue
            meta = windows.setdefault(wid, [set(), False, set()])
            meta[0].add(key)
            if meta[1]:
                meta[2].add(key)
            acc[(key, wid)] = store(acc.get((key, wid), 0.0) + 1.0)
            if meta[1]:
                results.append((ts, key, int(acc[(key, wid)])))
    if state is None:
        state = dict(acc)
    return results, {k: int(v) for k, v in state.items()}
