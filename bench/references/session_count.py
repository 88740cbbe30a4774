"""Plain reference of NEXMark q11: bids counted per bidder per
event-time session window.

Input is the keyed operator's input as it arrived, in order: ``("t",
bidder, ts, subtask)`` for a bid and ``("w", ts, subtask, input)`` for
a watermark.  Each subtask keeps the sessions of its own bidders.  The
semantics are the usual event-time ones for session windows with a gap
(Flink's ``EventTimeSessionWindows``, Beam's ``Sessions``), with the
configuration's allowed lateness under the update policy:

* a subtask's watermark is the least of the last watermarks of its
  inputs, once every input has sent one (every operator of the plan runs
  ``stateful_parallelism`` subtasks, so a subtask has that many inputs);
* a bid at ``ts`` spans ``[ts, ts + gap)``; it joins every session of
  its bidder that this span overlaps and merges them into one, which
  ends at the latest ``ts + gap`` of its bids and takes the id of its
  earliest bid (the bid's time in whole microseconds, rounded);
* a bid that overlaps no session, and whose ``ts + gap`` plus the
  allowed lateness is behind the watermark, is dropped;
* a bid that joins a session that has fired re-opens it;
* when the watermark advances, every open session whose end it has
  reached fires once with its count (result: ``(end, bidder, id,
  count)``); then every session that fired at an earlier advance, and
  whose end plus the allowed lateness the watermark has reached, is
  discarded.

Counts are kept in the precision the configuration states
(``float32``); the control keeps them in the next lower one
(``bfloat16``).  Nothing here imports the program.
"""
from __future__ import annotations

import math

from precision import rounder


def _session_id(ts: float) -> int:
    return int(math.floor(ts * 1e6 + 0.5))


def run(inputs, cut: int, config: dict, dtype: str = "float32"):
    """Returns (results, state at input position ``cut``): results as
    ``(end, bidder, id, count)``, state as ``{(bidder, id): count}``."""
    dep = config["deployment"]
    if dep["late_policy"] != "update":
        raise ValueError("the reference keeps the update late policy")
    gap, late = dep["session_gap_s"], dep["allowed_lateness_s"]
    n_inputs = config["stateful_parallelism"]
    store = rounder(dtype)
    by_bidder = {}         # subtask -> bidder -> [session]
    live = {}              # subtask -> (bidder, id) -> session
    wm, wm_in = {}, {}
    results, state = [], None
    for i, msg in enumerate(inputs):
        if i == cut:
            state = _counts(live)
        if msg[0] == "t":
            _, bidder, ts, sub = msg
            own = by_bidder.setdefault(sub, {}).setdefault(bidder, [])
            mine = live.setdefault(sub, {})
            hit = [s for s in own if ts < s["end"] and ts + gap > s["start"]]
            if not hit and ts + gap + late < wm.get(sub, -math.inf):
                continue
            count = 1.0
            for s in hit:
                own.remove(s)
                del mine[(bidder, s["id"])]
                count = store(count + s["count"])
            start = min([ts] + [s["start"] for s in hit])
            s = {"start": start, "id": _session_id(start), "count": count,
                 "end": max([ts + gap] + [s["end"] for s in hit]),
                 "fired_at": None}
            own.append(s)
            mine[(bidder, s["id"])] = s
            continue
        _, ts, sub, origin = msg
        last = wm_in.setdefault(sub, {})
        last[origin] = max(last.get(origin, -math.inf), ts)
        if len(last) < n_inputs or min(last.values()) <= \
                wm.get(sub, -math.inf):
            continue
        w = wm[sub] = min(last.values())
        mine = live.get(sub, {})
        for (bidder, sid), s in list(mine.items()):
            if s["fired_at"] is None:
                if s["end"] <= w:
                    s["fired_at"] = w
                    results.append((s["end"], bidder, sid,
                                    int(s["count"])))
            elif s["fired_at"] < w and s["end"] + late <= w:
                del mine[(bidder, sid)]
                by_bidder[sub][bidder].remove(s)
    if state is None:
        state = _counts(live)
    return results, state


def _counts(live) -> dict:
    return {key: int(s["count"]) for mine in live.values()
            for key, s in mine.items()}
