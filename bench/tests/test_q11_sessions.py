"""The ``q11-sessions`` cell: its plain reference on hand-built session
inputs (merges, a predating bid, a late re-open, the lateness horizon),
a tiny CPU rehearsal through the harness's whole path, its bfloat16
control, its tail moving with the seed, and its two per-layer
readers."""
import time

import pytest

import harness
import session_count
from metrics import drop_calls_per_kevent, session_steps_per_kevent
from metrics.lib import xplane

# a 1 s gap and 1 s of lateness: sessions close, fire and purge within
# the tiny run's 2.5 s warm-up and 2 s span
TINY = {"slots": 64, "rate": 2_000.0, "batch": 32, "session_gap_s": 1.0,
        "warmup_s": 2.5, "span_s": 2.0}
CONFIG = {"stateful_parallelism": 1,
          "deployment": {"session_gap_s": 1.0, "allowed_lateness_s": 1.0,
                         "late_policy": "update"}}


def _sid(ts):
    return int(round(ts * 1e6))


def test_reference_merges_reopens_and_purges():
    bids = [("t", 0, 0.0, 0), ("t", 0, 1.5, 0),    # two sessions of 0
            ("t", 0, 0.8, 0),                       # bridges them
            ("t", 1, 5.0, 0), ("t", 1, 4.5, 0)]     # predates its session
    inputs = bids + [("w", 3.0, 0, "a"),            # fires 0's session
                     ("t", 0, 2.0, 0),              # late: re-opens it
                     ("w", 3.1, 0, "a"),            # re-fires it
                     ("w", 3.05, 0, "a"),           # behind: no advance
                     ("w", 4.2, 0, "a"),            # 3.0 + 1 s: purged
                     ("t", 0, 2.1, 0),              # past the horizon
                     ("w", 7.0, 0, "a")]            # fires 1's session
    results, state = session_count.run(inputs, 7, CONFIG)
    assert results == [(2.5, 0, _sid(0.0), 3), (3.0, 0, _sid(0.0), 4),
                       (6.0, 1, _sid(4.5), 2)]
    # at position 7 (after the re-open): both sessions open
    assert state == {(0, _sid(0.0)): 4, (1, _sid(4.5)): 2}
    assert session_count.run(inputs, len(inputs), CONFIG)[1] == \
        {(1, _sid(4.5)): 2}


def test_reference_waits_for_every_input_and_drops_past_the_horizon():
    cfg = dict(CONFIG, stateful_parallelism=2)
    inputs = [("t", 0, 0.0, 0), ("w", 5.0, 0, "a"),     # one input of two
              ("t", 0, 0.5, 0), ("w", 5.0, 0, "b"),     # now 5.0: fire
              ("t", 0, 2.9, 0),                         # 3.9 + 1 < 5
              ("t", 0, 3.1, 0)]                         # a new session
    results, state = session_count.run(inputs, len(inputs), cfg)
    assert results == [(1.5, 0, _sid(0.0), 2)]
    assert state == {(0, _sid(0.0)): 2, (0, _sid(3.1)): 1}


def test_reference_bfloat16_loses_counts_past_256():
    inputs = [("t", 0, i * 1e-3, 0) for i in range(300)] + \
        [("w", 5.0, 0, "a")]
    assert session_count.run(inputs, 0, CONFIG)[0][0][3] == 300
    assert session_count.run(inputs, 0, CONFIG, "bfloat16")[0][0][3] != 300


_runs = {}


def _run(seconds=2.0, trace=False, seed=7, control=None):
    cell = harness.load_cell("q11-sessions")
    return harness.run_cell(cell, seed, seconds, trace, time.perf_counter(),
                            sizes=TINY, control=control)


def _cached():
    if "sound" not in _runs:
        _runs["sound"] = _run(seed=2 ** 31 + 3, control="bfloat16")
    return _runs["sound"]


def test_q11_sessions_rehearsal():
    out = _cached()
    line = out.line
    assert line["correct"] is True and line["failed"] == 0, \
        (line["check"], out.extra)
    assert all(c["value"] == 0 and c["limit"] == 0
               for c in line["check"].values())
    assert set(line["metrics"]) == {"events_per_s", "sim_p99_ms",
                                    "setup_s"}
    assert out.extra["results"] > 500
    assert out.extra["window_traces"].get("compiles", 0) == 0
    # panes evicted and were fetched back: the cell's path
    c = out.extra["counters"]
    assert c["batches"] > 0 and c["device_hits"] > 0
    assert c["evictions"] > 0 and c["device_misses"] > 0


def test_q11_sessions_bfloat16_control_is_not_correct():
    ctl = _cached().extra["control"]
    assert ctl["results_mismatch"]["value"] + \
        ctl["state_mismatch"]["value"] > 0


def test_q11_sessions_sim_p99_moves_with_the_seed():
    """A fire is stamped with its session's end, so the tail runs from
    the end to delivery: the watermark's lag behind the seed's
    out-of-order bids, no constant of the configuration."""
    p99 = {seed: _run(seconds=0.5, seed=seed).line
           for seed in (5, 3_915_000_001)}
    assert all(line["correct"] for line in p99.values())
    v = {s: line["metrics"]["sim_p99_ms"]["value"]
         for s, line in p99.items()}
    assert v[5] != v[3_915_000_001]
    # at least the 0.3 s out-of-orderness bound, and under a second
    assert all(300.0 < x < 1000.0 for x in v.values())


def test_q11_sessions_traced_rehearsal():
    """On the CPU no device plane is traced: the step reader stays
    silent, the drop reader reads the window's calls."""
    out = _run(seconds=3.0, trace=True, seed=11)
    line = out.line
    assert line["correct"] is True, (line["check"], out.extra)
    assert [m["name"] for m in harness.load_cell(
        "q11-sessions").per_layer] == ["session_steps_per_kevent",
                                       "drop_calls_per_kevent"]
    assert "session_steps_per_kevent" not in line["metrics"]
    assert line["metrics"]["drop_calls_per_kevent"]["value"] > 0


def test_session_readers_on_hand_built_runs():
    s = xplane.Summary(window=(0, 10 ** 9), busy_ns=10 ** 6,
                       device_planes=["/device:TPU:0"],
                       modules={"jit_fused_step_sum(3)": [8, 2e-4],
                                "jit_drop_slots(4)": [2, 1e-5]})
    run = {"trace": s, "events": 3000, "calls": {"fused_step": 12,
                                                 "drop_slots": 3},
           "host": {"events": 1000, "wall_s": 1.0, "inside_s": 0.5}}
    # 8 steps over the 2,000 events traced; 3 drops over all 3,000
    assert session_steps_per_kevent.read(run) == pytest.approx(4.0)
    assert drop_calls_per_kevent.read(run) == pytest.approx(1.0)
    assert drop_calls_per_kevent.read(dict(run, calls={})) is None
    assert drop_calls_per_kevent.read(
        dict(run, calls={"fused_step": 4})) == 0.0
    assert session_steps_per_kevent.read(dict(run, trace=None)) is None
