"""CPU rehearsals of the two cells that YSB as published and q5 below
its slots add, at a tiny size: ``ysb-campaign-uniform`` (the ad ->
campaign join, then views counted per campaign in tumbling windows)
through the harness's whole path, its bfloat16 control, a traced run,
the two readers of the ``sum`` kind's program on hand-built summaries,
and ``q5-hot-20k``."""
import time

import pytest

import harness
from metrics import window_step_us, window_steps_per_kevent
from metrics.lib import xplane

# 100 campaigns counted in 1 s windows: fires at 1, 2 and 3 s of stream
TINY = {"slots": 256, "window_slots": 64, "rate": 4_000.0, "batch": 64,
        "warmup_s": 1.0, "span_s": 2.5, "n_ads": 5_000,
        "window_size_s": 1.0, "window_slide_s": 1.0}
# two campaigns: ~660 views a pane, past bfloat16's 256 of exact counts
FEW = dict(TINY, n_campaigns=2)


def _run(name, sizes, seconds=2.0, trace=False, seed=7, control=None):
    cell = harness.load_cell(name)
    return harness.run_cell(cell, seed, seconds, trace, time.perf_counter(),
                            sizes=sizes, control=control)


def test_ysb_campaign_rehearsal():
    out = _run("ysb-campaign-uniform", TINY, seed=2 ** 31 + 17)
    line = out.line
    assert line["correct"] is True and line["failed"] == 0
    assert all(c["value"] == 0 and c["limit"] == 0
               for c in line["check"].values())
    assert set(line["metrics"]) == {"events_per_s", "sim_p99_ms",
                                    "setup_s"}
    assert out.extra["results"] > 100
    assert out.extra["window_traces"].get("compiles", 0) == 0
    # both planes ran: the join's read batches and the count's sum
    # batches, with store fetches at the join
    c = out.extra["counters"]
    assert c["batches"] > 0 and c["device_misses"] > 0
    assert c["evictions"] > 0


def test_ysb_campaign_sim_p99_moves_with_the_seed():
    """The tail runs from each window's end to its count's delivery, so
    it takes in how the watermark met the seed's traffic on its way
    through the chain, and is no constant of the configuration."""
    p99 = {seed: _run("ysb-campaign-uniform", TINY, seconds=0.5,
                      seed=seed).line["metrics"]["sim_p99_ms"]["value"]
           for seed in (5, 3_915_000_001)}
    assert p99[5] != p99[3_915_000_001]
    # at least the watermark interval of 50 ms, and under a second
    assert all(50.0 < v < 1000.0 for v in p99.values())


def test_ysb_campaign_bfloat16_control_is_not_correct():
    out = _run("ysb-campaign-uniform", FEW, control="bfloat16")
    assert out.line["correct"] is True
    ctl = out.extra["control"]
    assert ctl["results_mismatch"]["value"] > 0


def test_ysb_campaign_traced_rehearsal():
    """The traced run is correct and reports its host layers; on the CPU
    no device plane is traced, so the two readers of the ``sum`` kind's
    program stay silent rather than read 0."""
    out = _run("ysb-campaign-uniform", TINY, seconds=4.0, trace=True,
               seed=11)
    line = out.line
    assert line["correct"] is True
    assert {m["name"] for m in harness.load_cell(
        "ysb-campaign-uniform").per_layer} == {"window_step_us",
                                               "window_steps_per_kevent"}
    assert "window_step_us" not in line["metrics"]
    assert "window_steps_per_kevent" not in line["metrics"]
    assert {"busy_s", "window_s"} <= set(line["device"])


def _summary(modules):
    return xplane.Summary(window=(0, 10 ** 9), busy_ns=10 ** 6,
                          device_planes=["/device:TPU:0"], modules=modules)


def test_window_readers_on_a_hand_built_summary():
    s = _summary({"jit_fused_step_sum(3)": [4, 2e-4],
                  "jit_fused_step_read(4)": [10, 1e-3],
                  "jit_fused_admit(5)": [7, 1e-4]})
    run = {"trace": s, "events": 3000,
           "host": {"events": 1000, "wall_s": 1.0, "inside_s": 0.5}}
    # 2e-4 s over 4 calls; 4 calls over the 2,000 events traced
    assert window_step_us.read(run) == pytest.approx(50.0)
    assert window_steps_per_kevent.read(run) == pytest.approx(2.0)


@pytest.mark.parametrize("run", [
    # a program without its kind in its name (the step before kinds
    # were named) is not the count's
    {"trace": _summary({"jit_fused_step(1)": [4, 2e-4]}), "events": 10,
     "host": {"events": 5}},
    # nothing traced, or no host split to take the traced events from
    {"trace": None, "events": 10, "host": {"events": 5}},
    {"trace": _summary({"jit_fused_step_sum(3)": [4, 2e-4]}),
     "events": 10, "host": None},
])
def test_window_readers_are_silent_without_their_program(run):
    assert window_steps_per_kevent.read(run) is None
    if run["trace"] is None or "jit_fused_step(1)" in run["trace"].modules:
        assert window_step_us.read(run) is None


def test_q5_hot_20k_rehearsal():
    sizes = {"slots": 64, "rate": 3_200.0, "batch": 32, "warmup_s": 3.0,
             "span_s": 2.0}
    out = _run("q5-hot-20k", sizes, seconds=1.0, seed=2 ** 31 + 5)
    assert out.line["correct"] is True
    assert out.extra["results"] > 100
