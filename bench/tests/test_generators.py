"""The benchmark's generators against the program's, and the sizes the
q5 cell's slot count was set from, counted from its generator alone."""
import math
import os

import numpy as np
import pytest

import harness

TRAFFIC = os.path.join(harness.BENCH, "traffic")


def _gen_module(name):
    return harness.load_module(os.path.join(TRAFFIC, f"{name}_gen.py"),
                               f"test_{name}_gen")


@pytest.mark.parametrize("rate", [25_000.0, 50_000.0])
@pytest.mark.parametrize("seed", [7, 2 ** 31 + 12345])
def test_nexmark_copy_replays_the_program_generator(rate, seed):
    from repro.streaming.nexmark import NexmarkConfig, NexmarkGen
    prog = NexmarkGen(NexmarkConfig(rate=rate, active_window=60.0,
                                    oo_bound=0.3, seed=seed,
                                    key_dist="nexmark"))
    ours = _gen_module("nexmark").NexmarkSource(
        rate=rate, seed=seed, active_window=60.0, oo_bound=0.3,
        offset_s=0.0)
    latest = float("-inf")
    for i in range(20_000):
        now = 0.5 + i / rate
        rec = ours(now)
        assert rec == prog(now), i
        latest = max(latest, rec[3])
    assert ours.n == prog.n
    # the latest watermark the source may issue over these records
    assert ours.watermark_limit() == latest - 0.3


@pytest.mark.parametrize("alpha", [1.0, 0.0])
def test_ysb_copy_replays_the_program_generator(alpha):
    from repro.streaming.ysb import YSBConfig, YSBGen
    seed = 2 ** 31 + 99
    prog = YSBGen(YSBConfig(rate=50_000.0, n_ads=100_000, zipf_alpha=alpha,
                            seed=seed))
    ours = _gen_module("ysb").YSBSource(rate=50_000.0, seed=seed,
                                        n_ads=100_000, zipf_alpha=alpha)
    for i in range(20_000):
        assert ours(i / 50_000.0) == prog(i / 50_000.0), i
    views = [r for r in (prog(0.0) for _ in range(3000))
             if r[1]["etype"] == "view"]
    assert 0.25 < len(views) / 3000 < 0.41


def test_offset_starts_q5_at_its_steady_active_range():
    cell = harness.load_cell("q5-hot-evict")
    assert cell.traffic["offset_s"] == 60.0
    rate = cell.traffic["rate"]
    gen = _gen_module("nexmark").make(cell.traffic, cell.config, 3)
    # 6% of the events open auctions, each active for 60 s
    steady = int(0.06 * rate * 60.0)
    assert gen.active_range(0.0 + gen.offset_s,
                            gen.auctions_per_s) == (0, steady)
    n = 20_000
    ids = [gen(i / rate) for i in range(n)]
    bids = [r[0] for r in ids if r[1]["type"] == "bid"]
    # the range keeps growing with the stream, by 6% of the rate a second
    assert steady - 1 in bids                        # the hot auction
    assert max(bids) < int(0.06 * rate * (60.0 + n / rate))
    assert min(bids) < steady // 30 and np.median(bids) > steady // 10
    # event time is not shifted
    assert all(r[3] <= i / rate for i, r in enumerate(ids))
    plain = _gen_module("nexmark").NexmarkSource(
        rate=rate, seed=3, active_window=60.0, oo_bound=0.3)
    early = [plain(i / rate)[0] for i in range(500)]
    assert max(early) < 100                      # no offset: a cold start


def test_q5_live_panes_over_the_span_exceed_the_slots():
    """Live panes (a window not yet past its end plus the allowed
    lateness behind the watermark, with at least one bid) counted from
    the generator alone, at the cell's full rate and offset, per keyed
    subtask (integer keys hash to ``key mod parallelism``)."""
    cell = harness.load_cell("q5-hot-evict")
    trf, cfg = cell.traffic, cell.config
    dep, par = cfg["deployment"], cfg["stateful_parallelism"]
    gen = _gen_module("nexmark").make(trf, cfg, 2024)
    size, slide = dep["window_size_s"], dep["window_slide_s"]
    late, oo = dep["allowed_lateness_s"], dep["oo_bound_s"]
    rate = trf["rate"]
    t0, t1 = trf["warmup_s"], trf["warmup_s"] + trf["span_s"]
    first = {}
    for i in range(int(t1 * rate)):
        now = (i + 1) / rate
        rec = gen(now)
        if rec[1]["type"] != "bid":
            continue
        ts = rec[3]
        wid = math.floor(ts / slide)
        while wid * slide > ts - size:
            first.setdefault((rec[0], wid), now)
            wid -= 1
    arrived = np.fromiter(first.values(), float)
    ends = np.fromiter((w * slide + size for _, w in first), float)
    sub = np.fromiter((a % par for a, _ in first), int)
    times = np.arange(t0, t1 + 1e-9, 0.05)
    live = np.array([[((arrived <= t) & (ends + late >= t - oo)
                       & (sub == s)).sum() for t in times]
                     for s in range(par)])
    slots = cfg["slots"]
    # the slots hold one window's panes of a subtask, not its live peak:
    # each subtask evicts before every fire's purge
    wids = np.fromiter((w for _, w in first), int)
    full = [((wids == w) & (sub == s)).sum() for s in range(par)
            for w in set(wids.tolist())
            if w * slide >= 0 and w * slide + size <= t1]
    assert max(full) < slots < live.max(axis=1).min()
    assert (live > slots).mean() > 0.2
