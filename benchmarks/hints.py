"""Hint-quality benchmark: all-hints vs selective vs selective+
speculative admission at matched offered load (DESIGN.md §13).

Runs NEXMark q5 (sliding-window panes), q8 (tumbling-window join), and
q20 (interval join) over the same arrival schedule, sweeping the
auction-id distribution — ``uniform`` (no skew), ``zipf`` (static hot
head), ``shift`` (zipf whose hot set ROTATES mid-run, the adversarial
case for learned suppression) — and the lookahead's HintFilter mode:

  * ``allhints``    — every extracted hint goes out (the ablation
                      baseline: maximum recall, maximum waste);
  * ``selective``   — residency + cold-key suppression with hot-key
                      priority (core/hint_filter.py decision table);
  * ``speculative`` — selective plus predicted hints: next-pane window
                      pre-hints at watermark advance and join-partner
                      frontier hints before the key appears upstream.

All three run TAC + Keyed Prefetching with delta-compressed hint
channels, so the ONLY variable is which hints are worth sending.  The
headline per scenario is the wasted-hint count (stagings evicted unused
PLUS duplicate hints for already-resident keys) against p99: selective
must cut waste without giving up tail latency, and every suppression is
graded retroactively (suppress_resident / suppress_miss /
suppress_unused) by the PrefetchRecorder.

Emits ``BENCH_hints.json``.  Expectation (ISSUE 7, the CI gate in
tools/bench_gate.py): on the Zipf scenario selective cuts wasted hints
>= 2x vs all-hints at equal load with p99 no worse, and q20 hint
precision improves from its 0.20 two-sided baseline (BENCH_joins.json).
``--smoke`` runs the Zipf column only at reduced scale.

    PYTHONPATH=src python benchmarks/hints.py
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

# HintFilter config per mode.  resident_ttl ~ a few hint-channel flush
# horizons: once a key was hinted, re-hinting inside that window only
# renews a resident entry — but only keys with CMS estimate >=
# resident_min_est are trusted to still BE resident (a cold key's
# staged entry loses every capacity fight; suppressing its re-hints
# converts prefetch hits into demand fetches, DESIGN.md §13).
MODES = {
    "allhints": {"mode": "all"},
    "selective": {"mode": "selective", "resident_ttl": 0.05,
                  "resident_min_est": 4},
    "speculative": {"mode": "selective", "resident_ttl": 0.05,
                    "resident_min_est": 4, "speculative": True,
                    "spec_width": 4},
}
DISTS = ("uniform", "zipf", "shift")

# calibrated full-scale configs (cache below the live key/pane
# population — the regime where wasted stagings evict load-bearing
# state; rates/windows follow BENCH_windowing / BENCH_joins).  The
# per-config "filter" block maps mode -> HintFilter overrides: the
# residency TTL models how long a staged entry survives in cache,
# which scales with cache size, so full-scale q5 (512 entries) carries
# a longer TTL than its smoke config (256); q20's speculative run adds
# the token-bucket budget (hot-key prioritisation under hint-channel
# saturation — its channel carries ~36k hints/s, the most of the
# three queries).
FULL = {
    "q5": dict(rate=5_000.0, active_window=1.0, oo_bound=0.3,
               window_size=2.0, window_slide=1.0, cache_entries=512,
               join_horizon=None, allowed_lateness=None, stateful="stateful",
               lookahead="win_lookahead",
               filter={"selective": {"resident_ttl": 0.12},
                       "speculative": {"resident_ttl": 0.12}}),
    "q8": dict(rate=9_000.0, active_window=4.0, oo_bound=0.3,
               window_size=2.0, window_slide=None, cache_entries=384,
               join_horizon=None, allowed_lateness=0.0, stateful="join",
               lookahead="join_lookahead", filter={}),
    "q20": dict(rate=18_000.0, active_window=30.0, oo_bound=0.25,
                window_size=None, window_slide=None, cache_entries=384,
                join_horizon=None, allowed_lateness=0.1, stateful="join",
                lookahead="join_lookahead",
                filter={"speculative": {"budget_per_s": 2_000.0,
                                        "priority_threshold": 8}}),
}
# reduced-scale CI smoke: same rates, smaller windows/horizons with
# proportionally smaller caches (and the default filter tuning)
SMOKE = {
    "q5": dict(FULL["q5"], window_size=1.0, window_slide=0.5,
               cache_entries=256, filter={}),
    "q8": dict(FULL["q8"], active_window=2.0, window_size=1.0,
               cache_entries=192),
    "q20": dict(FULL["q20"], active_window=15.0, cache_entries=224,
                filter={}),
}


def run_one(query: str, dist: str, mode: str, qcfg: dict, duration: float,
            warmup: float, seed: int = 7):
    from repro.streaming.backend import LOCAL_NVME
    from repro.streaming.nexmark import NexmarkConfig, build_query

    cfg = NexmarkConfig(rate=qcfg["rate"],
                        active_window=qcfg["active_window"],
                        oo_bound=qcfg["oo_bound"], seed=seed,
                        key_dist=dist)
    filt = dict(MODES[mode])
    filt.update(qcfg.get("filter", {}).get(mode, {}))
    eng = build_query(query, "tac", "prefetch", cfg,
                      cache_entries=qcfg["cache_entries"],
                      backend=LOCAL_NVME, parallelism=2,
                      source_parallelism=1, io_workers=4,
                      buffer_timeout=0.0003,
                      window_size=qcfg["window_size"],
                      window_slide=qcfg["window_slide"],
                      allowed_lateness=qcfg["allowed_lateness"],
                      join_horizon=qcfg["join_horizon"],
                      hint_filter=filt, compress_hints=True)
    m = eng.run(duration=duration, warmup=warmup)
    st, la = qcfg["stateful"], qcfg["lookahead"]
    hq = m.get(f"{st}_hint_quality", {})
    filt = m.get(f"{la}_hint_filter", {})
    received = m.get(f"{st}_hints_received", 0)
    # the headline: stagings that moved bytes nothing read, plus hints
    # that only renewed already-resident keys — the channel/staging work
    # selective admission exists to eliminate
    wasted_hints = hq.get("wasted", 0) + hq.get("duplicate", 0)
    emitted = filt.get("emitted", 0) \
        + m.get(f"{la}_burst_hints", 0) \
        + m.get(f"{la}_speculative_hints", 0)
    return {"p50": m["p50"], "p99": m["p99"], "p999": m["p999"],
            "throughput": m["throughput"],
            "hit_rate": m.get(f"{st}_hit_rate", 0.0),
            "hints_emitted": emitted,
            "hints_received": received,
            "speculative_hints": m.get(f"{la}_speculative_hints", 0),
            "burst_hints": m.get(f"{la}_burst_hints", 0),
            "wasted_hints": wasted_hints,
            "wasted_hint_ratio": wasted_hints / max(1, received),
            "precision": hq.get("precision", 0.0),
            "recall": hq.get("recall", 0.0),
            "hint_filter": filt,
            "hint_quality": hq,
            "hint_bytes": m.get("hint_bytes", 0),
            "hint_bytes_raw": m.get("hint_bytes_raw", 0),
            "hint_compression": m.get("hint_compression", 1.0),
            "backend_reads": m.get(f"{st}_backend_reads", 0)}


def main() -> None:
    from repro.compile_cache import use_compile_cache
    use_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--queries", default="q5,q8,q20")
    ap.add_argument("--dists", default=",".join(DISTS))
    ap.add_argument("--modes", default=",".join(MODES))
    ap.add_argument("--duration", type=float, default=6.0)
    ap.add_argument("--warmup", type=float, default=2.0)
    ap.add_argument("--smoke", action="store_true",
                    help="reduced-scale CI config (Zipf column only, 3s "
                         "runs) for the bench-smoke gate")
    ap.add_argument("--out", default="BENCH_hints.json")
    args = ap.parse_args()

    cfgs = SMOKE if args.smoke else FULL
    duration, warmup = (3.0, 1.5) if args.smoke else \
        (args.duration, args.warmup)
    dists = ["zipf"] if args.smoke and args.dists == ",".join(DISTS) \
        else args.dists.split(",")

    result = {"config": {"smoke": args.smoke, "duration": duration,
                         "warmup": warmup, "queries": dict(cfgs),
                         "modes": dict(MODES), "dists": dists,
                         "parallelism": 2, "io_workers": 4,
                         "buffer_timeout": 0.0003}}
    for query in args.queries.split(","):
        result[query] = {}
        for dist in dists:
            result[query][dist] = {}
            for mode in args.modes.split(","):
                t0 = time.time()
                r = run_one(query, dist, mode, cfgs[query], duration,
                            warmup)
                r["bench_wall_s"] = time.time() - t0
                result[query][dist][mode] = r
                print(f"[bench/hints] {query} {dist:7s} {mode:11s} "
                      f"p99={r['p99']*1e3:7.2f}ms "
                      f"wasted={r['wasted_hints']:6d} "
                      f"ratio={r['wasted_hint_ratio']:.3f} "
                      f"prec={r['precision']:.2f} "
                      f"recall={r['recall']:.2f} "
                      f"({r['bench_wall_s']:.0f}s)", file=sys.stderr)
            rs = result[query][dist]
            if "allhints" in rs and "selective" in rs:
                rs_all, rs_sel = rs["allhints"], rs["selective"]
                result[query][dist]["headline"] = {
                    "wasted_cut": rs_all["wasted_hints"]
                    / max(1, rs_sel["wasted_hints"]),
                    "p99_ratio": rs_sel["p99"]
                    / max(1e-12, rs_all["p99"]),
                    "precision_gain": rs_sel["precision"]
                    - rs_all["precision"],
                }
                if "speculative" in rs:
                    result[query][dist]["headline"][
                        "speculative_precision"] = \
                        rs["speculative"]["precision"]

    with open(args.out, "w") as f:
        json.dump(result, f, indent=2, sort_keys=True)
    print(json.dumps({q: {d: result[q][d].get("headline")
                          for d in dists}
                      for q in args.queries.split(",")}, indent=2))


if __name__ == "__main__":
    main()
