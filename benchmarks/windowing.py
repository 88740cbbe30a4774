"""Event-time windowing benchmark: deadline-ts vs arrival-ts hints vs
on-demand at matched offered load (DESIGN.md §10).

Runs NEXMark q5 (hot items, SLIDING window, late-side updates) and q7
(highest bid, TUMBLING window, late drops) over the same arrival schedule
in three modes:

  * ``ondemand``  — LRU cache, synchronous state access (no hints);
  * ``arrival``   — TAC + Keyed Prefetching with per-tuple ARRIVAL-ts
                    hints (accurate key, mistimed for fire-time reads);
  * ``deadline``  — TAC + hints carrying the WINDOW-FIRE DEADLINE, with
                    fire-time burst prefetch and deadline-aware eviction.

Cache capacity is calibrated between one window's pane count and the
live-pane total, the regime where ordering matters: arrival-ts ordering
evicts panes of the window awaiting fire, so its fire burst stalls on
backend refetches; deadline ordering keeps the next-to-fire window
resident and the burst re-stages the rest off the tuple path.

Emits ``BENCH_windowing.json``.  Expectation (ISSUE 3): deadline-ts beats
BOTH baselines on p99 end-to-end latency for q5 and q7 at equal load.
``--smoke`` runs a reduced-scale config for the CI perf gate
(tools/bench_gate.py).

    PYTHONPATH=src python benchmarks/windowing.py
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

MODES = {"ondemand": ("lru", "sync", "deadline"),
         "arrival": ("tac", "prefetch", "arrival"),
         "deadline": ("tac", "prefetch", "deadline")}

# calibrated full-scale configs (see module docstring on the cache regime)
FULL = {
    "q5": dict(rate=5_000.0, active_window=1.0, oo_bound=0.3,
               window_size=2.0, window_slide=1.0, cache_entries=512),
    "q7": dict(rate=8_000.0, active_window=2.0, oo_bound=0.4,
               window_size=2.0, window_slide=None, cache_entries=576),
}
# reduced-scale CI smoke: same rates (the cache/pane-count balance must
# survive), half-size windows with proportionally smaller caches
SMOKE = {
    "q5": dict(rate=5_000.0, active_window=1.0, oo_bound=0.3,
               window_size=1.0, window_slide=0.5, cache_entries=256),
    "q7": dict(rate=8_000.0, active_window=2.0, oo_bound=0.4,
               window_size=1.0, window_slide=None, cache_entries=288),
}


def run_one(query: str, mode: str, qcfg: dict, duration: float,
            warmup: float, seed: int = 7):
    from repro.streaming.backend import LOCAL_NVME
    from repro.streaming.nexmark import NexmarkConfig, build_query

    policy, access, hint_ts = MODES[mode]
    cfg = NexmarkConfig(rate=qcfg["rate"], active_window=qcfg["active_window"],
                        oo_bound=qcfg["oo_bound"], seed=seed)
    eng = build_query(query, policy, access, cfg,
                      cache_entries=qcfg["cache_entries"],
                      backend=LOCAL_NVME, parallelism=2,
                      source_parallelism=1, io_workers=4,
                      buffer_timeout=0.002, hint_ts=hint_ts,
                      window_size=qcfg["window_size"],
                      window_slide=qcfg["window_slide"])
    m = eng.run(duration=duration, warmup=warmup)
    return {"p50": m["p50"], "p99": m["p99"], "p999": m["p999"],
            "throughput": m["throughput"],
            "hit_rate": m.get("stateful_hit_rate", 0.0),
            "fires": m.get("stateful_fires", 0),
            "late_dropped": m.get("stateful_late_dropped", 0),
            "late_updates": m.get("stateful_late_updates", 0),
            "panes_purged": m.get("stateful_panes_purged", 0),
            "burst_hints": m.get("win_lookahead_burst_hints", 0),
            "hints_received": m.get("stateful_hints_received", 0),
            "hints_late": m.get("stateful_hints_late", 0),
            "prefetch_hits": m.get("stateful_prefetch_hits", 0),
            "backend_reads": m.get("stateful_backend_reads", 0),
            # prefetch-quality telemetry (DESIGN.md §12): per-hint
            # outcomes, precision/recall, signed lead-time percentiles
            "hint_quality": m.get("stateful_hint_quality", {}),
            "evictions": m.get("stateful_evictions", {})}


def main() -> None:
    from repro.compile_cache import use_compile_cache
    use_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--queries", default="q5,q7")
    ap.add_argument("--modes", default="ondemand,arrival,deadline")
    ap.add_argument("--duration", type=float, default=6.0)
    ap.add_argument("--warmup", type=float, default=2.0)
    ap.add_argument("--smoke", action="store_true",
                    help="reduced-scale CI config (half-size windows, "
                         "3s run) for the bench-smoke perf gate")
    ap.add_argument("--out", default="BENCH_windowing.json")
    args = ap.parse_args()

    cfgs = SMOKE if args.smoke else FULL
    duration, warmup = (3.0, 1.5) if args.smoke else \
        (args.duration, args.warmup)

    result = {"config": {"smoke": args.smoke, "duration": duration,
                         "warmup": warmup, "queries": dict(cfgs),
                         "parallelism": 2, "io_workers": 4,
                         "buffer_timeout": 0.002}}
    for query in args.queries.split(","):
        result[query] = {}
        for mode in args.modes.split(","):
            t0 = time.time()
            r = run_one(query, mode, cfgs[query], duration, warmup)
            r["bench_wall_s"] = time.time() - t0
            result[query][mode] = r
            print(f"[bench/windowing] {query} {mode:9s} "
                  f"p50={r['p50']*1e3:6.2f}ms p99={r['p99']*1e3:7.2f}ms "
                  f"hit={r['hit_rate']:.2f} fires={r['fires']} "
                  f"late={r['late_dropped']}+{r['late_updates']} "
                  f"({r['bench_wall_s']:.0f}s)", file=sys.stderr)
        rs = result[query]
        if "deadline" in rs:
            headline = {}
            for base in ("ondemand", "arrival"):
                if base in rs:
                    headline[f"p99_speedup_vs_{base}"] = \
                        rs[base]["p99"] / max(1e-12, rs["deadline"]["p99"])
            result[query]["headline"] = headline
            print(f"[bench/windowing] {query} deadline p99 speedup: "
                  + ", ".join(f"{k.split('_vs_')[1]} x{v:.2f}"
                              for k, v in headline.items()),
                  file=sys.stderr)

    with open(args.out, "w") as f:
        json.dump(result, f, indent=2, sort_keys=True)
    print(json.dumps({q: result[q].get("headline")
                      for q in args.queries.split(",")}, indent=2))


if __name__ == "__main__":
    main()
