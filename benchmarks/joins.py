"""Stream-stream join benchmark: two-sided vs one-sided hints vs
on-demand at matched offered load (DESIGN.md §11).

Runs NEXMark q8 (tumbling-window person⋈auction, co-grouped panes fired
on watermark) and q20 (auction⋈bid interval join with retention-deadline
expiry) over the same arrival schedule in three modes:

  * ``ondemand``  — LRU cache, synchronous state access (no hints);
  * ``onesided``  — TAC + Keyed Prefetching with hints from the PROBE
                    side only (auctions for q8, bids for q20): the
                    conventional lookahead, blind to the build side;
  * ``twosided``  — both inputs emit cross-side hints: a build-side
                    tuple pre-stages the state future probes will read
                    (pane-deadline hints for q8, retention-deadline
                    hints for q20), so the key is resident before its
                    FIRST probe arrives and stays protected for as long
                    as a match remains possible.

Cache capacity is calibrated below the live key/pane population, the
regime where on-demand thrashes and hint protection decides which side
of the join survives eviction.

Emits ``BENCH_joins.json``.  Expectation (ISSUE 4): two-sided hints beat
on-demand on p99 end-to-end latency for q8 and q20 at equal load (the
CI gate), and improve on one-sided hints where build-side state matters.
``--smoke`` runs a reduced-scale config for the bench-smoke perf gate
(tools/bench_gate.py).

    PYTHONPATH=src python benchmarks/joins.py
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

MODES = {"ondemand": ("lru", "sync", "two"),
         "onesided": ("tac", "prefetch", "one"),
         "twosided": ("tac", "prefetch", "two")}

# calibrated full-scale configs (cache below the live key population,
# data channels in the low-latency flush gear so the floor does not mask
# state-access effects — DESIGN.md §8)
FULL = {
    "q8": dict(rate=9_000.0, active_window=4.0, oo_bound=0.3,
               window_size=2.0, join_horizon=None, cache_entries=384,
               allowed_lateness=0.0),
    "q20": dict(rate=18_000.0, active_window=30.0, oo_bound=0.25,
                window_size=None, join_horizon=None, cache_entries=384,
                allowed_lateness=0.1),
}
# reduced-scale CI smoke: same rates (the cache/population balance must
# survive), smaller windows/horizons with proportionally smaller caches
SMOKE = {
    "q8": dict(rate=9_000.0, active_window=2.0, oo_bound=0.3,
               window_size=1.0, join_horizon=None, cache_entries=192,
               allowed_lateness=0.0),
    "q20": dict(rate=18_000.0, active_window=15.0, oo_bound=0.25,
                window_size=None, join_horizon=None, cache_entries=224,
                allowed_lateness=0.1),
}


def run_one(query: str, mode: str, qcfg: dict, duration: float,
            warmup: float, seed: int = 7):
    from repro.streaming.backend import LOCAL_NVME
    from repro.streaming.nexmark import NexmarkConfig, build_query

    policy, access, sides = MODES[mode]
    cfg = NexmarkConfig(rate=qcfg["rate"],
                        active_window=qcfg["active_window"],
                        oo_bound=qcfg["oo_bound"], seed=seed)
    eng = build_query(query, policy, access, cfg,
                      cache_entries=qcfg["cache_entries"],
                      backend=LOCAL_NVME, parallelism=2,
                      source_parallelism=1, io_workers=4,
                      buffer_timeout=0.0003,
                      window_size=qcfg["window_size"],
                      allowed_lateness=qcfg["allowed_lateness"],
                      join_hints=sides, join_horizon=qcfg["join_horizon"])
    m = eng.run(duration=duration, warmup=warmup)
    return {"p50": m["p50"], "p99": m["p99"], "p999": m["p999"],
            "throughput": m["throughput"],
            "hit_rate": m.get("join_hit_rate", 0.0),
            "joined": m.get("join_joined", 0),
            "late_dropped": m.get("join_late_dropped", 0),
            "keys_expired": m.get("join_keys_expired", 0),
            "fires": m.get("join_fires", 0),
            "hints_left": m.get("join_lookahead_hints_left", 0),
            "hints_right": m.get("join_lookahead_hints_right", 0),
            "hints_received": m.get("join_hints_received", 0),
            "hints_late": m.get("join_hints_late", 0),
            "prefetch_hits": m.get("join_prefetch_hits", 0),
            "backend_reads": m.get("join_backend_reads", 0),
            # prefetch-quality telemetry (DESIGN.md §12): per-hint
            # outcomes, precision/recall, signed lead-time percentiles
            "hint_quality": m.get("join_hint_quality", {}),
            "evictions": m.get("join_evictions", {})}


def main() -> None:
    from repro.compile_cache import use_compile_cache
    use_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--queries", default="q8,q20")
    ap.add_argument("--modes", default="ondemand,onesided,twosided")
    ap.add_argument("--duration", type=float, default=6.0)
    ap.add_argument("--warmup", type=float, default=2.0)
    ap.add_argument("--smoke", action="store_true",
                    help="reduced-scale CI config (smaller windows/"
                         "horizons, 3s run) for the bench-smoke gate")
    ap.add_argument("--out", default="BENCH_joins.json")
    args = ap.parse_args()

    cfgs = SMOKE if args.smoke else FULL
    duration, warmup = (3.0, 1.5) if args.smoke else \
        (args.duration, args.warmup)

    result = {"config": {"smoke": args.smoke, "duration": duration,
                         "warmup": warmup, "queries": dict(cfgs),
                         "parallelism": 2, "io_workers": 4,
                         "buffer_timeout": 0.0003}}
    for query in args.queries.split(","):
        result[query] = {}
        for mode in args.modes.split(","):
            t0 = time.time()
            r = run_one(query, mode, cfgs[query], duration, warmup)
            r["bench_wall_s"] = time.time() - t0
            result[query][mode] = r
            print(f"[bench/joins] {query} {mode:9s} "
                  f"p50={r['p50']*1e3:6.2f}ms p99={r['p99']*1e3:7.2f}ms "
                  f"hit={r['hit_rate']:.2f} joined={r['joined']} "
                  f"hints=L{r['hints_left']}/R{r['hints_right']} "
                  f"({r['bench_wall_s']:.0f}s)", file=sys.stderr)
        rs = result[query]
        if "twosided" in rs:
            headline = {}
            for base in ("ondemand", "onesided"):
                if base in rs:
                    headline[f"p99_speedup_vs_{base}"] = \
                        rs[base]["p99"] / max(1e-12, rs["twosided"]["p99"])
            result[query]["headline"] = headline
            print(f"[bench/joins] {query} twosided p99 speedup: "
                  + ", ".join(f"{k.split('_vs_')[1]} x{v:.2f}"
                              for k, v in headline.items()),
                  file=sys.stderr)

    with open(args.out, "w") as f:
        json.dump(result, f, indent=2, sort_keys=True)
    print(json.dumps({q: result[q].get("headline")
                      for q in args.queries.split(",")}, indent=2))


if __name__ == "__main__":
    main()
