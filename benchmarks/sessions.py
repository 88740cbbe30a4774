"""Session-window benchmark: MOVING-deadline hints vs arrival-ts hints
vs on-demand on NEXMark q11 (per-bidder activity sessions, DESIGN.md
§15).

Sessions are the adversarial case for deadline prefetching: a pane's
fire deadline is not known at assignment — every bid extends it and a
bridging bid MERGES two panes — so the lookahead must RE-HINT each move
and the TAC must renew resident panes in place.  Three modes over the
same arrival schedule:

  * ``ondemand``  — LRU cache, synchronous state access (no hints);
  * ``arrival``   — TAC + Keyed Prefetching, per-tuple ARRIVAL-ts hints
                    (right pane, mistimed for fire-time reads);
  * ``deadline``  — TAC + hints carrying the session's CURRENT end,
                    re-hinted on every extension/merge, deadline-aware
                    eviction and fire-time burst.

Emits ``BENCH_sessions.json``.  Expectation (ISSUE 9): the session query
under prefetch (deadline) holds p99 <= on-demand at equal offered load —
gated by tools/bench_gate.py.  ``--smoke`` is the reduced CI config.

    PYTHONPATH=src python benchmarks/sessions.py
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

# cache calibrated BELOW the active-pane population (the regime where
# eviction ordering matters: on-demand thrashes panes awaiting fire)
FULL = {
    "q11": dict(rate=6_000.0, oo_bound=0.2, session_gap=0.4,
                allowed_lateness=0.2, cache_entries=128),
}
# reduced-scale CI smoke: same gap geometry (fire cadence must survive),
# lower rate and a proportionally smaller cache
SMOKE = {
    "q11": dict(rate=4_000.0, oo_bound=0.2, session_gap=0.4,
                allowed_lateness=0.2, cache_entries=96),
}


def run_one(query: str, mode: str, qcfg: dict, duration: float,
            warmup: float, seed: int = 7):
    from repro.streaming.backend import LOCAL_NVME
    from repro.streaming.nexmark import NexmarkConfig, build_query

    policy, access, hint_ts = MODES[mode]
    cfg = NexmarkConfig(rate=qcfg["rate"], oo_bound=qcfg["oo_bound"],
                        seed=seed, watermark_interval=0.05)
    eng = build_query(query, policy, access, cfg,
                      cache_entries=qcfg["cache_entries"],
                      backend=LOCAL_NVME, parallelism=2,
                      source_parallelism=1, io_workers=4,
                      buffer_timeout=0.002, hint_ts=hint_ts,
                      session_gap=qcfg["session_gap"],
                      allowed_lateness=qcfg["allowed_lateness"])
    # q11 stamps a fire with its session's end, so sink latency holds
    # the watermark's lag, the same in every mode; this benchmark
    # compares the fire-time state read, so it stamps the fire's own
    # scheduling time
    eng.operators["stateful"].latency_from_end = False
    m = eng.run(duration=duration, warmup=warmup)
    return {"p50": m["p50"], "p99": m["p99"], "p999": m["p999"],
            "throughput": m["throughput"],
            "hit_rate": m.get("stateful_hit_rate", 0.0),
            "fires": m.get("stateful_fires", 0),
            "sessions_created": m.get("stateful_sessions_created", 0),
            "sessions_merged": m.get("stateful_sessions_merged", 0),
            "sessions_reopened": m.get("stateful_sessions_reopened", 0),
            "panes_purged": m.get("stateful_panes_purged", 0),
            "late_dropped": m.get("stateful_late_dropped", 0),
            "rehints": m.get("sess_lookahead_rehints", 0),
            "burst_hints": m.get("sess_lookahead_burst_hints", 0),
            "hints_received": m.get("stateful_hints_received", 0),
            "prefetch_hits": m.get("stateful_prefetch_hits", 0),
            "backend_reads": m.get("stateful_backend_reads", 0),
            "hint_quality": m.get("stateful_hint_quality", {}),
            "evictions": m.get("stateful_evictions", {})}


def main() -> None:
    from repro.compile_cache import use_compile_cache
    use_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--queries", default="q11")
    ap.add_argument("--modes", default="ondemand,arrival,deadline")
    ap.add_argument("--duration", type=float, default=6.0)
    ap.add_argument("--warmup", type=float, default=2.0)
    ap.add_argument("--smoke", action="store_true",
                    help="reduced-scale CI config (3s run) for the "
                         "bench-smoke perf gate")
    ap.add_argument("--out", default="BENCH_sessions.json")
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
            print(f"[bench/sessions] {query} {mode:9s} "
                  f"p50={r['p50']*1e3:6.2f}ms p99={r['p99']*1e3:7.2f}ms "
                  f"hit={r['hit_rate']:.2f} fires={r['fires']} "
                  f"merged={r['sessions_merged']} "
                  f"rehints={r['rehints']} ({r['bench_wall_s']:.0f}s)",
                  file=sys.stderr)
        rs = result[query]
        if "deadline" in rs:
            headline = {}
            for base in ("ondemand", "arrival"):
                if base in rs:
                    headline[f"p99_speedup_vs_{base}"] = \
                        rs[base]["p99"] / max(1e-12, rs["deadline"]["p99"])
            result[query]["headline"] = headline
            print(f"[bench/sessions] {query} deadline p99 speedup: "
                  + ", ".join(f"{k.split('_vs_')[1]} x{v:.2f}"
                              for k, v in headline.items()),
                  file=sys.stderr)

    with open(args.out, "w") as f:
        json.dump(result, f, indent=2, sort_keys=True)
    print(json.dumps({q: result[q].get("headline")
                      for q in args.queries.split(",")}, indent=2))


if __name__ == "__main__":
    main()
