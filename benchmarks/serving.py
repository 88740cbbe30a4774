"""Serving benchmark: prefetch vs on-demand TTFT at equal offered load.

Runs the paged session-state serving path (real jitted smoke-model decode,
calibrated store latency on the hybrid clock) in ``sync`` (on-demand
staging), ``async`` and ``prefetch`` modes over the SAME arrival schedule,
and emits ``BENCH_serving.json``.

    PYTHONPATH=src python benchmarks/serving.py --requests 48
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))


def main() -> None:
    from repro.compile_cache import use_compile_cache
    use_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="gemma-7b")
    ap.add_argument("--requests", type=int, default=48)
    ap.add_argument("--sessions", type=int, default=16)
    ap.add_argument("--cache-sessions", type=int, default=8)
    ap.add_argument("--rate", type=float, default=500.0)
    ap.add_argument("--decode-tokens", type=int, default=3)
    ap.add_argument("--modes", default="sync,async,prefetch")
    ap.add_argument("--smoke", action="store_true",
                    help="reduced-scale CI config (fewer requests/"
                         "sessions, sync+prefetch only) for the "
                         "bench-smoke perf gate (tools/bench_gate.py)")
    ap.add_argument("--out", default="BENCH_serving.json")
    args = ap.parse_args()

    if args.smoke:
        args.requests = min(args.requests, 16)
        args.sessions = min(args.sessions, 8)
        args.cache_sessions = min(args.cache_sessions, 4)
        args.decode_tokens = min(args.decode_tokens, 2)
        if args.modes == "sync,async,prefetch":
            args.modes = "sync,prefetch"

    from repro.launch.serve import ServeConfig, run_serving

    cfg = ServeConfig(arch=args.arch, n_requests=args.requests,
                      n_sessions=args.sessions,
                      cache_sessions=args.cache_sessions,
                      decode_tokens=args.decode_tokens,
                      arrival_rate=args.rate)
    result = {"config": {"smoke": args.smoke,
                         "arch": cfg.arch, "n_requests": cfg.n_requests,
                         "n_sessions": cfg.n_sessions,
                         "cache_sessions": cfg.cache_sessions,
                         "arrival_rate": cfg.arrival_rate,
                         "decode_tokens": cfg.decode_tokens,
                         "store_latency": cfg.store_latency}}
    for mode in args.modes.split(","):
        t0 = time.time()
        r = run_serving(cfg, mode)
        r["bench_wall_s"] = time.time() - t0
        result[mode] = r
        print(f"[bench/serving] {mode:8s} "
              f"ttft p50={r['ttft_p50']*1e3:7.2f}ms "
              f"p99={r['ttft_p99']*1e3:7.2f}ms "
              f"tpot p50={r['tpot_p50']*1e3:6.2f}ms "
              f"hit={r['arena_hit_rate']:.2f} "
              f"overlap={r['staging_overlap']:.2f} "
              f"({r['bench_wall_s']:.0f}s)", file=sys.stderr)

    if "sync" in result and "prefetch" in result:
        sp = result["sync"]["ttft_p99"] / max(1e-12,
                                              result["prefetch"]["ttft_p99"])
        result["prefetch_p99_ttft_speedup"] = sp
        print(f"[bench/serving] prefetch p99 TTFT speedup {sp:.2f}x "
              "at equal offered load", file=sys.stderr)

    with open(args.out, "w") as f:
        json.dump(result, f, indent=2, sort_keys=True)
    print(json.dumps({k: v for k, v in result.items()
                      if k != "config"}, indent=2, sort_keys=True))


if __name__ == "__main__":
    main()
