"""Failure/recovery benchmark: cold vs hint-warmed recovery at matched
offered load (DESIGN.md §7).

Runs NEXMark q5 (sliding-window hot items, §10) and event-time q20
(auction⋈bid interval join, §11) with barrier-aligned checkpoints over a
replayable source, injects a whole-job failure mid-run, and compares
three scenarios over the same arrival schedule:

  * ``unfailed`` — checkpoints on, no failure (the baseline the
    recovered run's steady state must return to);
  * ``cold``     — failure + restore of the last completed epoch, replay
    with a COLD cache: every replayed state access pays backend latency,
    the paper's on-demand profile concentrated into the catch-up window;
  * ``warmed``   — same failure, but the logged hint stream for the
    replay horizon (hint WAL + snapshotted HintsBuffer) is re-issued
    through the PrefetchingManager before the data path resumes, staging
    the hot set off the tuple path.

Reported per scenario: the POST-RESTORE p99 spike (latencies sinking
between resume and replay catch-up), steady-state p99 after catch-up,
recovery time (failure → caught up), checkpoint alignment stall, and
restore volume.  Emits ``BENCH_recovery.json``.  Expectation (ISSUE 5):
warmed recovery shows a lower post-restore p99 spike than cold on both
queries, and the recovered run's steady-state p99 stays within 1.2x the
unfailed run (the CI gate, tools/bench_gate.py).  ``--smoke`` runs a
reduced-scale config for the bench-smoke job.

    PYTHONPATH=src python benchmarks/recovery.py
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import numpy as np

# calibrated configs (DESIGN.md §8).  Per query, the gear that makes the
# cold-restore spike OBSERVABLE at p99 (without it the network-flush
# floor or async overlap hides state latency, and cold == warmed):
#
#   * q5  — fire-burst spike: when the watermark resumes, every pane of
#     the backlogged windows is read at once; a cold cache turns that
#     into an I/O-lane convoy.  Normal 4-lane pool, 2 ms flush gear
#     (the windowing-bench config).
#   * q20 — arrival-burst spike: the interval join's misses overlap so
#     well under a deep thread pool that a cold cache never queues; the
#     bench narrows the state thread pool to ONE lane per subtask
#     (steady-state demand stays well under its capacity) and runs the
#     0.3 ms low-latency flush gear, the same floor-lowering move as
#     benchmarks/joins.py.
#
# fail_at is relative to the end of warmup and lands just AFTER an epoch
# completes: the replay horizon stays short, so the spike isolates the
# cold-cache transient rather than raw catch-up queueing.
FULL = {
    "q5": dict(rate=5_000.0, active_window=1.0, oo_bound=0.3,
               window_size=1.0, window_slide=0.5, join_horizon=None,
               allowed_lateness=None, cache_entries=256, io_workers=4,
               buffer_timeout=0.002, ckpt_interval=0.8, fail_at=3.1,
               duration=9.0, warmup=1.0),
    "q20": dict(rate=12_000.0, active_window=8.0, oo_bound=0.25,
                window_size=None, window_slide=None, join_horizon=None,
                allowed_lateness=0.1, cache_entries=384, io_workers=1,
                buffer_timeout=0.0003, ckpt_interval=0.8, fail_at=3.1,
                duration=9.0, warmup=1.0),
}
SMOKE = {
    "q5": dict(rate=5_000.0, active_window=1.0, oo_bound=0.3,
               window_size=1.0, window_slide=0.5, join_horizon=None,
               allowed_lateness=None, cache_entries=256, io_workers=4,
               buffer_timeout=0.002, ckpt_interval=0.8, fail_at=2.3,
               duration=6.5, warmup=1.0),
    "q20": dict(rate=12_000.0, active_window=8.0, oo_bound=0.25,
                window_size=None, window_slide=None, join_horizon=None,
                allowed_lateness=0.1, cache_entries=384, io_workers=1,
                buffer_timeout=0.0003, ckpt_interval=0.8, fail_at=2.3,
                duration=6.5, warmup=1.0),
}

REPLAY_SPEEDUP = 2.0
SPIKE_WIN = 0.6      # post-restore transient window the spike p99 covers
STEADY_TAIL = 1.5    # steady-state p99 over the run's last seconds —
#                      the SAME wall window in every scenario, so the
#                      recovered steady state is compared against the
#                      unfailed run over matched samples


def _pctl(lat, t, lo, hi):
    sel = lat[(t >= lo) & (t < hi)]
    if len(sel) == 0:
        return None, 0
    return float(np.percentile(sel, 99)), int(len(sel))


def run_one(query: str, scenario: str, qcfg: dict, seed: int = 7):
    from repro.streaming.backend import LOCAL_NVME
    from repro.streaming.nexmark import NexmarkConfig, build_query
    from repro.streaming.recovery import (CheckpointCoordinator,
                                          inject_failure_at)

    cfg = NexmarkConfig(rate=qcfg["rate"],
                        active_window=qcfg["active_window"],
                        oo_bound=qcfg["oo_bound"], seed=seed)
    eng = build_query(query, "tac", "prefetch", cfg,
                      cache_entries=qcfg["cache_entries"],
                      backend=LOCAL_NVME, parallelism=2,
                      source_parallelism=1,
                      io_workers=qcfg["io_workers"],
                      buffer_timeout=qcfg["buffer_timeout"],
                      window_size=qcfg["window_size"],
                      window_slide=qcfg["window_slide"],
                      allowed_lateness=qcfg["allowed_lateness"],
                      join_horizon=qcfg["join_horizon"],
                      replayable=True)
    coord = CheckpointCoordinator(eng, interval=qcfg["ckpt_interval"])
    coord.start()
    t_fail = qcfg["warmup"] + qcfg["fail_at"]
    if scenario != "unfailed":
        inject_failure_at(eng, at=t_fail, mode=scenario,
                          replay_speedup=REPLAY_SPEEDUP)
    m = eng.run(duration=qcfg["duration"], warmup=qcfg["warmup"])

    op = "stateful" if query in ("q5", "q7") else "join"
    lat = np.asarray(eng.latencies)
    t = np.asarray(eng.latency_t)
    t_end = qcfg["warmup"] + qcfg["duration"]
    ck = m.get("checkpoint", {})
    steady_p99, n_steady = _pctl(lat, t, t_end - STEADY_TAIL, float("inf"))
    out = {"p50": m["p50"], "p99": m["p99"], "p999": m["p999"],
           "throughput": m["throughput"],
           "hit_rate": m.get(f"{op}_hit_rate", 0.0),
           "backend_reads": m.get(f"{op}_backend_reads", 0),
           "epochs_completed": ck.get("epochs_completed", 0),
           "align_stall_avg": ck.get("align_stall_avg", 0.0),
           "align_stall_max": ck.get("align_stall_max", 0.0),
           "snapshot_bytes": ck.get("snapshot_bytes_total", 0),
           "steady_p99": steady_p99, "steady_samples": n_steady}
    if scenario == "unfailed":
        return out

    rec = m.get("recovery", {})
    src = eng.operators["source"]
    done = [d for d in src.replay_done_t if d is not None]
    t_resume = rec.get("last_t_resume", t_fail)
    t_caught_up = max(done) if done else t_end
    spike_p99, n_spike = _pctl(lat, t, t_resume, t_resume + SPIKE_WIN)
    out.update({
        "post_restore_p99": spike_p99,
        "post_restore_samples": n_spike,
        "recovery_time": t_caught_up - t_fail,
        "downtime": rec.get("last_downtime"),
        "restore_bytes": rec.get("last_restore_bytes"),
        "warmup_lead": rec.get("last_warmup_lead"),
        "warmup_hints": rec.get("warmup_hints", 0),
        "replayed": rec.get("replayed", 0),
        "restored_epoch": rec.get("last_epoch"),
    })
    return out


def main() -> None:
    from repro.compile_cache import use_compile_cache
    use_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--queries", default="q5,q20")
    ap.add_argument("--scenarios", default="unfailed,cold,warmed")
    ap.add_argument("--smoke", action="store_true",
                    help="reduced-scale CI config for the bench-smoke "
                         "recovery gate")
    ap.add_argument("--out", default="BENCH_recovery.json")
    args = ap.parse_args()

    cfgs = SMOKE if args.smoke else FULL
    result = {"config": {"smoke": args.smoke, "queries": dict(cfgs),
                         "parallelism": 2,
                         "replay_speedup": REPLAY_SPEEDUP,
                         "spike_window": SPIKE_WIN,
                         "steady_tail": STEADY_TAIL}}
    for query in args.queries.split(","):
        result[query] = {}
        for scenario in args.scenarios.split(","):
            t0 = time.time()
            r = run_one(query, scenario, cfgs[query])
            r["bench_wall_s"] = time.time() - t0
            result[query][scenario] = r
            spike = r.get("post_restore_p99")
            print(f"[bench/recovery] {query} {scenario:9s} "
                  f"p99={r['p99']*1e3:7.2f}ms "
                  + (f"spike_p99={spike*1e3:7.2f}ms "
                     f"steady_p99={(r['steady_p99'] or 0)*1e3:6.2f}ms "
                     f"rec={r['recovery_time']:.2f}s "
                     f"warm_hints={r['warmup_hints']} "
                     if spike is not None else
                     f"(epochs={r['epochs_completed']}) ")
                  + f"({r['bench_wall_s']:.0f}s)", file=sys.stderr)
        rs = result[query]
        if "cold" in rs and "warmed" in rs \
                and rs["cold"].get("post_restore_p99") \
                and rs["warmed"].get("post_restore_p99"):
            headline = {"spike_reduction_vs_cold":
                        rs["cold"]["post_restore_p99"]
                        / max(1e-12, rs["warmed"]["post_restore_p99"])}
            if rs.get("unfailed"):
                headline["warmed_steady_vs_unfailed"] = \
                    (rs["warmed"]["steady_p99"] or 0.0) \
                    / max(1e-12, rs["unfailed"]["steady_p99"]
                          or rs["unfailed"]["p99"])
            result[query]["headline"] = headline
            print(f"[bench/recovery] {query} warmed spike reduction "
                  f"x{headline['spike_reduction_vs_cold']:.2f} vs cold",
                  file=sys.stderr)

    with open(args.out, "w") as f:
        json.dump(result, f, indent=2, sort_keys=True)
    print(json.dumps({q: result[q].get("headline")
                      for q in args.queries.split(",")}, indent=2))


if __name__ == "__main__":
    main()
