"""Observability overhead benchmark: tracing-enabled vs disabled
wall-clock throughput on the q5 smoke pipeline (DESIGN.md §12).

The observability plane's contract is ZERO-COST WHEN OFF and cheap when
on: sources check one flag per tuple, operator marks hide behind a
``trace is not None`` test, and disabled registry handles are shared
no-op singletons.  This benchmark proves it with the only number that
can — WALL-CLOCK tuples/sec (sim-time latency percentiles are invariant
to host overhead by construction, so they cannot see instrumentation
cost):

  * ``disabled`` — tracing off (``sample_every=0``), the default;
  * ``traced``   — per-tuple critical-path tracing at the default
                   sampling rate plus a periodic JSONL snapshot export;
  * ``timeline`` — everything ``traced`` does PLUS the temporal plane
                   (DESIGN.md §16): interval snapshots on the logical
                   clock, the full health-detector set, engine event
                   recording, and a Perfetto/Chrome trace export.

Host noise on a shared machine dwarfs the actual instrumentation cost,
so the modes are INTERLEAVED (disabled, traced, timeline, disabled,
...) — temporal drift hits all equally — and each mode keeps the best
of its ``--repeats`` runs.  Disabled still goes first in every round,
so any warm-cache advantage of running later accrues to the
instrumented modes: conservative is fine, flattering is not.

The run also replays the chaos alert oracle (DESIGN.md §16): on three
seeded fault schedules, the golden run must raise ZERO alerts and every
effective injected fault must raise its mapped alert within the logical
delay bound — the ``alerts`` block the gate reads.

Emits ``BENCH_obs.json`` plus ``obs_trace.json`` (a Perfetto
trace of the timeline run — loadable in chrome://tracing / ui.perfetto.dev).
The bench-smoke gate (tools/bench_gate.py) requires traced AND timeline
throughput >= 0.95x disabled, a dominant stage, nonzero staged hints,
alert-oracle recall 1.0, and zero golden false alerts.

    PYTHONPATH=src python benchmarks/obs.py --smoke
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import tempfile
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

# the windowing benchmark's q5 configs (same calibration rationale —
# benchmarks/windowing.py): deadline-ts hints so the hint-quality block
# exercises every outcome class
FULL = dict(rate=5_000.0, active_window=1.0, oo_bound=0.3,
            window_size=2.0, window_slide=1.0, cache_entries=512)
SMOKE = dict(rate=5_000.0, active_window=1.0, oo_bound=0.3,
             window_size=1.0, window_slide=0.5, cache_entries=256)


def run_one(mode: str, qcfg: dict, duration: float, warmup: float,
            sample_every: int, seed: int = 7, trace_out: str = None):
    from repro.streaming.backend import LOCAL_NVME
    from repro.streaming.nexmark import NexmarkConfig, build_query

    cfg = NexmarkConfig(rate=qcfg["rate"],
                        active_window=qcfg["active_window"],
                        oo_bound=qcfg["oo_bound"], seed=seed)
    eng = build_query("q5", "tac", "prefetch", cfg,
                      cache_entries=qcfg["cache_entries"],
                      backend=LOCAL_NVME, parallelism=2,
                      source_parallelism=1, io_workers=4,
                      buffer_timeout=0.002, hint_ts="deadline",
                      window_size=qcfg["window_size"],
                      window_slide=qcfg["window_slide"])
    export_path = None
    if mode in ("traced", "timeline"):
        eng.enable_tracing(sample_every=sample_every)
        export_path = os.path.join(tempfile.mkdtemp(prefix="obs_bench_"),
                                   "snapshots.jsonl")
        eng.enable_export(export_path, interval=0.5)
    if mode == "timeline":
        eng.enable_timeline(interval=0.1)
    t0 = time.perf_counter()
    m = eng.run(duration=duration, warmup=warmup)
    wall_s = time.perf_counter() - t0
    r = {"wall_s": wall_s, "n_outputs": m["n_outputs"],
         "tuples_per_s": m["n_outputs"] / wall_s if wall_s > 0 else 0.0,
         "p50": m["p50"], "p99": m["p99"],
         "hit_rate": m.get("stateful_hit_rate", 0.0)}
    if mode in ("traced", "timeline"):
        r["trace"] = m.get("trace", {})
        r["hint_quality"] = m.get("stateful_hint_quality", {})
        r["evictions"] = m.get("stateful_evictions", {})
        with open(export_path) as f:
            r["export_snapshots"] = sum(1 for _ in f)
    if mode == "timeline":
        r["timeline"] = m.get("timeline", {})
        r["health"] = m.get("health", {})
        r["n_alerts"] = len(m.get("alerts", []))
        if trace_out:
            from repro.obs import chrome_trace
            trace = chrome_trace(eng, path=trace_out)
            r["perfetto_events"] = len(trace["traceEvents"])
    return r


# the three validated oracle schedules (tests/test_timeline.py runs the
# same set): every fault kind the oracle maps, plus one deliberately
# ineffective migrate that effective-event filtering must drop
def oracle_schedules():
    from repro.streaming.chaos import FaultEvent, FaultSchedule
    return [
        FaultSchedule(101, (
            FaultEvent("load_shift", 0.5, (2.5, 0.5)),
            FaultEvent("migrate", 1.0, (0, 1)),
            FaultEvent("failure", 1.3, ("warmed",)))),
        FaultSchedule(202, (
            FaultEvent("failure", 0.7, ("cold",)),
            FaultEvent("load_shift", 1.1, (0.4, 0.4)),
            FaultEvent("migrate", 1.4, (1, 0)))),
        FaultSchedule(303, (
            FaultEvent("migrate", 0.5, (3, 0)),
            FaultEvent("migrate", 0.7, (2, 0)),
            FaultEvent("load_shift", 0.9, (3.0, 0.4)),
            FaultEvent("failure", 1.35, ("warmed",)))),
    ]


def run_alert_oracle():
    """Chaos-validated detector soundness + sensitivity (the gate's
    ``alerts`` rule): aggregate recall and golden-false-alert counts
    over the seeded schedules."""
    from repro.streaming.chaos import alert_oracle, run_schedule
    agg = {"schedules": [], "injected": 0, "matched": 0,
           "golden_alerts": 0, "golden_false_stall": 0,
           "per_kind": {}}
    for sched in oracle_schedules():
        golden = run_schedule(sched.with_events(()), t_cut=2.0,
                              observe=True)
        pert = run_schedule(sched, t_cut=2.0, observe=True)
        rep = alert_oracle(sched, pert, golden)
        agg["schedules"].append({"seed": sched.seed, **{
            k: rep[k] for k in ("injected", "matched", "recall",
                                "golden_alerts", "golden_false_stall",
                                "per_kind")}})
        agg["injected"] += rep["injected"]
        agg["matched"] += rep["matched"]
        agg["golden_alerts"] += rep["golden_alerts"]
        agg["golden_false_stall"] += rep["golden_false_stall"]
        for kind, pk in rep["per_kind"].items():
            slot = agg["per_kind"].setdefault(
                kind, {"injected": 0, "matched": 0})
            slot["injected"] += pk["injected"]
            slot["matched"] += pk["matched"]
    agg["recall"] = agg["matched"] / agg["injected"] \
        if agg["injected"] else 0.0
    return agg


def main() -> None:
    from repro.compile_cache import use_compile_cache
    use_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--duration", type=float, default=6.0)
    ap.add_argument("--warmup", type=float, default=2.0)
    ap.add_argument("--repeats", type=int, default=3,
                    help="runs per mode; best (lowest wall) is kept")
    ap.add_argument("--sample-every", type=int, default=64)
    ap.add_argument("--smoke", action="store_true",
                    help="reduced-scale CI config (half-size windows, "
                         "3s run) for the bench-smoke obs-overhead gate")
    ap.add_argument("--out", default="BENCH_obs.json")
    ap.add_argument("--trace-out", default="obs_trace.json",
                    help="Perfetto/Chrome trace of the timeline run")
    args = ap.parse_args()

    qcfg = SMOKE if args.smoke else FULL
    duration, warmup = (3.0, 1.5) if args.smoke else \
        (args.duration, args.warmup)

    result = {"config": {"smoke": args.smoke, "duration": duration,
                         "warmup": warmup, "query": dict(qcfg),
                         "repeats": args.repeats,
                         "sample_every": args.sample_every,
                         "parallelism": 2, "io_workers": 4}}
    # interleaved, disabled first in each round (see module docstring)
    best: dict = {}
    for i in range(max(1, args.repeats)):
        for mode in ("disabled", "traced", "timeline"):
            # heap garbage from the previous engine (event lists, spans,
            # ring buffers) must not bill its GC pauses to this mode
            gc.collect()
            r = run_one(mode, qcfg, duration, warmup, args.sample_every,
                        trace_out=args.trace_out
                        if mode == "timeline" else None)
            if mode not in best or r["wall_s"] < best[mode]["wall_s"]:
                best[mode] = r
            print(f"[bench/obs] {mode:9s} #{i + 1} "
                  f"wall={r['wall_s']:6.2f}s "
                  f"tput={r['tuples_per_s']:9.0f} tup/s "
                  f"p99={r['p99']*1e3:.2f}ms", file=sys.stderr)
    result.update(best)

    result["alerts"] = run_alert_oracle()

    dis = max(1e-12, result["disabled"]["tuples_per_s"])
    tput_ratio = result["traced"]["tuples_per_s"] / dis
    tl_ratio = result["timeline"]["tuples_per_s"] / dis
    result["headline"] = {
        "throughput_ratio_traced_vs_disabled": tput_ratio,
        "throughput_ratio_timeline_vs_disabled": tl_ratio,
        "alert_recall": result["alerts"]["recall"],
        "golden_alerts": result["alerts"]["golden_alerts"]}
    tr = result["traced"].get("trace", {})
    hq = result["traced"].get("hint_quality", {})
    print(f"[bench/obs] traced/disabled throughput x{tput_ratio:.3f} "
          f"timeline/disabled x{tl_ratio:.3f} "
          f"dominant={tr.get('dominant_stage')} "
          f"precision={hq.get('precision', 0.0):.2f} "
          f"recall={hq.get('recall', 0.0):.2f}", file=sys.stderr)
    print(f"[bench/obs] alert oracle: recall="
          f"{result['alerts']['recall']:.2f} "
          f"({result['alerts']['matched']}/{result['alerts']['injected']}) "
          f"golden alerts={result['alerts']['golden_alerts']} "
          f"trace events={result['timeline'].get('perfetto_events', 0)} "
          f"-> {args.trace_out}", file=sys.stderr)

    with open(args.out, "w") as f:
        json.dump(result, f, indent=2, sort_keys=True)
    print(json.dumps(result["headline"], indent=2))


if __name__ == "__main__":
    main()
