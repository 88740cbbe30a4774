"""One run of one benchmark cell, found by name in ``BENCHMARK.json``.

A cell names a configuration (``configs/<config>.json``: the deployment,
the plan that builds it through the program's entry points, and its
plain reference) and a traffic mix (``traffic/<cell>.json``: the
generator, rate, skew, stream offset, warm-up and the fixed span S).
Per-layer metrics are readers ``metrics/<metric>.py``.  Nothing here
names a cell, a configuration or a metric, so a cell that only adds
those files needs no edit of this one.

A run:

1. set-up: build the engine, install the benchmark's own generator on
   its source, compile every device program at the shapes the cell
   uses, and run ``warmup_s`` of stream;
2. the measured window: advance the simulated clock by ``step_s`` of
   stream at a time until ``seconds`` of wall time have passed;
3. outside the window: run on until the stream reaches warm-up + S,
   stop the sources and drain (the drain of ``chip_smoke.py``);
4. read the device's peak memory, free the program, run the plain
   reference over the operator's recorded input and compare.

The events counted in the window are the keyed records (bids, views)
that the stateful operator took from its input queues: work the
operator did, so input left queued in simulated time is not counted.
"""
from __future__ import annotations

import collections
import gc
import importlib.util
import json
import os
import shutil
import statistics
import sys
import time
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
for _p in (os.path.join(ROOT, "src"), BENCH, os.path.join(BENCH, "references")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

OUT_DIR = os.path.join(ROOT, ".bench_out")
PROGRAMS = ("fused_step", "fused_admit", "gather_rows", "drop_slots")
ADMIT_WIDTHS = (1, 8, 16, 32, 64)     # FusedPlane._flush_admits chunk widths
DROP_WIDTH = 32                       # FusedPlane.DROP_W


def log(*a) -> None:
    print(*a, file=sys.stderr, flush=True)


def load_module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


# ------------------------------------------------------------- the cell
@dataclass
class Cell:
    name: str
    workload: dict
    config: dict
    traffic: dict
    end_to_end: List[dict]
    per_layer: List[dict]
    bench: str = BENCH

    def module(self, kind: str, name: str):
        return load_module(os.path.join(self.bench, kind, f"{name}.py"),
                           f"bench_{kind}_{name}".replace("-", "_"))


def load_cell(workload: str, root: str = ROOT) -> Cell:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    wl = next((w for w in manifest["workloads"] if w["name"] == workload),
              None)
    if wl is None:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json")
    entry = next(c for c in manifest["configs"] if c["name"] == wl["config"])
    bench = os.path.join(root, os.path.dirname(os.path.dirname(
        entry["file"])))
    with open(os.path.join(root, entry["file"])) as f:
        config = json.load(f)
    with open(os.path.join(bench, "traffic", f"{wl['traffic']}.json")) as f:
        traffic = json.load(f)

    def here(m):
        return "workloads" not in m or workload in m["workloads"]
    e2e = [m for m in manifest["end_to_end"] if here(m)]
    names = {m["name"] for m in e2e}
    per_layer = [m for m in manifest["per_layer"]
                 if here(m) and m["moves"] in names]
    return Cell(workload, wl, config, traffic, e2e, per_layer, bench)


# ------------------------------------------------------ what is recorded
class Recorder:
    """The keyed operator's input as it arrives off the network, in
    order: ``("t", key, ts, subtask)`` per record and ``("w", ts,
    subtask, input)`` per watermark; and every result the sink receives,
    with its simulated emission time.

    Each watermark is also held, as it arrives, to the generator's own
    ``watermark_limit()``: the latest watermark the source may have
    issued over the records made so far.  ``wm_ahead`` counts those
    that passed it; ``wm_checked`` those held to it."""

    def __init__(self, eng, gen):
        from repro.streaming.events import Tuple_, Watermark
        from repro.streaming.windows import FIRE
        self.inputs: List[tuple] = []
        self.results: List[tuple] = []           # (emit t, ingest t, tup)
        self.arrived = 0
        self.wm_ahead = self.wm_checked = 0
        self.checking = True
        op = self.op = eng.operators["stateful"]
        sink = eng.operators["sink"]
        sim = eng.sim
        inputs, results, rec = self.inputs, self.results, self
        deliver, process = op.deliver_batch, sink.process
        self._tuple, self._fire = Tuple_, FIRE

        def deliver_batch(sub, batch, origin=None):
            if origin is not None:
                for m in batch:
                    if type(m) is Tuple_:
                        inputs.append(("t", m.key, m.ts, sub))
                        rec.arrived += 1
                    elif type(m) is Watermark:
                        inputs.append(("w", m.ts, sub, m.origin))
                        if rec.checking:
                            rec.wm_checked += 1
                            rec.wm_ahead += m.ts > gen.watermark_limit()
            return deliver(sub, batch, origin)

        def sink_process(sub, tup):
            results.append((sim.t, tup.ingest_t, tup))
            return process(sub, tup)
        op.deliver_batch = deliver_batch
        sink.process = sink_process

    def taken(self) -> int:
        """Keyed records the operator has taken from its input queues:
        those that arrived less those still queued (its own FIREs are
        not records)."""
        tup, fire = self._tuple, self._fire
        queued = sum(1 for q in self.op.queues for m in q
                     if type(m) is tup and m.payload is not fire)
        return self.arrived - queued


class CompileCounter:
    """Counts JAX traces and backend compiles (a listener on JAX's own
    monitoring events)."""

    def __init__(self):
        from jax._src import monitoring
        self._monitoring = monitoring
        self.counts = collections.Counter()
        monitoring.register_event_duration_secs_listener(self._on)

    def close(self) -> None:
        self._monitoring.unregister_event_duration_listener(self._on)

    def _on(self, event: str, secs: float, **kw) -> None:
        if event.endswith("jaxpr_trace_duration"):
            self.counts["traces"] += 1
        elif event.endswith("backend_compile_duration"):
            self.counts["compiles"] += 1


class Spans:
    """Host spans around the program's device calls, recorded from the
    benchmark's side: ``FusedPlane.batch_step`` and the ``tac_jax`` entry
    points a plane calls through ``plane._tj``.  Each span is a
    ``jax.profiler.TraceAnnotation`` named ``bench.<call>``, so it lands
    in the profiler's trace beside the device ops; the wall time inside
    outermost spans and the calls per program are counted here."""

    def __init__(self):
        import jax
        self._ann = jax.profiler.TraceAnnotation
        self._block = jax.block_until_ready
        self.depth = 0
        self.inside_s = 0.0
        self.calls = collections.Counter()

    def wrap(self, name: str, fn, block: bool = False):
        ann, spans = self._ann, self
        label = f"bench.{name}"

        def call(*a, **k):
            if name in PROGRAMS:
                spans.calls[name] += 1
            outer = spans.depth == 0
            t0 = time.perf_counter()
            spans.depth += 1
            try:
                with ann(label):
                    out = fn(*a, **k)
                    if block:
                        # the caller reads the result on the host at once
                        spans._block(out)
                    return out
            finally:
                spans.depth -= 1
                if outer:
                    spans.inside_s += time.perf_counter() - t0
        return call

    def install(self, planes) -> None:
        for p in planes:
            tj = p._tj
            proxy = type("TacJaxSpans", (), {})()
            for n in dir(tj):
                if not n.startswith("__"):
                    setattr(proxy, n, getattr(tj, n))
            proxy.fused_step = self.wrap("fused_step", tj.fused_step)
            proxy.fused_admit = self.wrap("fused_admit", tj.fused_admit)
            proxy.drop_slots = self.wrap("drop_slots", tj.drop_slots)
            proxy.gather_rows = self.wrap("gather_rows", tj.gather_rows,
                                          block=True)
            p._tj = proxy
            p.batch_step = self.wrap("batch_step", p.batch_step)


# ------------------------------------------------------------ set-up
def fused_planes(eng) -> list:
    from repro.streaming.engine import StatefulOp
    from repro.streaming.fused import FusedPlane
    return [c for op in eng.operators.values() if isinstance(op, StatefulOp)
            for c in op.caches if isinstance(c, FusedPlane)]


def warm_programs(planes) -> Dict[str, float]:
    """Compile (or load from the persistent cache) every device program
    a plane calls, at the shapes it calls it with: ``fused_step`` at
    (B, W), ``fused_admit`` at each admission chunk width,
    ``gather_rows`` at one row, ``drop_slots`` at its fixed width.  Runs
    each once on throwaway state; returns seconds per program."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    out, seen = {}, set()
    for p in planes:
        tj, B, W, V = p._tj, p.batch, p.n_slots, p.spec.width
        if (B, W, V, p.spec.kind) in seen:
            continue                  # subtasks share their programs
        seen.add((B, W, V, p.spec.kind))
        st = tj.init(1, W, 1)
        pages = jnp.zeros((W + 1, 1, V + 1), jnp.float32)
        i32, f32 = np.int32, np.float32
        calls = {
            f"fused_step[{p.spec.kind}]": lambda: tj.fused_step(
                st, pages, np.full(B, p.PAD_KEY, i32), np.zeros(B, f32),
                np.zeros((B, V), f32), np.zeros(B, bool), np.zeros(B, bool),
                kind=p.spec.kind),
            "gather_rows[1]": lambda: tj.gather_rows(
                pages, np.zeros(1, i32)),
            f"drop_slots[{DROP_WIDTH}]": lambda: tj.drop_slots(
                st, np.zeros(DROP_WIDTH, i32), np.zeros(DROP_WIDTH, bool)),
        }
        for w in ADMIT_WIDTHS:
            calls[f"fused_admit[{w}]"] = (lambda w=w: tj.fused_admit(
                st, pages, np.zeros(w, i32), np.zeros(w, i32),
                np.zeros(w, f32), np.zeros((w, V), f32), np.zeros(w, bool),
                np.zeros(w, bool)))
        for name, fn in calls.items():
            t0 = time.perf_counter()
            jax.block_until_ready(fn())
            out[name] = round(time.perf_counter() - t0, 4)
    return out


# ------------------------------------------------------------- the drain
def _quiet(eng) -> bool:
    """No operator holds queued, parked or in-flight work and no channel
    holds or carries a message (copied from ``chip_smoke.py``)."""
    from repro.streaming.engine import StatefulOp
    now = eng.sim.t
    for op in eng.operators.values():
        if any(op.busy) or any(op.queues) or any(op.ready):
            return False
        for ch in op.out_data + op.out_hint:
            if any(ch.bufs.values()) or \
                    max(ch.last_arrival.values(), default=now) > now:
                return False
        if isinstance(op, StatefulOp) and (
                any(any(w.values()) for w in op.waiting)
                or any(op.in_flight) or any(op.io_q)
                or any(op.wb_pending)):
            return False
    return True


def _settle(eng, t: float, step: float = 0.25, limit: float = 1e4) -> float:
    while True:
        t += step
        eng.sim.run_until(t)
        if _quiet(eng):
            return t
        if t > limit:
            raise RuntimeError(f"engine still busy at t={t}")


def drain(eng, plan) -> tuple:
    """Stop the sources, let in-flight data and I/O land, and take the
    final keyed state: dirty entries flushed through the fused plane to
    the store, resident entries read back from the device pool (copied
    from ``chip_smoke.py``).  Returns (state, simulated time, sources);
    ``fire_all`` then fires every open window."""
    from repro.streaming.engine import SourceOp
    op = eng.operators["stateful"]
    srcs = [o for o in eng.operators.values() if isinstance(o, SourceOp)]
    for src in srcs:
        src.stopped = True
    t = _settle(eng, eng.sim.t)
    raw = {}
    for sub in range(op.parallelism):
        for e in op.caches[sub].flush_dirty():
            op.backends[sub].write(e.key, e.state, op.state_size)
        raw.update(op.backends[sub].data)
        raw.update({k: e.state for k, e in op.caches[sub].entries.items()})
    # a prefetch materializes a never-written pane as None in the store;
    # whether it landed before the stop is timing, not state
    state = dict(plan.state_of(k, v) for k, v in raw.items()
                 if v is not None)
    return state, t, srcs


def fire_all(eng, srcs, t: float) -> None:
    op = eng.operators["stateful"]
    if getattr(op, "windows", None) is None:
        return
    final = t + 1e6                   # event times trail the sim clock
    for src in srcs:
        for s in range(src.parallelism):
            src.wm[s] = final
            src.emit_watermark(s, final)
    _settle(eng, t)


# -------------------------------------------------------------- compare
def compare(plan_results, plan_state, ref_results, ref_state,
            state_rule: str, keyed_log, inputs, rec) -> Dict[str, dict]:
    """Every number compared, each with its limit (all exact: 0)."""
    got = collections.Counter(plan_results)
    want = collections.Counter(ref_results)
    res_bad = sum((got - want).values()) + sum((want - got).values())
    if state_rule == "equal":
        keys = set(plan_state) | set(ref_state)
    else:                             # the program holds a subset
        keys = set(plan_state)
    st_bad = sum(1 for k in keys
                 if k not in ref_state or plan_state.get(k) != ref_state[k])
    sent = collections.Counter(keyed_log)
    seen = collections.Counter((m[1], m[2]) for m in inputs if m[0] == "t")
    lost = sum((sent - seen).values())
    extra = sum((seen - sent).values())
    return {
        "results_mismatch": {"value": res_bad, "limit": 0,
                             "of": len(ref_results)},
        "state_mismatch": {"value": st_bad, "limit": 0, "of": len(keys)},
        "input_mismatch": {"value": lost + extra, "limit": 0,
                           "of": len(keyed_log), "lost": lost},
        "watermark_ahead": {"value": rec.wm_ahead, "limit": 0,
                            "of": rec.wm_checked},
    }


def p99_ms(results, lo: float, hi: float) -> Optional[float]:
    """p99 of emission minus ingest time, on the simulated clock, over
    every result emitted in [lo, hi]."""
    lat = [e - i for e, i, _ in results if lo <= e <= hi]
    if len(lat) < 100:
        return None
    return statistics.quantiles(lat, n=100, method="inclusive")[98] * 1e3


# ------------------------------------------------------------ one run
@dataclass
class RunOut:
    line: Dict[str, Any]
    extra: Dict[str, Any] = field(default_factory=dict)


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool,
             t_start: float, sizes: Optional[dict] = None,
             control: Optional[str] = None) -> RunOut:
    """One run of ``cell``.  ``sizes`` overrides numbers of the
    configuration or traffic (the CPU rehearsals shrink a cell with it);
    ``control`` names a lower precision to run the plain reference in,
    beside the program (the control check)."""
    import jax
    cfg = json.loads(json.dumps(cell.config))
    trf = json.loads(json.dumps(cell.traffic))
    for k, v in (sizes or {}).items():
        if k in trf:
            trf[k] = v
        elif k in cfg:
            cfg[k] = v
        else:
            cfg["deployment"][k] = v
    plan = cell.module("plans", cfg["plan"])
    reference = cell.module("references", cfg["reference"])
    gen = cell.module("traffic", f"{trf['generator']}_gen").make(
        trf, cfg, seed)
    compiles = CompileCounter()

    eng = plan.build(cfg, trf, seed)
    eng.operators["source"].gen = gen
    rec = Recorder(eng, gen)
    planes = fused_planes(eng)
    warm = warm_programs(planes)
    warmup, span, step = trf["warmup_s"], trf["span_s"], trf["step_s"]
    eng.run(duration=warmup)
    spans = Spans() if trace else None
    if spans is not None:
        spans.install(planes)
    events = rec.taken
    before = dict(compiles.counts)
    setup_s = time.perf_counter() - t_start

    # ---- the measured window
    trace_dir = os.path.join(OUT_DIR, "trace")
    trace_s = min(4.0, 0.25 * seconds) if trace else 0.0
    marks = {}
    t_sim = eng.sim.t
    ev0, made0 = events(), gen.n
    w0 = time.perf_counter()
    if trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0      # Python frames: off, too costly
        opts.host_tracer_level = 2        # TraceMe spans, ours included
        opts.enable_hlo_proto = False
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
        window_ann = jax.profiler.TraceAnnotation("bench.window")
        window_ann.__enter__()
    tracing = trace
    while True:
        t_sim += step
        eng.sim.run_until(t_sim)
        now = time.perf_counter()
        if tracing and now - w0 >= trace_s:
            window_ann.__exit__(None, None, None)
            jax.profiler.stop_trace()
            tracing = False
            now = time.perf_counter()
            marks.update(t=now, events=events(), inside=spans.inside_s)
        if now - w0 >= seconds:
            break
    window_s = now - w0
    if spans is not None:
        marks["end"] = {"t": now, "events": events(),
                        "inside": spans.inside_s}
        calls = dict(spans.calls)
    n_events = events() - ev0
    made = gen.n - made0
    queued = rec.arrived - rec.taken()
    t_window_end = eng.sim.t
    window_traces = {k: v - before.get(k, 0)
                     for k, v in compiles.counts.items()}
    compiles.close()

    # ---- outside the window: finish the span S, drain, compare
    while eng.sim.t < warmup + span:
        eng.sim.run_until(min(eng.sim.t + 0.25, warmup + span))
    state, t, srcs = drain(eng, plan)
    cut = len(rec.inputs)
    rec.checking = False              # fire_all's watermark is our own
    fire_all(eng, srcs, t)
    t_done = time.perf_counter()
    dev = jax.devices()[0]
    stats = dev.memory_stats() or {}
    peak = int(stats.get("peak_bytes_in_use", 0))
    results = [plan.result_of(tup) for _, _, tup in rec.results]
    sim_p99 = p99_ms(rec.results, warmup, warmup + span)
    counters = plane_counters(planes)
    del eng, planes, spans
    rec.results.clear()
    gc.collect()

    ref_results, ref_state, rule = _reference(reference, rec.inputs, cut,
                                              cfg, "float32")
    check = compare(results, state, ref_results, ref_state, rule,
                    gen.keyed_log, rec.inputs, rec)
    correct = all(c["value"] <= c["limit"] for c in check.values())
    extra = {"sim_stop_t": t, "window_end_sim_t": t_window_end,
             "after_window_s": round(t_done - w0 - window_s, 3),
             "window_events": n_events, "window_source_events": made,
             "queued_at_window_end": queued,
             "window_traces": window_traces, "warm_programs": warm,
             "counters": counters, "results": len(results),
             "state_keys": len(state), "inputs": len(rec.inputs)}
    if control is not None:
        c_results, c_state, _ = _reference(reference, rec.inputs, cut, cfg,
                                           control)
        extra["control"] = compare(c_results, c_state, ref_results,
                                   ref_state, rule, gen.keyed_log,
                                   rec.inputs, rec)
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": peak}
    line = {"correct": bool(correct), "attempted": int(gen.n),
            "failed": int(check["input_mismatch"]["lost"]),
            "metrics": {}, "device": device}
    if not trace:
        values = {"events_per_s": n_events / window_s,
                  "sim_p99_ms": sim_p99, "setup_s": setup_s}
        for m in cell.end_to_end:
            v = values.get(m["name"])
            if v is not None:
                line["metrics"][m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        run = _traced_run(cell, trace_dir, marks, window_s, n_events,
                          calls)
        device.update(busy_s=run.get("busy_s", 0.0),
                      window_s=run.get("window_s", 0.0))
        for m in cell.per_layer:
            reader = cell.module("metrics", m["name"])
            v = reader.read(run)
            if v is not None:
                line["metrics"][m["name"]] = {"value": v, "unit": m["unit"]}
        if run.get("breakdown"):
            line["breakdown"] = run["breakdown"]
        shutil.rmtree(trace_dir, ignore_errors=True)
    line["check"] = {k: {"value": c["value"], "limit": c["limit"]}
                     for k, c in check.items()}
    return RunOut(line, extra)


def _reference(reference, inputs, cut, cfg, dtype):
    results, state = reference.run(inputs, cut, cfg, dtype)
    rule = getattr(reference, "STATE_RULE", "equal")
    return results, state, rule


def plane_counters(planes) -> Dict[str, int]:
    keys = ("batches", "lanes", "device_hits", "device_misses", "evictions",
            "prefetch_insertions", "hits", "misses")
    return {k: sum(int(getattr(p, k)) for p in planes) for k in keys}


def _traced_run(cell, trace_dir, marks, window_s, n_events,
                calls) -> Dict[str, Any]:
    """What the per-layer readers read: the reduced trace (with the peaks
    of the chip it ran on), and the host spans and device calls of the
    window."""
    from metrics.lib import xplane
    run: Dict[str, Any] = {"calls": calls,
                           "window_s": window_s, "events": n_events,
                           "host": None, "trace": None}
    # the host split is taken over the part of the window after the
    # profiler stopped, which it did not slow down
    rest = marks.get("end", {})
    if "t" in marks and rest.get("events", 0) > marks["events"]:
        run["host"] = {"wall_s": rest["t"] - marks["t"],
                       "events": rest["events"] - marks["events"],
                       "inside_s": rest["inside"] - marks["inside"]}
    summary = xplane.summarize(trace_dir)
    if summary is not None and summary.device_planes:
        import jax
        with open(os.path.join(cell.bench, "peaks.json")) as f:
            peaks = json.load(f)["devices"]
        kind = jax.devices()[0].device_kind
        if kind not in peaks:
            raise KeyError(f"no peaks for device kind {kind!r} in "
                           "peaks.json")
        run["peak"] = peaks[kind]
        run["trace"] = summary
        run["busy_s"] = summary.busy_s
        run["window_s"] = summary.window_s
        run["breakdown"] = summary.breakdown()
    return run
