#!/usr/bin/env python3
"""Bring-up check of the fused keyed-state hot path on one TPU.

    python3 chip_smoke.py [--seed N]

One process, one chip, three phases:

  (a) device: the first JAX device must be a TPU (there is no CPU
      fallback).  The fused programs are compiled ahead of time at the
      run's batch B and directory width W; the compile seconds of each
      are printed, and the compiled ``fused_step`` must hold the Pallas
      kernels (``tpu_custom_call``).
  (b) NEXMark q5 (sliding 2 s / 1 s bid count per auction) at 50,000
      events/s with a 60 s active-auction window, through
      ``build_query(..., fused=True)``, with W device slots — fewer than
      the run's peak live panes, so evictions and prefetch staging both
      occur.
  (c) YSB enrichment join at 50,000 events/s over 100,000 Zipf(1) ads
      through ``build_ysb(..., fused=True)``, with a pool smaller than
      the ad key space, so backend fetches stay on the path.

(b) and (c) each run again on the interpreted plane (``fused=False``)
with the same seed, and the emitted window/join results and the final
keyed state must agree exactly.  The process exits 0 only if every phase
passed; its last stdout line is then the JSON object
``{"ok": true, "device": {"platform", "kind", "count"}}``.  The phase
functions take their sizes as arguments, so the tests call them on the
CPU at a tiny size (tests/test_chip_smoke.py).
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro.core import tac_jax  # noqa: E402
from repro.streaming.fused import FusedPlane  # noqa: E402

BATCH = 256                   # fused device batch width B
# W, the device slots of each plane.  q5 at 50,000 events/s peaks near
# 42,000 live panes (2 s / 1 s windows, 0.3 s out-of-orderness, 1 s
# lateness), so 2^16 slots would hold all of it and never evict: 2^15
# is the widest power of two that keeps evictions on the path
Q5_SLOTS = 2 ** 15
YSB_SLOTS = 2 ** 16           # fewer slots than the 100,000 ads
RATE = 50_000.0               # events/s, NEXMark's and YSB's default
DURATION, WARMUP = 6.0, 2.0   # simulated seconds: a few fired windows


def log(*a) -> None:
    print(*a, flush=True)


# ------------------------------------------------------------ phase (a)
def compile_fused(n_slots: int, batch: int = BATCH):
    """Compile every fused program at (B, W) for the default device.
    Returns ({program: (compile seconds, kernel launches in its HLO)},
    the compiled ``fused_step[sum]`` HLO text)."""
    S = jax.ShapeDtypeStruct
    st = jax.eval_shape(lambda: tac_jax.init(1, n_slots, 1))
    pages = S((n_slots + 1, 1, 2), jnp.float32)
    i32, f32, b1 = jnp.int32, jnp.float32, jnp.bool_
    lane = lambda dt, *extra: S((batch, *extra), dt)  # noqa: E731
    programs = {
        "fused_step[sum]": lambda: tac_jax.fused_step.lower(
            st, pages, lane(i32), lane(f32), lane(f32, 1), lane(b1),
            lane(b1), kind="sum"),
        "fused_step[read]": lambda: tac_jax.fused_step.lower(
            st, pages, lane(i32), lane(f32), lane(f32, 1), lane(b1),
            lane(b1), kind="read"),
        "fused_admit[64]": lambda: tac_jax.fused_admit.lower(
            st, pages, S((64,), i32), S((64,), i32), S((64,), f32),
            S((64, 1), f32), S((64,), b1), S((64,), b1)),
        "gather_rows[1]": lambda: tac_jax.gather_rows.lower(
            pages, S((1,), i32)),
        "drop_slots[32]": lambda: tac_jax.drop_slots.lower(
            st, S((32,), i32), S((32,), b1)),
    }
    out, hlo = {}, ""
    for name, lower in programs.items():
        t0 = time.perf_counter()
        text = lower().compile().as_text()
        out[name] = (time.perf_counter() - t0,
                     text.count("tpu_custom_call"))
        hlo = hlo or text             # fused_step[sum] compiles first
    return out, hlo


def phase_device(n_slots: int, batch: int = BATCH) -> None:
    log(f"== (a) device: fused programs at B={batch} W={n_slots}")
    res, hlo = compile_fused(n_slots, batch)
    for name, (secs, n_kern) in res.items():
        log(f"compile {name:18s} {secs:.3f} s  tpu_custom_call x{n_kern}")
    for line in hlo.splitlines():
        if "tpu_custom_call" in line:
            log("  " + line.strip()[:160])
    if res["fused_step[sum]"][1] < 3:
        raise SystemExit("compiled fused_step lacks its Pallas kernels "
                         "(probe, gather, scatter)")


# ---------------------------------------------------------- (b) and (c)
def _quiet(eng) -> bool:
    """No operator holds queued, parked or in-flight work and no channel
    holds or carries a message."""
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


def _settle(eng, t: float, step: float = 0.25,
            limit: float = 600.0) -> float:
    """Advance the simulated clock from ``t`` until the engine is quiet;
    returns the clock."""
    while True:
        t += step
        eng.sim.run_until(t)
        if _quiet(eng):
            return t
        if t > limit:
            raise RuntimeError(f"engine still busy at t={t}")


def _drive(eng, duration: float, warmup: float) -> dict:
    """Run the engine, then drain it deterministically and collect what
    the fused and interpreted planes must agree on: every emitted result,
    and the final keyed state (dirty entries flushed to the backend).

    Drain: stop the sources (their record sequence up to the stop time
    is a function of the seed), let in-flight data and I/O land, take
    the state, then fire every open window with one final watermark."""
    from repro.streaming.engine import SourceOp
    op = eng.operators["stateful"]
    sink = eng.operators["sink"]
    emits = []
    inner = sink.process
    sink.process = lambda sub, tup: (
        emits.append((tup.ts, repr(tup.key), repr(tup.payload))),
        inner(sub, tup))[1]
    peak = [0]
    windows = getattr(op, "windows", None)
    if windows is not None:
        def sample():
            live = sum(len(m["keys"]) for w in windows for m in w.values())
            peak[0] = max(peak[0], live)
            eng.sim.after(0.05, sample)
        eng.sim.after(0.05, sample)
    t0 = time.perf_counter()
    m = eng.run(duration=duration, warmup=warmup)
    srcs = [o for o in eng.operators.values() if isinstance(o, SourceOp)]
    for src in srcs:
        src.stopped = True
    t = _settle(eng, warmup + duration)
    state = {}
    for sub in range(op.parallelism):
        for e in op.caches[sub].flush_dirty():
            op.backends[sub].write(e.key, e.state, op.state_size)
        state.update(op.backends[sub].data)
    # a prefetch materializes a never-written pane as None in the
    # backend; whether it landed before the run stopped is timing
    state = {repr(k): v for k, v in state.items() if v is not None}
    if windows is not None:
        final = t + 1e6               # event times trail the sim clock
        for src in srcs:
            for s in range(src.parallelism):
                src.wm[s] = final
                src.emit_watermark(s, final)
        _settle(eng, t)
    wall = time.perf_counter() - t0
    caches = op.caches
    out = {"emits": sorted(emits), "state": state, "wall_s": wall,
           "live_panes_peak": peak[0],
           "evictions": sum(c.evictions for c in caches),
           "prefetch_staged": sum(c.prefetch_insertions for c in caches),
           "hits": sum(c.hits for c in caches),
           "misses": sum(c.misses for c in caches),
           "backend_reads": sum(b.reads for b in op.backends),
           "p99_sim_s": m["p99"]}
    planes = [c for c in caches if isinstance(c, FusedPlane)]
    if planes:
        dev = jax.devices()[0]
        for p in planes:
            if p.pages.devices() != {dev} or p.tac.keys.devices() != {dev}:
                raise AssertionError(
                    f"fused plane state is not on {dev}: pool on "
                    f"{p.pages.devices()}, directory on "
                    f"{p.tac.keys.devices()}")
        out.update(
            slots=sum(p.n_slots for p in planes),
            pool_bytes=sum(p.pages.nbytes for p in planes),
            directory_bytes=sum(sum(a.nbytes for a in p.tac)
                                for p in planes),
            batches=sum(p.batches for p in planes),
            lanes=sum(p.lanes for p in planes),
            fill=sum(p.lanes for p in planes)
            / max(1, sum(p.batches * p.batch for p in planes)),
            device_hits=sum(p.device_hits for p in planes),
            device_misses=sum(p.device_misses for p in planes),
            device=f"{dev.platform}:{dev.device_kind}")
    return out


def run_q5(fused: bool, seed: int, n_slots: int, rate: float = RATE,
           duration: float = DURATION, warmup: float = WARMUP,
           batch: int = BATCH) -> dict:
    """NEXMark q5 on one stateful subtask (the chip holds the whole
    keyed state) at NEXMark's 60 s active-auction window."""
    from repro.streaming.nexmark import NexmarkConfig, build_query
    cfg = NexmarkConfig(rate=rate, active_window=60.0, oo_bound=0.3,
                        seed=seed)
    eng = build_query("q5", "tac", "prefetch", cfg, fused=fused,
                      fused_batch=batch, cache_entries=n_slots,
                      parallelism=1, source_parallelism=1)
    return _drive(eng, duration, warmup)


def run_ysb(fused: bool, seed: int, n_slots: int, rate: float = RATE,
            n_ads: int = 100_000, duration: float = DURATION,
            warmup: float = WARMUP, batch: int = BATCH) -> dict:
    """YSB's ad -> campaign enrichment join on one stateful subtask."""
    from repro.streaming.ysb import YSBConfig, build_ysb
    cfg = YSBConfig(rate=rate, n_ads=n_ads, seed=seed)
    eng = build_ysb("tac", "prefetch", cfg, fused=fused, fused_batch=batch,
                    cache_entries=n_slots, parallelism=1,
                    source_parallelism=1)
    return _drive(eng, duration, warmup)


def parity(name: str, runner, seed: int, **size) -> dict:
    """Run ``runner`` fused and interpreted with one seed; print the
    fused run's sizes and counters; raise unless results and final keyed
    state agree exactly.  Returns the fused run."""
    fused = runner(True, seed, **size)
    ref = runner(False, seed, **size)
    shown = {k: v for k, v in fused.items() if k not in ("emits", "state")}
    log(f"{name} fused: " + json.dumps(shown, sort_keys=True))
    log(f"{name} interpreted: wall_s={ref['wall_s']:.3f} "
        f"evictions={ref['evictions']} "
        f"prefetch_staged={ref['prefetch_staged']}")
    log(f"{name} results: {len(fused['emits'])} emitted, "
        f"{len(fused['state'])} keys of final state")
    if fused["emits"] != ref["emits"]:
        only_f = sorted(set(fused["emits"]) - set(ref["emits"]))[:5]
        only_r = sorted(set(ref["emits"]) - set(fused["emits"]))[:5]
        raise AssertionError(f"{name}: emitted results differ "
                             f"(fused only {only_f}, interpreted only "
                             f"{only_r})")
    if fused["state"] != ref["state"]:
        diff = [k for k in set(fused["state"]) | set(ref["state"])
                if fused["state"].get(k) != ref["state"].get(k)][:5]
        raise AssertionError(f"{name}: final keyed state differs at {diff}")
    log(f"{name} parity: exact (results and final keyed state)")
    return fused


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=7)
    args = ap.parse_args(argv)
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, found {dev.platform}",
              file=sys.stderr)
        return 1
    from repro.compile_cache import use_compile_cache
    log(f"device: {dev.platform} {dev.device_kind} x{len(jax.devices())}; "
        f"compile cache: {use_compile_cache()}")
    phase_device(Q5_SLOTS)
    log(f"== (b) NEXMark q5: rate={RATE:.0f}/s active_window=60s "
        f"W={Q5_SLOTS} B={BATCH}")
    q5 = parity("q5", run_q5, args.seed, n_slots=Q5_SLOTS)
    if not (q5["evictions"] > 0 and q5["prefetch_staged"] > 0):
        raise AssertionError("q5 ran without evictions or prefetch staging")
    log(f"== (c) YSB: rate={RATE:.0f}/s n_ads=100000 W={YSB_SLOTS} "
        f"B={BATCH}")
    ysb = parity("ysb", run_ysb, args.seed, n_slots=YSB_SLOTS)
    if ysb["backend_reads"] == 0:
        raise AssertionError("ysb ran without backend fetches")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
