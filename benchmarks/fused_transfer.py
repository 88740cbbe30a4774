"""Per-call cost of the fused plane's host<->device interface (DESIGN.md
§14): each device call with its per-lane arrays handed over one by one
(five into ``fused_step`` and five read back, six into ``fused_admit``,
two into ``drop_slots``) against the entry points as the plane calls
them, which pack the lanes into one int32 slab each way.  The device
compute is the same in both; only what crosses the bus differs.

Each variant runs ``--calls`` times in a row, threading its state as the
plane does, and blocks on what the plane reads (the step's lane
results; the directory after an admit or a drop).  Rounds alternate the
two variants; the median of ``--rounds`` is reported, split into
dispatch (the call returns) and readback (the host holds the results).

The ``fused_step_deferred`` variant times what a deferred readback
leaves: each step is dispatched, its output slab's host copy started
(``copy_to_host_async``, "async") or not ("sync"), the host then spins
for 0.25, 0.5, 1 or 2 ms as other work would, and the wait of the read
that follows is reported per call.

    python benchmarks/fused_transfer.py        # on a host with a TPU

Writes ``chiprun_out/fused_transfer.json``; exits 1 without a TPU.
"""
from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.core import tac_jax  # noqa: E402

CELLS = [("sum", 256, 4096), ("read", 256, 65536)]
BUSY_MS = (0.25, 0.5, 1.0, 2.0)


def _per_array_programs(kind: str):
    """The three programs with one argument per lane array (the
    interface before the slabs), around the same device compute."""
    def step(state, pages, keys, ts, weights, fire, valid):
        return tac_jax._fused_step(state, pages, keys, ts, weights, fire,
                                   valid, kind)
    return (jax.jit(step), jax.jit(tac_jax._fused_admit),
            jax.jit(tac_jax._drop_slots))


def _inputs(rng, B: int, W: int, V: int = 1):
    step = (rng.randint(0, 2 * W, B).astype(np.int32),
            rng.rand(B).astype(np.float32), np.ones((B, V), np.float32),
            rng.rand(B) < 0.1, np.ones(B, bool))
    n = 64
    admit = (rng.choice(W, n, replace=False).astype(np.int32),
             rng.randint(0, 2 * W, n).astype(np.int32),
             rng.rand(n).astype(np.float32), np.ones((n, V), np.float32),
             np.ones(n, bool), np.zeros(n, bool))
    drop = (rng.choice(W, 32, replace=False).astype(np.int32),
            np.ones(32, bool))
    return step, admit, drop


def measure(kind: str, B: int, W: int, calls: int, rounds: int,
            seed: int = 0) -> dict:
    """Median microseconds per call, per program and variant."""
    rng = np.random.RandomState(seed)
    step_in, admit_in, drop_in = _inputs(rng, B, W)
    p_step, p_admit, p_drop = _per_array_programs(kind)
    state = tac_jax.init(1, W, 1)
    pages = jnp.zeros((W + 1, 1, 2), jnp.float32)

    def run_step(per_array):
        nonlocal state, pages
        t0 = time.perf_counter()
        disp = 0.0
        for _ in range(calls):
            ta = time.perf_counter()
            if per_array:
                state, pages, *lanes = p_step(state, pages, *step_in)
                disp += time.perf_counter() - ta
                [np.asarray(x) for x in lanes]
            else:
                out = tac_jax.fused_step(state, pages, *step_in, kind=kind)
                state, pages = out.state, out.pages
                disp += time.perf_counter() - ta
                out.read()
        total = time.perf_counter() - t0
        return {"call_us": total / calls * 1e6,
                "dispatch_us": disp / calls * 1e6,
                "readback_us": (total - disp) / calls * 1e6}

    def run_admit(per_array):
        nonlocal state, pages
        fn = p_admit if per_array else tac_jax.fused_admit
        t0 = time.perf_counter()
        for _ in range(calls):
            state, pages, _ = fn(state, pages, *admit_in)
        jax.block_until_ready(state)
        return {"call_us": (time.perf_counter() - t0) / calls * 1e6}

    def run_drop(per_array):
        nonlocal state
        fn = p_drop if per_array else tac_jax.drop_slots
        t0 = time.perf_counter()
        for _ in range(calls):
            state = fn(state, *drop_in)
        jax.block_until_ready(state)
        return {"call_us": (time.perf_counter() - t0) / calls * 1e6}

    def run_deferred(busy_s, copy):
        nonlocal state, pages
        wait = 0.0
        for _ in range(calls):
            out = tac_jax.fused_step(state, pages, *step_in, kind=kind)
            state, pages = out.state, out.pages
            if copy:
                out.out.copy_to_host_async()
            until = time.perf_counter() + busy_s
            while time.perf_counter() < until:
                pass
            ta = time.perf_counter()
            out.read()
            wait += time.perf_counter() - ta
        return {"wait_us": wait / calls * 1e6}

    runs = {"fused_step": run_step, "fused_admit": run_admit,
            "drop_slots": run_drop}
    res = {}
    for name, run in runs.items():
        run(True), run(False)                       # compile, warm
        got = {"per_array": [], "slab": []}
        for _ in range(rounds):
            got["per_array"].append(run(True))
            got["slab"].append(run(False))
        res[name] = {v: {k: round(statistics.median(r[k] for r in rs), 2)
                         for k in rs[0]} for v, rs in got.items()}
        res[name]["saved_us"] = round(res[name]["per_array"]["call_us"]
                                      - res[name]["slab"]["call_us"], 2)
    deferred = res["fused_step_deferred"] = {}
    for ms in BUSY_MS:
        got = {"async": [], "sync": []}
        for _ in range(rounds):
            for v, waits in got.items():
                waits.append(run_deferred(ms / 1e3, v == "async")["wait_us"])
        deferred[f"busy_{ms}ms"] = {
            f"{v}_wait_us": round(statistics.median(w), 2)
            for v, w in got.items()}
    return res


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--calls", type=int, default=200)
    ap.add_argument("--rounds", type=int, default=5)
    args = ap.parse_args()
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"no TPU: {dev.platform}", file=sys.stderr)
        return 1
    out = {"device": {"platform": dev.platform, "kind": dev.device_kind},
           "calls": args.calls, "rounds": args.rounds, "cells": {}}
    for kind, B, W in CELLS:
        key = f"{kind}.B{B}.W{W}"
        out["cells"][key] = measure(kind, B, W, args.calls, args.rounds)
        print(key, json.dumps(out["cells"][key]), flush=True)
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/fused_transfer.json", "w") as f:
        json.dump(out, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
