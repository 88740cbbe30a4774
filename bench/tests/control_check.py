#!/usr/bin/env python3
"""The control of ``correct``, on the chip at a cell's own size.

    python3 bench/tests/control_check.py --workload <cell> \\
        --seeds <n> [<n> ...] [--seconds 5] [--pool bfloat16]

For each seed, one run of the cell (a short window at the cell's own
load, then the whole span S and the drain) reads the comparison twice:
the program against the plain reference in the configuration's
precision (float32), which is the sound reading, and the same reference
computed in bfloat16 put in the program's place, which is the control
and must come out not correct.  With ``--pool bfloat16`` one more run
per seed casts the fused plane's state pool to that dtype before the
first batch, reaching the program's own pool dtype from the benchmark's
side.  Prints one JSON line per run.  Run it from the checkout's root
on a host with the cell's chips; ``test_cells.py`` runs the same at a
tiny size on the CPU.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

import harness  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--pool", default=None)
    args = ap.parse_args(argv)
    import jax
    import jax.numpy as jnp
    from repro.compile_cache import use_compile_cache
    use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    cell = harness.load_cell(args.workload)
    dev = jax.devices()[0]
    for seed in args.seeds:
        t0 = time.perf_counter()
        out = harness.run_cell(cell, seed, args.seconds, False, t0,
                               control="bfloat16")
        print(json.dumps({
            "workload": args.workload, "seed": seed, "device": dev.device_kind,
            "program": out.line["check"], "correct": out.line["correct"],
            "control_bfloat16_reference": {
                k: v["value"] for k, v in out.extra["control"].items()},
            "compared": {k: v["of"] for k, v in out.extra["control"].items()},
            "seconds": round(time.perf_counter() - t0, 1)}), flush=True)
    if args.pool:
        real = harness.fused_planes

        def cast(eng):
            planes = real(eng)
            for p in planes:
                p.pages = p.pages.astype(getattr(jnp, args.pool))
            return planes
        harness.fused_planes = cast
        for seed in args.seeds:
            t0 = time.perf_counter()
            try:
                out = harness.run_cell(cell, seed, args.seconds, False, t0)
                rec = {"program": out.line["check"],
                       "correct": out.line["correct"]}
            except Exception as e:             # a control that crashes
                rec = {"error": f"{type(e).__name__}: {e}"[:400]}
            print(json.dumps({"workload": args.workload, "seed": seed,
                              f"pool_{args.pool}": rec,
                              "seconds": round(time.perf_counter() - t0, 1)}),
                  flush=True)
        harness.fused_planes = real
    return 0


if __name__ == "__main__":
    sys.exit(main())
