#!/usr/bin/env python3
"""Run one benchmark cell once on the chip this process finds.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> \\
        --trace <0|1>

Exits 1, printing no result, unless JAX's first device is a TPU and
there are as many as the cell asks for.  Otherwise the last line of
standard output is one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics`` (the cell's end-to-end metrics with
``--trace 0``, its per-layer metrics with ``--trace 1``), ``device``,
with ``--trace 1`` ``breakdown``, and last ``check``: each number the
correctness comparison made, beside its limit.  The same numbers end
standard error.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
# the TPU runtime would otherwise log under /tmp, outside the checkout
os.environ.setdefault("TPU_LOG_DIR", "disabled")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    import harness
    cell = harness.load_cell(args.workload)
    import jax
    devs = jax.devices()
    need = int(cell.workload["chips"])
    if devs[0].platform != "tpu" or len(devs) < need:
        print(f"bench: needs {need} TPU chip(s), found {len(devs)} "
              f"{devs[0].platform} device(s)", file=sys.stderr)
        return 1
    from repro.compile_cache import use_compile_cache
    cache = use_compile_cache()
    # every program the cell runs is small: keep all of them, so a second
    # run in this checkout compiles nothing
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    harness.log(f"bench: {args.workload} seed={args.seed} on "
                f"{devs[0].device_kind} x{len(devs)}; compile cache {cache}")
    out = harness.run_cell(cell, args.seed, args.seconds, bool(args.trace),
                           T_START)
    harness.log("bench: " + json.dumps(out.extra, sort_keys=True,
                                       default=str))
    for name, c in out.line["check"].items():
        harness.log(f"check {name} = {c['value']} (limit {c['limit']})")
    print(json.dumps(out.line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
