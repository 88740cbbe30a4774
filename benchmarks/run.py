# One function per paper table. Print ``name,us_per_call,derived`` CSV.
import argparse
import json
import os
import sys
import time


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma list: fig6,fig7,fig8,fig9,fig10,fig11,"
                         "tab1,tab2,claims")
    ap.add_argument("--out", default="results/bench")
    ap.add_argument("--fail-at", type=float, default=None,
                    help="run the failure/recovery scenario instead of the "
                         "paper figures: inject a failure this many seconds "
                         "after warmup on q5 and q20 (DESIGN.md §7)")
    ap.add_argument("--recover", default="warmed,cold",
                    help="comma list of recovery modes to run with "
                         "--fail-at (warmed|cold)")
    ap.add_argument("--fused", action="store_true",
                    help="run stateful hot paths on the fused device "
                         "plane where a FusedSpec exists (ysb; q5/q7 "
                         "overrides) — other workloads stay interpreted "
                         "(DESIGN.md §14)")
    args = ap.parse_args()
    only = set(args.only.split(",")) if args.only else None

    root = os.path.join(os.path.dirname(__file__), "..")
    sys.path.insert(0, os.path.join(root, "src"))
    sys.path.insert(0, root)              # `benchmarks` package itself
    from repro.compile_cache import use_compile_cache
    use_compile_cache()
    from benchmarks import paper
    paper.FUSED = args.fused

    if args.fail_at is not None:
        from benchmarks import recovery as rbench
        modes = args.recover.split(",")
        bad = [m for m in modes if m not in ("warmed", "cold")]
        if bad:
            ap.error(f"--recover modes must be warmed|cold, got {bad}")
        os.makedirs(args.out, exist_ok=True)
        rows = ["name,us_per_call,derived"]
        for query in ("q5", "q20"):
            qcfg = dict(rbench.FULL[query], fail_at=args.fail_at)
            for mode in modes:
                r = rbench.run_one(query, mode, qcfg)
                spike = r.get("post_restore_p99") or 0.0
                rows.append(
                    f"recovery_{query}_{mode},{spike*1e6:.1f},"
                    f"steady_p99_us={(r['steady_p99'] or 0)*1e6:.1f};"
                    f"recovery_s={r.get('recovery_time', 0):.3f};"
                    f"warmup_hints={r.get('warmup_hints', 0)}")
                print(rows[-1], file=sys.stderr)
        csv = "\n".join(rows)
        print(csv)
        with open(os.path.join(args.out, "recovery.csv"), "w") as f:
            f.write(csv + "\n")
        return

    os.makedirs(args.out, exist_ok=True)
    rows = ["name,us_per_call,derived"]

    def want(x):
        return only is None or x in only

    fig6_out = {}
    t0 = time.time()
    if want("fig6") or want("tab1") or want("tab2") or want("claims"):
        fig6_out = paper.fig6(rows)
        print(f"[bench] fig6 done ({time.time()-t0:.0f}s)", file=sys.stderr)
    if want("fig7"):
        paper.fig7(rows)
        print(f"[bench] fig7 done ({time.time()-t0:.0f}s)", file=sys.stderr)
    if want("fig8"):
        paper.fig8(rows)
        print(f"[bench] fig8 done ({time.time()-t0:.0f}s)", file=sys.stderr)
    if want("fig9"):
        paper.fig9(rows)
        print(f"[bench] fig9 done ({time.time()-t0:.0f}s)", file=sys.stderr)
    if want("fig10"):
        paper.fig10(rows)
        print(f"[bench] fig10 done ({time.time()-t0:.0f}s)", file=sys.stderr)
    if want("fig11"):
        paper.fig11(rows)
        print(f"[bench] fig11 done ({time.time()-t0:.0f}s)", file=sys.stderr)
    if fig6_out and want("tab1"):
        paper.tab1(rows, fig6_out)
    if fig6_out and want("tab2"):
        paper.tab2(rows, fig6_out)
    if fig6_out and want("claims"):
        paper.validate_claims(rows, fig6_out)

    csv = "\n".join(rows)
    print(csv)
    with open(os.path.join(args.out, "bench.csv"), "w") as f:
        f.write(csv + "\n")


if __name__ == "__main__":
    main()
