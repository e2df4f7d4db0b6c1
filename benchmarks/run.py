"""Benchmark suite entry point — one section per paper table/figure.

``PYTHONPATH=src python -m benchmarks.run [--only storage,query,...]``
prints ``name,us_per_call,derived`` CSV rows.
"""

from __future__ import annotations

import argparse
import sys
import traceback


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default="all",
                    help="comma list: storage,query,traversal,hybrid,"
                         "analytics,learning,exp5,exp6,readwrite,"
                         "exp7,serving,exp8,macro,exp9,tail,exp10,incr,"
                         "exp11,durability,kernels")
    ap.add_argument("--smoke", action="store_true",
                    help="smoke mode for sections that support it "
                         "(exp8/exp9/exp10: equality gate only, small "
                         "store)")
    args = ap.parse_args()
    wanted = set(args.only.split(",")) if args.only != "all" else {
        "storage", "query", "hybrid", "analytics", "learning",
        "readwrite", "serving", "macro", "tail", "incr", "durability",
        "kernels"}

    from repro.compile_cache import configure_compile_cache
    configure_compile_cache()

    from benchmarks.common import emit_header
    emit_header()

    sections = []
    if "storage" in wanted:
        from benchmarks import storage_bench
        sections.append(("storage", storage_bench.run))
    if "query" in wanted:
        from benchmarks import query_bench
        sections.append(("query", query_bench.run))
    elif "traversal" in wanted:      # exp4 standalone (query runs it too)
        from benchmarks import query_bench
        sections.append(("traversal", query_bench.run_traversal))
    if "hybrid" in wanted:
        from benchmarks import hybrid_bench
        sections.append(("hybrid", hybrid_bench.run))
    if "analytics" in wanted:
        from benchmarks import analytics_bench
        sections.append(("analytics", analytics_bench.run))
    if "learning" in wanted:
        from benchmarks import learning_bench
        sections.append(("learning", learning_bench.run))
    elif "exp5" in wanted:           # exp5 standalone (learning runs it too)
        from benchmarks import learning_bench
        sections.append(("exp5", learning_bench.run_exp5))
    if wanted & {"readwrite", "exp6"}:
        from benchmarks import readwrite_bench
        sections.append(("readwrite", readwrite_bench.run))
    if wanted & {"serving", "exp7"}:
        from benchmarks import serving_bench
        sections.append(("serving", serving_bench.run))
    if wanted & {"macro", "exp8"}:
        from benchmarks import macro_bench
        sections.append(
            ("macro", lambda: macro_bench.run(smoke=args.smoke)))
    if wanted & {"tail", "exp9"}:
        from benchmarks import tail_bench
        sections.append(
            ("tail", lambda: tail_bench.run(smoke=args.smoke)))
    if wanted & {"incr", "exp10"}:
        from benchmarks import incr_bench
        sections.append(
            ("incr", lambda: incr_bench.run(smoke=args.smoke)))
    if wanted & {"durability", "exp11"}:
        from benchmarks import durability_bench
        sections.append(
            ("durability", lambda: durability_bench.run(smoke=args.smoke)))
    if "kernels" in wanted:
        from benchmarks import kernel_bench
        sections.append(("kernels", kernel_bench.run))

    failed = []
    for name, fn in sections:
        try:
            fn()
        except Exception:  # noqa: BLE001
            failed.append(name)
            traceback.print_exc()
    if failed:
        print(f"FAILED sections: {failed}", file=sys.stderr)
        sys.exit(1)


if __name__ == "__main__":
    main()
