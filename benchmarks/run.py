"""Benchmark orchestrator — one section per paper table/figure.

Prints ``name,us_per_call,derived`` CSV lines (see benchmarks/common.emit).
BENCH_FAST=1 (default) runs CI-sized inputs; BENCH_FAST=0 runs the full
sizes.  The dry-run/roofline section only reports cells whose artifacts
exist (run ``python -m repro.launch.dryrun --all`` first for the full table).
"""
from __future__ import annotations

import sys
import traceback


def _section(name, fn):
    print(f"# === {name} ===", flush=True)
    try:
        fn()
    except Exception as e:  # noqa: BLE001 — keep the harness running
        print(f"# SECTION FAILED {name}: {type(e).__name__}: {e}", flush=True)
        traceback.print_exc()
        return False
    return True


def main() -> None:
    from benchmarks import (bench_hetero, bench_kernels, bench_overhead,
                            bench_scaling, roofline)

    ok = True
    # paper Table 2 (overhead column): communicator construction vs ranks
    ok &= _section("overhead (paper Table 2)", bench_overhead.run)
    # paper Figs 5-8 + Table 2: join/sort weak+strong scaling, BM vs RP
    ok &= _section("scaling join/sort (paper Figs 5-8)", bench_scaling.run)
    # paper Figs 9-11: heterogeneous vs batch (the 4-15% claim)
    ok &= _section("heterogeneous vs batch (paper Figs 9-11)", bench_hetero.run)
    # kernel hot-spots (paper §4.4 discussion)
    ok &= _section("kernel hot-spots", bench_kernels.run)
    # roofline table from dry-run artifacts (this repro's §Roofline)
    ok &= _section("roofline (from dry-run artifacts)", roofline.run)
    if not ok:
        sys.exit(1)


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
