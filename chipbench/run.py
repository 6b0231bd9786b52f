"""Chip benchmark of the pilot's distributed join and sort.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process, run from the root of a checkout.  It checks the device (a
platform other than ``tpu``, too few chips, or a device kind missing from
``chipbench/peaks.json`` exits non-zero with no result), turns on the
persistent compile cache, makes the cell's tables from the seed, places
them on the cell's chips, compiles the cell's operator for them without
running it (set-up ends here), then times a closed loop of pilot tasks,
one at a time, until ``--seconds`` have passed and the last task has
ended.  It compares the output with its
own reference and prints one JSON line: the end-to-end metrics with
``--trace 0``, the per-layer metrics read from a profiler trace of the
window with ``--trace 1``.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from chipbench import harness  # noqa: E402


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = harness.load_cell(args.workload)
    harness.check_device(cell.chips)
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    result = harness.run_cell(cell, args.seed, args.seconds, bool(args.trace),
                              T_START)
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    main()
