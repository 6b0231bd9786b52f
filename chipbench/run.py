"""Chip benchmark of the pilot's operations: the distributed join and sort,
and whatever op a cell's configuration names.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process, run from the root of a checkout.  It checks the device (a
platform other than ``tpu``, too few chips, or a device kind missing from
``chipbench/peaks.json`` exits non-zero with no result), turns on the
persistent compile cache, and runs the cell's op (``chipbench/ops/<op>.py``,
see ``chipbench/harness.py``): its set-up makes the inputs from the seed,
places them on the cell's chips and compiles its programs without running
them (set-up ends here); then a closed loop of operations, one at a time,
runs until ``--seconds`` have passed and the last operation has ended.  The
op compares the output with its own reference, and the run prints one JSON
line: the end-to-end metrics with ``--trace 0``, the per-layer metrics read
from a profiler trace of the window with ``--trace 1``.
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
