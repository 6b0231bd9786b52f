"""Readings that set the upper limits of the comparison that decides
``correct``.

    python3 chipbench/control.py --workload <cell> --seeds 1,2,3

For each seed, at the cell's own size, the control: the plain reference
put in the program's place and computed one precision down from the
configuration's, its float32 payloads rounded to bfloat16 before the
operator.  Its output goes through the same comparison with the float32
reference as a run's output does.  The operators move payloads without
arithmetic, so this is also what the program gives on bfloat16 payloads.
It runs on the host and needs no chip.  One JSON line a seed: each number
compared and whether the control came out correct (it must not).  The
benchmark's own runs never run the control.
"""
import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from chipbench import harness  # noqa: E402
from chipbench import tables as traffic_gen  # noqa: E402


def lower_precision(tables: list) -> list:
    """Every float32 column rounded to bfloat16 (and held as float32)."""
    import ml_dtypes
    import numpy as np
    return [{k: (v.astype(ml_dtypes.bfloat16).astype(np.float32)
                 if v.dtype == np.float32 else v) for k, v in t.items()}
            for t in tables]


def readings(cell, seed: int) -> dict:
    key = cell.config["key"]
    host = traffic_gen.make_tables(cell.config, cell.traffic, cell.rows, seed)
    ref = cell.op.reference(host, key)
    got = cell.op.reference(lower_precision(host), key)
    checks = {"rows_out_diff": abs(len(got[key]) - len(ref[key])),
              **cell.op.compare(got, ref, key)}
    return {"workload": cell.name, "seed": seed, "run": "control",
            "correct": all(v <= 0 for v in checks.values()), "checks": checks}


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated whole numbers")
    args = ap.parse_args(argv)
    cell = harness.load_cell(args.workload)
    for seed in (int(s) for s in args.seeds.split(",")):
        print(json.dumps(readings(cell, seed)), flush=True)


if __name__ == "__main__":
    main()
