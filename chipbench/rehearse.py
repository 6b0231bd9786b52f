"""Compile each cell's operator at its real size for a described TPU v5e,
without the chip, and print what the compiler plans for one chip's memory.

    JAX_PLATFORMS=cpu python3 chipbench/rehearse.py [--workload <cell> ...]

The compile runs in this process on the TPU compiler (a one-chip mesh or
the 2x2 host), with the operator built by the cell's ``ops/<op>.py``
(``build``) for the abstract inputs its set-up places (``input_shapes``).
It proves that the program compiles and fits; it runs nothing.  One JSON
line a cell, with ``memory_analysis()`` in bytes.
"""
import argparse
import json
import os
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from chipbench import harness  # noqa: E402


def compile_cell(cell, topo) -> dict:
    import numpy as np
    from jax.sharding import Mesh

    mesh = Mesh(np.array(topo.devices[:cell.chips]), ("df",))
    fn = cell.op.build(mesh, cell.config, "return")
    mem = fn.lower(*cell.op.input_shapes(cell, mesh)).compile().memory_analysis()
    fields = ("argument_size_in_bytes", "output_size_in_bytes",
              "alias_size_in_bytes", "temp_size_in_bytes",
              "generated_code_size_in_bytes")
    out = {f: getattr(mem, f) for f in fields}
    out["planned_peak_bytes"] = (out["argument_size_in_bytes"]
                                 + out["output_size_in_bytes"]
                                 - out["alias_size_in_bytes"]
                                 + out["temp_size_in_bytes"])
    return out


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", action="append")
    ap.add_argument("--chips", type=int, choices=(1, 4),
                    help="compile for this many chips instead of the cell's")
    args = ap.parse_args(argv)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    from jax.experimental import topologies
    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    for name in args.workload or [w["name"] for w in bench["workloads"]]:
        cell = harness.load_cell(name)
        cell.chips = args.chips or cell.chips
        print(json.dumps({"workload": name, "chips": cell.chips,
                          **compile_cell(cell, topo)}),
              flush=True)


if __name__ == "__main__":
    main()
