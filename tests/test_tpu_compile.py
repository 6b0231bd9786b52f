"""Compiles of the main path's device programs for a described TPU v5e.

Nothing runs here: each test lowers one program at its real size for chips
that are described, not attached, and the TPU compiler must accept it —
which interpret-mode kernel tests cannot show (tiling, unsupported
lowerings, device memory).  The topology is described inside a module
fixture, never at import, so every pytest-xdist worker collects the same
tests and only the one that runs this file loads the TPU compiler.
"""
import math
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import Mesh, NamedSharding, SingleDeviceSharding
from jax.sharding import PartitionSpec as P

from repro.dataframe import ops_dist as D
from repro.dataframe.table import Table
from repro.kernels.flash_attention.ops import flash_attention
from repro.kernels.radix_partition.ops import radix_partition

HBM_BYTES = 16 * 10**9          # one v5e chip
PAPER_ROWS = 35_000_000         # the paper's strong-scaling table size


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler: nothing to test
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip can be written to the persistent cache
    # but never read back without one: keep the cache out of these tests
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield desc
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def test_radix_partition_compiles_for_v5e(one_chip):
    # the out-of-core shuffle's call: 4 M rows, its 4096-row block
    rows = jax.ShapeDtypeStruct((4 * 2**20,), jnp.int32, sharding=one_chip)
    compiled = radix_partition.lower(rows, 8, block=4096).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_flash_attention_compiles_for_v5e_at_qwen3_8b_widths(one_chip):
    # qwen3-8b: 32 query heads, 8 KV heads, head dim 128; 4096 positions
    q = jax.ShapeDtypeStruct((1, 4096, 32, 128), jnp.bfloat16,
                             sharding=one_chip)
    kv = jax.ShapeDtypeStruct((1, 4096, 8, 128), jnp.bfloat16,
                              sharding=one_chip)
    compiled = flash_attention.lower(q, kv, kv).compile()
    assert "tpu_custom_call" in compiled.as_text()


def _compile_dist_op(topo, op, n_chips):
    """The paper-rows program of ``op`` on ``n_chips`` described chips:
    35 M rows a table (int32 key, float32 payload) with the examples'
    capacity rule."""
    mesh = Mesh(topo.devices[:n_chips], ("df",))
    rows = NamedSharding(mesh, P("df"))
    cap = PAPER_ROWS // n_chips * 2 + 64

    def table(payload):
        col = jax.ShapeDtypeStruct((n_chips * cap,), jnp.int32, sharding=rows)
        val = jax.ShapeDtypeStruct((n_chips * cap,), jnp.float32,
                                   sharding=rows)
        nrows = jax.ShapeDtypeStruct((n_chips,), jnp.int32, sharding=rows)
        return Table(columns={"k": col, payload: val}, nrows=nrows)

    if op == "sort":
        return D.make_dist_sort(mesh, "k").lower(table("v")).compile()
    return D.make_dist_join(mesh, "k").lower(table("v"), table("w")).compile()


@pytest.fixture(scope="module")
def dist_op(topo):
    """Compiles each (op, n_chips) program once for this file's tests."""
    compiled = {}

    def get(op, n_chips):
        if (op, n_chips) not in compiled:
            compiled[op, n_chips] = _compile_dist_op(topo, op, n_chips)
        return compiled[op, n_chips]
    return get


@pytest.mark.parametrize("n_chips", [1, 4])
@pytest.mark.parametrize("op", ["sort", "join"])
def test_dist_op_at_paper_rows_fits_v5e(dist_op, op, n_chips):
    """The paper-rows program compiles and fits one chip's HBM."""
    mem = dist_op(op, n_chips).memory_analysis()
    peak = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            - mem.alias_size_in_bytes + mem.temp_size_in_bytes)
    assert peak < HBM_BYTES, (op, n_chips, peak)


_COMPUTATION = re.compile(r"^(?:ENTRY )?%(\S+) [^\n]*\{\n(.*?)^\}",
                          re.M | re.S)
_CALLS = re.compile(r"(?:calls|to_apply|body|condition)=%([^\s,]+)"
                    r"|branch_computations=\{([^}]*)\}")
_WHILE_BODY = re.compile(r" while\(.*?body=%([^\s,]+)")
_GATHER = re.compile(r"%(\S+) = \w+\[([\d,]*)\]\S* gather\(")


def loop_gathers(hlo: str, min_elems: int) -> list:
    """(name, elements) of every gather with at least ``min_elems`` outputs
    that runs inside a ``while`` loop of the compiled module ``hlo``."""
    bodies = {m[1]: m[2] for m in _COMPUTATION.finditer(hlo)}
    todo = [b for text in bodies.values() for b in _WHILE_BODY.findall(text)]
    seen = set()
    while todo:
        name = todo.pop()
        if name in seen or name not in bodies:
            continue
        seen.add(name)
        for direct, branches in _CALLS.findall(bodies[name]):
            todo += [direct] if direct else re.findall(r"%([^\s,]+)",
                                                      branches)
    found = []
    for name in seen:
        for gather, dims in _GATHER.findall(bodies[name]):
            elems = math.prod(int(d) for d in dims.split(",") if d)
            if elems >= min_elems:
                found.append((gather, elems))
    return found


def test_loop_gathers_finds_a_binary_search_scan(one_chip):
    """The guard below sees the gather of a full-length searchsorted scan."""
    n = 4096
    sorted_keys = jax.ShapeDtypeStruct((n,), jnp.int32, sharding=one_chip)
    hlo = jax.jit(lambda a, q: jnp.searchsorted(a, q)).lower(
        sorted_keys, sorted_keys).compile().as_text()
    assert [e for _, e in loop_gathers(hlo, n)] == [n]
    assert loop_gathers(hlo, n + 1) == []


def test_paper_rows_join_has_no_full_length_gather_in_a_loop(dist_op):
    """No ``while`` loop of the one-chip join gathers a value for each of its
    rows: the search merges by sort and expands by scatter.  (The pack's
    ``searchsorted`` loop gathers one value a partition each step.)"""
    hlo = dist_op("join", 1).as_text()
    assert loop_gathers(hlo, PAPER_ROWS) == []
