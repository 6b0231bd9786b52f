"""The chip benchmark's parts off the chip: BENCHMARK.json's form,
discovery by name, the traffic generator, the exact comparison, the bytes
functions and the trace reduction."""
import dataclasses
import json
import re
import shutil
import sys
import threading
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from chipbench import compare, harness, tables, xplane  # noqa: E402
from chipbench.compare import rows_mismatched  # noqa: E402
from chipbench.ops import _tables  # noqa: E402

BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
CELLS = [w["name"] for w in BENCH["workloads"]]
#: the cells whose op is a table operator, with one program of fixed inputs
TABLE_CELLS = [w for w in CELLS
               if hasattr(harness.load_cell(w).op, "input_shapes")]
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def one_line(text: str) -> bool:
    return 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text


# ---------------------------------------------------------------------------
# BENCHMARK.json
# ---------------------------------------------------------------------------
def check_benchmark(root: Path):
    """BENCHMARK.json under ``root`` has the contract's keys, names and
    characters.  Shared with the protocol test, which runs it over the
    real file with a model cell added."""
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cells = [w["name"] for w in bench["workloads"]]
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert len((root / "BENCHMARK.json").read_bytes()) <= 64 * 1024
    assert 1 <= bench["run_seconds"] <= 51
    assert all(one_line(w) for w in bench["command"])
    for p in bench["paths"]:
        assert re.fullmatch(r"[A-Za-z0-9_./-]{1,200}", p) and ".." not in p
        assert (root / p).is_dir()
    for c in bench["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and one_line(c["source"])
        assert one_line(c["why"]) and all(NAME.match(k) for k in c["reduced"])
        assert any(c["file"].startswith(p + "/") for p in bench["paths"])
    for w in bench["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4) and one_line(w["why"])
    assert len(cells) == len(set(cells))
    assert 1 <= len(bench["command"]) <= 32 and 1 <= len(bench["paths"]) <= 16
    assert 1 <= len(bench["configs"]) <= 24 and 1 <= len(cells) <= 24
    assert 1 <= len(bench["end_to_end"]) <= 16
    assert 1 <= len(bench["per_layer"]) <= 128
    configs = [c["name"] for c in bench["configs"]]
    files = [c["file"] for c in bench["configs"]]
    assert len(configs) == len(set(configs)) and len(files) == len(set(files))
    assert all(len(c["reduced"]) <= 16 for c in bench["configs"])
    used = [w["config"] for w in bench["workloads"]]
    assert set(used) == set(configs)
    # a pair of configuration and traffic mix is one cell, never two
    pairs = [(w["config"], w["traffic"]) for w in bench["workloads"]]
    assert len(pairs) == len(set(pairs))
    metrics = bench["end_to_end"] + bench["per_layer"]
    names = [m["name"] for m in metrics]
    assert len(names) == len(set(names))
    for m in bench["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in bench["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert one_line(m["layer"]) and m["moves"] in names
        assert set(m.get("workloads", cells)) <= set(cells)
    for m in metrics:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher") and m["source"] in SOURCES
    assert "setup_s" in names
    n_four = sum(w["chips"] == 4 for w in bench["workloads"])
    assert n_four <= max(1, len(cells) // 2)


def check_cell(cell: str, root: Path):
    """The cell loads by name from the files under ``root``, its op gives
    the protocol's names, and it reports ``setup_s``, another end-to-end
    metric and a per-layer metric.  Nothing here is particular to one kind
    of op: a model cell passes it too."""
    c = harness.load_cell(cell, root=root)
    for name in ("setup", "operation", "check", "least_time"):
        assert callable(getattr(c.op, name)), name
    e2e = {m["name"] for m in c.end_to_end}
    assert "setup_s" in e2e and len(e2e) >= 2
    assert c.per_layer and all(callable(r.read) for _, r in c.per_layer)


def check_table_cell(cell: str, root: Path):
    """A table cell's op also gives the operator, its task body, its
    reference and its abstract inputs, and sizes its tables by rows a
    rank."""
    c = harness.load_cell(cell, root=root)
    assert _tables.rows(c) == c.config["rows_per_rank"] * c.chips
    for name in ("input_shapes", "build", "payload", "reference"):
        assert callable(getattr(c.op, name)), name


def test_benchmark_json_keys_and_characters():
    check_benchmark(ROOT)


@pytest.mark.parametrize("cell", CELLS)
def test_every_cell_finds_its_files(cell):
    check_cell(cell, ROOT)


@pytest.mark.parametrize("cell", TABLE_CELLS)
def test_every_table_cell_gives_its_table_parts(cell):
    check_table_cell(cell, ROOT)


@pytest.mark.parametrize("cell", ["join-uniform-1chip", "sort-uniform-1chip"])
def test_set_up_places_what_input_shapes_describes(cell):
    """The abstract inputs that rehearse.py and the phase readers compile
    are the tables set-up places, so the three compile one program."""
    import jax
    from repro.core import build_communicator
    c = harness.load_cell(cell)
    c.config["rows_per_rank"] = 300
    comm = build_communicator(jax.devices()[:1])
    state = c.op.setup(c, 3, comm)
    shapes = c.op.input_shapes(c, comm.mesh)
    placed, abstract = jax.tree.leaves(state.resident), jax.tree.leaves(shapes)
    assert len(placed) == len(abstract) == 3 * len(c.config["tables"])
    for x, y in zip(placed, abstract):
        assert (x.shape, x.dtype, x.sharding) == (y.shape, y.dtype, y.sharding)
    fn = c.op.build(comm.mesh, c.config, "return")
    assert fn.lower(*state.resident).as_text() == fn.lower(*shapes).as_text()


def test_adding_a_cell_edits_no_existing_file(tmp_path):
    """A new traffic mix, operator, configuration and per-layer metric are
    new files plus entries in BENCHMARK.json; no file of the harness
    changes."""
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "chipbench", tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = {p: p.read_bytes() for p in (tmp_path / "chipbench").rglob("*")
              if p.is_file()}
    cb = tmp_path / "chipbench"
    (cb / "traffic" / "lowmatch.json").write_text(json.dumps(
        {"why": "right keys on [0, 8 rows)",
         "keys": [{"dist": "uniform", "range": 1.0},
                  {"dist": "uniform", "range": 8.0}]}))
    shutil.copy(cb / "ops" / "sort.py", cb / "ops" / "sort2.py")
    conf = json.loads((cb / "configs" / "cylon-sort-ws35m.json").read_text())
    conf.update(name="other-sort", op="sort2")
    (cb / "configs" / "other-sort.json").write_text(json.dumps(conf))
    (cb / "layer_metrics" / "ops_done.py").write_text(
        "def read(run):\n    return float(len(run.ops))\n")
    bench = json.loads((tmp_path / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "other-sort", "source": "s",
                             "file": "chipbench/configs/other-sort.json",
                             "reduced": [], "why": "w"})
    bench["workloads"] += [
        {"name": "join-lowmatch-1chip", "config": "cylon-join-ws35m",
         "traffic": "lowmatch", "chips": 1, "why": "w"},
        {"name": "other-1chip", "config": "other-sort", "traffic": "uniform",
         "chips": 1, "why": "w"}]
    bench["per_layer"].append(
        {"name": "ops_done", "unit": "ops", "better": "higher",
         "source": "host_clock", "layer": "pilot scheduler",
         "moves": "op_time_s", "workloads": ["other-1chip"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))

    low = harness.load_cell("join-lowmatch-1chip", root=tmp_path)
    assert low.traffic["keys"][1]["range"] == 8.0
    other = harness.load_cell("other-1chip", root=tmp_path)
    assert other.op.TASK == "dist_sort"
    names = [m["name"] for m, _ in other.per_layer]
    # the new metric, and those every cell reports; no other cell's roofline
    assert names[-1] == "ops_done" and "jit_ms" in names
    assert not {"dist_join_roofline", "dist_sort_roofline"} & set(names)
    for p, data in before.items():
        assert p.read_bytes() == data, p


def test_unknown_device_kind_and_cpu_are_refused(monkeypatch):
    import jax

    class Dev:
        platform, device_kind = "tpu", "TPU v99"

    monkeypatch.setattr(jax, "devices", lambda: [Dev()])
    with pytest.raises(harness.DeviceError, match="peaks.json"):
        harness.check_device(1)
    Dev.device_kind = "TPU v5 lite"
    assert harness.check_device(1)["count"] == 1
    with pytest.raises(harness.DeviceError, match="asks for 4 chips"):
        harness.check_device(4)
    Dev.platform = "cpu"
    with pytest.raises(harness.DeviceError, match="no TPU"):
        harness.check_device(1)


# ---------------------------------------------------------------------------
# traffic, comparison, bytes
# ---------------------------------------------------------------------------
def test_same_seed_same_tables_for_any_seed():
    conf = harness.load_cell("join-uniform-1chip").config
    uniform = {"keys": [{"dist": "uniform", "range": 1.0}]}
    for seed in (0, 2**31 + 12345, 2**40, -3):
        a = tables.make_tables(conf, uniform, 1000, seed)
        b = tables.make_tables(conf, uniform, 1000, seed)
        assert all(np.array_equal(x[k], y[k]) for x, y in zip(a, b)
                   for k in x)
    a = tables.make_tables(conf, uniform, 1000, 1)
    b = tables.make_tables(conf, uniform, 1000, 2)
    assert not np.array_equal(a[0]["k"], b[0]["k"])
    assert a[0]["k"].dtype == np.int32 and a[1]["w"].dtype == np.float32
    assert 0 <= a[0]["k"].min() and a[0]["k"].max() < 1000


def test_zipf_keys_are_bounded_and_skewed():
    keys = tables.draw_keys(tables.rng_for(7), {"dist": "zipf", "a": 1.2},
                            100_000, np.int32)
    assert keys.min() >= 0 and keys.max() < 100_000
    counts = np.bincount(keys)
    assert counts[0] > 50 * np.median(counts[counts > 0])
    with pytest.raises(ValueError):
        tables.draw_keys(tables.rng_for(7), {"dist": "normal"}, 10, np.int32)


def test_compare_is_exact_and_order_free():
    rng = np.random.default_rng(3)
    n = 5000
    ref = {"k": rng.integers(0, 500, n, dtype=np.int32),
           "v": rng.standard_normal(n, dtype=np.float32),
           "w": rng.standard_normal(n, dtype=np.float32)}
    perm = rng.permutation(n)
    got = {c: v[perm] for c, v in ref.items()}
    assert rows_mismatched(got, ref, "k") == 0
    # a pairing swapped between two rows of one key keeps every column's
    # multiset and still differs
    i, j = np.flatnonzero(ref["k"] == ref["k"][0])[:2]
    swapped = {c: v.copy() for c, v in ref.items()}
    swapped["w"][[i, j]] = swapped["w"][[j, i]]
    assert rows_mismatched(swapped, ref, "k") > 0
    bit = {c: v.copy() for c, v in ref.items()}
    bit["v"][7] = np.nextafter(bit["v"][7], np.float32(np.inf))
    assert rows_mismatched(bit, ref, "k") > 0
    short = {c: v[:-3] for c, v in ref.items()}
    assert rows_mismatched(short, ref, "k") >= 3
    assert rows_mismatched({"k": ref["k"], "v": ref["v"]}, ref, "k") == n


@pytest.mark.parametrize("dist", [{"dist": "uniform", "range": 1.0},
                                  {"dist": "zipf", "a": 1.2, "range": 1.0}])
def test_references_by_key_range_equal_the_whole_at_once(dist):
    """Split into key ranges and worked in threads, the references give the
    rows that one pass over the whole tables gives: the sort exactly, the
    join as a multiset."""
    join = harness.load_cell("join-uniform-1chip")
    sort = harness.load_cell("sort-uniform-1chip")
    host = tables.make_tables(join.config, {"keys": [dist]}, 40_000, 9)
    assert len(compare.range_bounds(host[0]["k"])) > 1
    whole = join.op._join(*host, "k")
    got = join.op.reference(host, "k")
    assert len(got["k"]) == len(whole["k"])
    assert rows_mismatched(got, whole, "k") == 0
    order = np.argsort(host[0]["k"], kind="stable")
    got = sort.op.reference(host[:1], "k")
    assert all(np.array_equal(got[c], host[0][c][order]) for c in host[0])


def test_bytes_functions():
    join = harness.load_cell("join-uniform-1chip").op
    sort = harness.load_cell("sort-uniform-1chip").op
    # one chip: inputs read once and output written once, nothing sent
    assert join.least_bytes([10, 20], [8, 8], 30, 12, 1) == (80 + 160 + 360, 0)
    assert sort.least_bytes([10], [8], 10, 8, 1) == (160, 0)
    # four chips: a quarter each, three quarters of a chip's rows leave it
    hbm, ici = join.least_bytes([400, 400], [8, 8], 400, 12, 4)
    assert hbm == (6400 + 4800) / 4 and ici == 6400 / 4 * 3 / 4
    hbm, ici = sort.least_bytes([400], [8], 400, 8, 4)
    assert hbm == 6400 / 4 and ici == 3200 / 4 * 3 / 4


def test_least_time_names_its_bound():
    peaks = json.loads((ROOT / "chipbench" / "peaks.json").read_text())
    v5e = peaks["devices"]["TPU v5 lite"]
    one = harness.load_cell("join-uniform-1chip")
    rows = _tables.rows(one)
    state = _tables.State(cell=one, host=None, resident=None, ref_rows=rows)
    t, bound = one.op.least_time(one, state, v5e)
    assert bound == "hbm"
    assert t == pytest.approx(rows * (16 + 12) / v5e["hbm_bytes_per_s"])
    four = dataclasses.replace(one, chips=4)
    rows = _tables.rows(four)
    state.ref_rows = rows
    t, bound = four.op.least_time(four, state, v5e)
    assert bound == "ici"
    assert t == pytest.approx(rows * 16 / 4 * 3 / 4 / v5e["ici_bytes_per_s"])


# ---------------------------------------------------------------------------
# the trace reduction
# ---------------------------------------------------------------------------
SYNTHETIC = """
planes {
  id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 2000000000000 duration_ps: 3000000000000 }
    events { metadata_id: 2 offset_ps: 4000000000000 duration_ps: 2000000000000 }
    events { metadata_id: 1 offset_ps: 8000000000000 duration_ps: 1000000000000 } }
  lines { id: 2 name: "Async XLA Ops" timestamp_ns: 0
    events { metadata_id: 3 offset_ps: 7500000000000 duration_ps: 1000000000000 } }
  event_metadata { key: 1 value { id: 1 name: "fusion.3" } }
  event_metadata { key: 2 value { id: 2 name: "all-to-all.1" } }
  event_metadata { key: 3 value { id: 3 name: "all-to-all-start.2" } }
}
planes {
  id: 2 name: "/device:TPU:1"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 2000000000000 duration_ps: 1000000000000 } }
  event_metadata { key: 1 value { id: 1 name: "fusion.4" } }
}
planes {
  id: 3 name: "/host:CPU"
  lines { id: 1 name: "main" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 1000000000000 duration_ps: 9000000000000 }
    events { metadata_id: 2 offset_ps: 1000000000000 duration_ps: 6000000000000 }
    events { metadata_id: 2 offset_ps: 7000000000000 duration_ps: 3000000000000 } }
  lines { id: 2 name: "worker" timestamp_ns: 0
    events { metadata_id: 3 offset_ps: 1000000000000 duration_ps: 400000000000 }
    events { metadata_id: 4 offset_ps: 7500000000000 duration_ps: 2300000000000 } }
  event_metadata { key: 1 value { id: 1 name: "chipbench/window" } }
  event_metadata { key: 2 value { id: 2 name: "chipbench/op" } }
  event_metadata { key: 3 value { id: 3 name: "chipbench/build" } }
  event_metadata { key: 4 value { id: 4 name: "chipbench/call" } }
  event_metadata { key: 5 value { id: 5 name: "other" } }
}
"""


def test_reduction_of_a_synthetic_two_chip_trace():
    from jax.profiler import ProfileData
    t = xplane.from_profile(ProfileData.from_text_proto(SYNTHETIC), [0, 1])
    # window 1..10 s; chip 0 busy 2..6 and 8..9, chip 1 busy 2..3
    assert t.spans("chipbench/op") == [(1.0, 7.0), (7.0, 10.0)]
    assert t.busy_window() == (pytest.approx((5 + 1) / 2), pytest.approx(9))
    assert t.busy_mean(1.0, 7.0) == pytest.approx((4 + 1) / 2)
    assert t.matching_mean("all-to-all", 1.0, 7.0) == pytest.approx(1.0)
    # an exchange in flight (async line) counts, and is not busy time
    assert t.matching_mean("all-to-all", 7.0, 10.0) == pytest.approx(0.5)
    b = t.breakdown()
    # chip 0's two operations overlap without nesting: each keeps its time
    assert b["device_ops"] == [["fusion.3", pytest.approx(2.0)],
                               ["all-to-all.1", pytest.approx(1.0)],
                               ["fusion.4", pytest.approx(0.5)]]
    # idle: chip 0 in 1..2, 6..8 (tasks) and 9..10 (a task body's call),
    # chip 1 in 1..2 and 3..10
    assert dict(b["idle_gaps"]) == {"chipbench/op": pytest.approx(5.5),
                                    "chipbench/call": pytest.approx(0.5)}
    only0 = xplane.from_profile(ProfileData.from_text_proto(SYNTHETIC), [0])
    assert list(only0.busy) == [0]


def test_self_times_name_nested_operations_by_their_loop():
    events = [("%while.1 = (s32[]) while(...)", 0.0, 10.0),
              ("%fusion.2 = s32[8] fusion(...)", 1.0, 4.0),
              ("%fusion.2 = s32[8] fusion(...)", 5.0, 9.0),
              ("%sort.3 = s32[8] sort(...)", 11.0, 12.0)]
    assert xplane.self_times(events, 0.0, 11.5) == {
        "while.1": pytest.approx(3.0), "fusion.2 in while.1": pytest.approx(7.0),
        "sort.3": pytest.approx(0.5)}


def test_reduction_finds_the_spans_of_a_recorded_trace(tmp_path):
    """A trace recorded here: the annotations of the window, a task on the
    main thread and the task body on another thread are found by name and
    nest; this host has no device plane, so nothing is busy."""
    import jax
    import jax.numpy as jnp
    from jax.profiler import TraceAnnotation
    f = jax.jit(lambda x: jnp.sort(x) * 2)
    x = jnp.arange(4096.0)[::-1]
    f(x).block_until_ready()

    def body():
        with TraceAnnotation("chipbench/call"):
            f(x).block_until_ready()

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    with TraceAnnotation("chipbench/window"):
        for _ in range(2):
            with TraceAnnotation("chipbench/op"):
                th = threading.Thread(target=body)
                th.start()
                th.join(timeout=60)
    jax.profiler.stop_trace()
    t = xplane.load(tmp_path, [0])
    ops, calls = t.spans("chipbench/op"), t.spans("chipbench/call")
    assert len(ops) == 2 and len(calls) == 2
    for (olo, ohi), (clo, chi) in zip(ops, calls):
        assert olo <= clo <= chi <= ohi
    assert t.busy_window()[0] == 0.0
    assert t.breakdown() == {"device_ops": [], "idle_gaps": []}
