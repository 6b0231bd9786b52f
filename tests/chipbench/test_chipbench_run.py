"""Whole runs of the chip benchmark at a tiny row count on the CPU.

The device check is patched here, in the test; the run itself goes through
the pilot, the scheduler, the executor and the operators as on the chip.
The faults and the control must turn ``correct`` false."""
import importlib.util
import json
import os
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from chipbench import control, harness  # noqa: E402

ROWS = 3000                  # rows a table a rank
ONE_CHIP = ["join-uniform-1chip", "sort-uniform-1chip"]
V5E = json.loads((ROOT / "chipbench" / "peaks.json").read_text())[
    "devices"]["TPU v5 lite"]


def tiny(name):
    cell = harness.load_cell(name)
    cell.config["rows_per_rank"] = ROWS
    return cell


@pytest.fixture
def on_cpu(monkeypatch):
    """The run as on the chip, with the device check and the peaks of a
    v5e patched in and the persistent compile cache left off."""
    import repro.compile_cache
    monkeypatch.setattr(harness, "check_device",
                        lambda chips: {"platform": "cpu"})
    monkeypatch.setattr(harness, "load_peaks", lambda kind: V5E)
    monkeypatch.setattr(harness, "load_cell",
                        lambda name, _load=harness.load_cell:
                        _tiny_loaded(_load(name)))
    monkeypatch.setattr(repro.compile_cache, "enable_compile_cache",
                        lambda: "off")


def _tiny_loaded(cell):
    cell.config["rows_per_rank"] = ROWS
    return cell


@pytest.fixture(scope="module")
def run_module():
    spec = importlib.util.spec_from_file_location(
        "chipbench_run_main", ROOT / "chipbench" / "run.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_run_exits_nonzero_without_a_tpu(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run(
        [sys.executable, str(ROOT / "chipbench" / "run.py"), "--workload",
         "sort-uniform-1chip", "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=120)
    assert r.returncode != 0
    assert r.stdout.strip() == ""
    assert "no TPU" in r.stderr


@pytest.mark.parametrize("trace", [0, 1])
@pytest.mark.parametrize("cell", ONE_CHIP)
def test_cell_end_to_end(on_cpu, run_module, capsys, cell, trace):
    run_module.main(["--workload", cell, "--seed", str(2**31 + 99),
                     "--seconds", "0", "--trace", str(trace)])
    out, err = capsys.readouterr()
    result = json.loads(out.strip().splitlines()[-1])
    assert result["correct"] is True
    assert list(result)[-1] == "checks"
    assert {"correct", "attempted", "failed", "metrics", "device"} <= set(result)
    assert result["attempted"] >= 1 and result["failed"] == 0
    checks = result["checks"]
    assert all(c == {"value": 0, "limit": 0} for c in checks.values())
    assert err.strip().splitlines()[-len(checks):] == [
        f"check {k}: 0 (limit 0)" for k in checks]
    metrics = result["metrics"]
    if trace:
        # no device plane on the CPU: only host-side readings appear
        assert {"dispatch_wait_ms", "comm_build_ms", "jit_ms",
                "op_mfu"} <= set(metrics)
        assert not {"dist_join_roofline", "dist_sort_roofline",
                    "device_idle_share"} & set(metrics)
        assert {"busy_s", "window_s"} <= set(result["device"])
        assert set(result["breakdown"]) == {"device_ops", "idle_gaps"}
    else:
        assert {"op_time_s", "setup_s"} <= set(metrics)
        assert metrics["op_time_s"]["value"] > 0


def _unchanged(comm, tables, config):
    """A task that hands back its input as its output."""
    import jax.numpy as jnp
    return tables[0], jnp.bool_(False)


def _half(real):
    def payload(comm, tables, config):
        out, ovf = real(comm, tables, config)
        out.nrows = out.nrows // 2          # half the rows left out
        return out, ovf
    return payload


def _altered(real):
    def payload(comm, tables, config):
        out, ovf = real(comm, tables, config)
        name = sorted(c for c in out.columns if c != config["key"])[0]
        out.columns[name] = out.columns[name].at[0].add(1.0)
        return out, ovf
    return payload


@pytest.mark.parametrize("fault", ["unchanged", "half", "altered"])
@pytest.mark.parametrize("cell", ONE_CHIP)
def test_a_broken_timed_path_is_not_correct(monkeypatch, cell, fault):
    monkeypatch.setattr(harness, "load_peaks", lambda kind: V5E)
    c = tiny(cell)
    real = c.op.payload
    c.op.payload = {"unchanged": _unchanged, "half": _half(real),
                    "altered": _altered(real)}[fault]
    r = harness.run_cell(c, 5, 0.0, False, 0.0)
    assert r["correct"] is False
    assert any(v["value"] > v["limit"] for v in r["checks"].values())


@pytest.mark.parametrize("cell", ONE_CHIP)
def test_control_at_lower_precision_is_not_correct(monkeypatch, cell):
    monkeypatch.setattr(harness, "load_peaks", lambda kind: V5E)
    program = harness.run_cell(tiny(cell), 11, 0.0, False, 0.0)
    ctrl = control.readings(tiny(cell), 11)
    assert program["correct"] is True and ctrl["run"] == "control"
    assert ctrl["correct"] is False
    assert set(ctrl["checks"]) <= set(program["checks"])
    # nearly every standard normal float32 changes when rounded to bf16
    assert ctrl["checks"]["rows_mismatched"] > 0.9 * ROWS


FOUR_CHIPS = r"""
import json, sys
sys.path[:0] = [sys.argv[1], sys.argv[1] + "/src"]
from chipbench import harness
from repro.dataframe import comm
peaks = json.load(open(sys.argv[1] + "/chipbench/peaks.json"))
harness.load_peaks = lambda kind: peaks["devices"]["TPU v5 lite"]
# the join configuration on one 4-chip communicator, whichever cells
# BENCHMARK.json lists
cell = harness.load_cell("join-uniform-1chip")
cell.chips, cell.config["rows_per_rank"] = 4, 500
ok = harness.run_cell(cell, 2**31 + 5, 0.0, False, 0.0)
comm.all_to_all = lambda x, axis: x          # the exchange left out
broken = harness.run_cell(cell, 2**31 + 5, 0.0, False, 0.0)
print(json.dumps([ok["correct"], broken["correct"], ok["checks"],
                  broken["checks"]]))
"""


def test_four_chip_cell_on_four_host_devices():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    r = subprocess.run([sys.executable, "-c", FOUR_CHIPS, str(ROOT)],
                       env=env, capture_output=True, text=True, timeout=300)
    assert r.returncode == 0, r.stderr[-3000:]
    ok, broken, ok_checks, broken_checks = json.loads(
        r.stdout.strip().splitlines()[-1])
    assert ok is True and all(v["value"] == 0 for v in ok_checks.values())
    assert broken is False
    assert broken_checks["rows_mismatched"]["value"] > 0


def test_sampler_keeps_each_output_with_equal_chance():
    kept = np.zeros(4, int)
    for seed in range(4000):
        s = harness.Sampler(seed)
        for i in range(4):
            s.offer(i)
        kept[s.kept] += 1
    assert (abs(kept - 1000) < 150).all(), kept


WARM_UP = r"""
import json, sys
sys.path[:0] = [sys.argv[1], sys.argv[1] + "/src"]
import jax
jax.config.update("jax_compilation_cache_dir", sys.argv[2])
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
from chipbench import harness
peaks = json.load(open(sys.argv[1] + "/chipbench/peaks.json"))
harness.load_peaks = lambda kind: peaks["devices"]["TPU v5 lite"]
cell = harness.load_cell(sys.argv[3])
cell.config["rows_per_rank"] = 2000
print(json.dumps(harness.run_cell(cell, 7, 0.0, False, 0.0)["correct"]))
"""


@pytest.mark.parametrize("cell", ONE_CHIP)
def test_set_up_compiles_and_the_window_only_loads(tmp_path, cell):
    """The warm-up compiles the operator without running it; every window
    task rebuilds it and finds it in the persistent cache."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, "-c", WARM_UP, str(ROOT),
                        str(tmp_path / "cache"), cell],
                       env=env, capture_output=True, text=True, timeout=300)
    assert r.returncode == 0, r.stderr[-3000:]
    assert r.stdout.strip().splitlines()[-1] == "true"
    assert any((tmp_path / "cache").iterdir())
    assert " compiles_in_window=0 " in r.stderr, r.stderr[-3000:]
