"""chip_smoke.py off the chip: it refuses to run without a TPU, and its
phases — the pilot's sort/join tasks and the served model with its oracle
check — pass at a tiny size on the CPU, so a change that breaks the chip
run is caught here first."""
import dataclasses
import importlib.util
import os
import subprocess
import sys
from pathlib import Path

import pytest

from repro.configs import get_config, reduced
from tests._subproc import run_with_devices

ROOT = Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_smoke_exits_nonzero_without_a_tpu(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, str(ROOT / "chip_smoke.py")],
                       cwd=tmp_path, env=env, capture_output=True, text=True,
                       timeout=120)
    assert r.returncode != 0
    assert '"ok"' not in r.stdout
    assert "no TPU" in r.stderr


def test_data_phase_equals_reference_on_one_device(smoke, capsys):
    smoke.data_phase(0, 1, rows=3000)
    out = capsys.readouterr().out
    assert "dist_sort chips=1" in out and "dist_join chips=1" in out
    assert out.count("equals_reference=True") == 2


def test_model_phase_checks_every_stream_against_oracle(smoke, capsys):
    cfg = dataclasses.replace(reduced(get_config("qwen3-8b")), n_layers=2,
                              dtype="bfloat16")
    smoke.model_phase(1, cfg=cfg, n_requests=3, prompt_lens=(4, 12),
                      n_new=5, max_batch=2, max_seq=32)
    out = capsys.readouterr().out
    assert "requests_answered=3/3" in out
    assert out.count("oracle: request") == 3


SNIPPET = r"""
import importlib.util, sys
spec = importlib.util.spec_from_file_location("chip_smoke", sys.argv[1])
smoke = importlib.util.module_from_spec(spec)
spec.loader.exec_module(smoke)
smoke.data_phase(0, 4, rows=5000)
smoke.pair_phase(0, rows=1024)
print("FOUR_OK")
"""


@pytest.mark.integration
def test_four_device_phases_on_host_devices():
    """The --chips 4 path on four virtual CPU devices: one 4-rank
    communicator, then two concurrent 2-rank tasks on disjoint devices."""
    snippet = SNIPPET.replace("sys.argv[1]", repr(str(ROOT / "chip_smoke.py")))
    out = run_with_devices(snippet, n_devices=4)
    assert "FOUR_OK" in out
    assert "chips=[0, 1]" in out and "chips=[2, 3]" in out
