"""The op protocol carries an operation that is no table operator: a cell
that serves a model, written as new files into a root of its own, runs
through ``load_cell`` and ``run_cell`` with no edit to the harness."""
import json
import shutil
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

sys.path.insert(0, str(Path(__file__).resolve().parent))

from chipbench import harness  # noqa: E402
from test_chipbench_parts import (check_benchmark, check_cell,  # noqa: E402
                                  check_table_cell)

V5E = json.loads((ROOT / "chipbench" / "peaks.json").read_text())[
    "devices"]["TPU v5 lite"]

#: an op module that serves a fixed batch of greedy requests through the
#: pilot (``ContinuousEngine`` + ``ServeDriver``) and checks every stream
#: against the full-forward oracle
SERVE_OP = '''
"""Serving a fixed batch of greedy requests to completion, one operation."""
import dataclasses
import gc

import numpy as np


def _model(cell, seed):
    import jax
    from repro.configs import get_config, reduced
    from repro.models import get_model
    cfg = dataclasses.replace(reduced(get_config(cell.config["model"])),
                              n_layers=cell.config["n_layers"])
    api = get_model(cfg)
    return cfg, jax.jit(lambda k: api.init(k, cfg))(jax.random.key(seed % 2**32))


def _requests(state, first_uid):
    from repro.serve import Request
    return [Request(prompt=p, max_new_tokens=state["new_tokens"],
                    uid=first_uid + i) for i, p in enumerate(state["prompts"])]


def setup(cell, seed, comm):
    import jax
    from repro.serve import ContinuousEngine
    cfg, params = _model(cell, seed)
    t = cell.traffic
    rng = np.random.default_rng([seed % 2**64, 2])
    lens = rng.integers(t["prompt_lens"][0], t["prompt_lens"][1] + 1,
                        t["requests"])
    state = {"cell": cell, "seed": seed, "cfg": cfg,
             "new_tokens": t["new_tokens"], "served": 0,
             "params_n": sum(x.size for x in jax.tree_util.tree_leaves(params)),
             "prompts": [rng.integers(0, cfg.vocab_size, n, dtype=np.int32)
                         for n in lens]}
    state["engine"] = ContinuousEngine(cfg, params,
                                       max_batch=cell.config["max_batch"],
                                       max_seq=cell.config["max_seq"])
    state["engine"].run(_requests(state, -len(lens)))     # the warm-up
    return state


def operation(session, state):
    from repro.serve import ServeDriver
    first = len(session.tasks)
    reqs = _requests(state, state["served"])
    state["served"] += len(reqs)
    out = ServeDriver(state["engine"], session).run(reqs, timeout=600)
    uids = [t.uid for t in session.tasks[first:]]
    return uids, {r.uid: (r.prompt, out[r.uid]) for r in reqs}, {}


def check(state, kept, ops):
    from repro.serve import greedy_reference
    state["engine"] = None
    gc.collect()
    cfg, params = _model(state["cell"], state["seed"])
    wrong = 0
    for prompt, got in (kept or {}).values():
        ref = greedy_reference(cfg, params, prompt, len(got))
        wrong += int(np.count_nonzero(np.asarray(got) != ref))
    return {"tokens_wrong": wrong if kept else 1}


def least_time(cell, state, peaks):
    tokens = sum(len(p) + state["new_tokens"] for p in state["prompts"])
    return 2.0 * state["params_n"] * tokens / peaks["bf16_flops_per_s"], "flops"
'''


SERVE_CONFIG = {"name": "qwen3-8b-toy", "source": "s",
                "file": "chipbench/configs/qwen3-8b-toy.json",
                "reduced": [], "why": "w"}
SERVE_CELL = {"name": "serve-toy", "config": "qwen3-8b-toy",
              "traffic": "fixed-batch", "chips": 1, "why": "w"}


def write_serve_files(cb: Path):
    """The new files of one serve cell: its configuration, its traffic
    mix and its op module."""
    for d in ("configs", "traffic", "ops", "layer_metrics"):
        (cb / d).mkdir(parents=True, exist_ok=True)
    (cb / "configs" / "qwen3-8b-toy.json").write_text(json.dumps(
        {"name": "qwen3-8b-toy", "op": "serve", "model": "qwen3-8b",
         "n_layers": 2, "max_batch": 2, "max_seq": 32}))
    (cb / "traffic" / "fixed-batch.json").write_text(json.dumps(
        {"why": "a fixed batch of greedy requests, more than the slots",
         "requests": 3, "prompt_lens": [3, 6], "new_tokens": 4}))
    (cb / "ops" / "serve.py").write_text(SERVE_OP)


@pytest.fixture
def serve_root(tmp_path, monkeypatch):
    """A root holding only the new files of one serve cell."""
    monkeypatch.setattr(harness, "load_peaks", lambda kind: V5E)
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cb = tmp_path / "chipbench"
    write_serve_files(cb)
    per_layer = [m for m in bench["per_layer"]
                 if m["name"] in ("dispatch_wait_ms", "op_mfu")]
    for m in per_layer:
        shutil.copy(ROOT / "chipbench" / "layer_metrics" / f"{m['name']}.py",
                    cb / "layer_metrics")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(dict(
        bench, configs=[SERVE_CONFIG], workloads=[SERVE_CELL],
        per_layer=per_layer)))
    return tmp_path


def test_the_benchmark_with_a_serve_cell_added_passes_every_cell_check(
        tmp_path):
    """The real benchmark, with the serve cell's files and entries added
    and nothing else changed, passes the checks that the benchmark's own
    tests make over every cell: no test has to change for a model cell."""
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    for d in json.loads((ROOT / "BENCHMARK.json").read_text())["paths"]:
        shutil.copytree(ROOT / d, tmp_path / d,
                        ignore=shutil.ignore_patterns("__pycache__"))
    before = {p: p.read_bytes() for p in (tmp_path / "chipbench").rglob("*")
              if p.is_file()}
    write_serve_files(tmp_path / "chipbench")
    bench = json.loads((tmp_path / "BENCHMARK.json").read_text())
    bench["configs"].append(SERVE_CONFIG)
    bench["workloads"].append(SERVE_CELL)
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    assert all(p.read_bytes() == b for p, b in before.items())

    check_benchmark(tmp_path)
    cells = [w["name"] for w in bench["workloads"]]
    tables = [c for c in cells
              if hasattr(harness.load_cell(c, root=tmp_path).op,
                         "input_shapes")]
    assert "serve-toy" in cells and "serve-toy" not in tables
    assert len(tables) == len(cells) - 1
    for c in cells:
        check_cell(c, tmp_path)
    for c in tables:
        check_table_cell(c, tmp_path)
    serve = harness.load_cell("serve-toy", root=tmp_path)
    assert {m["name"] for m, _ in serve.per_layer} >= {"dispatch_wait_ms",
                                                    "op_mfu"}


def shifted(operation):
    """The operation with every served token id moved on by one."""
    def broken(session, state):
        uids, out, facts = operation(session, state)
        vocab = state["cfg"].vocab_size
        return uids, {u: (p, (s + 1) % vocab) for u, (p, s) in out.items()}, facts
    return broken


@pytest.mark.parametrize("fault", [None, "shifted"])
def test_a_serve_cell_of_new_files_runs_through_the_harness(serve_root, fault):
    cell = harness.load_cell("serve-toy", root=serve_root)
    assert cell.op.__file__.startswith(str(serve_root))
    if fault:
        cell.op.operation = shifted(cell.op.operation)
    r = harness.run_cell(cell, 2**31 + 17, 0.0, False, 0.0)
    assert r["attempted"] == 1 and r["failed"] == 0
    assert set(r["checks"]) == {"ops_failed", "tokens_wrong"}
    assert r["checks"]["ops_failed"]["value"] == 0
    assert {"op_time_s", "setup_s"} <= set(r["metrics"])
    if fault:
        assert r["correct"] is False
        assert r["checks"]["tokens_wrong"]["value"] > 0
    else:
        assert r["correct"] is True
        assert r["checks"]["tokens_wrong"]["value"] == 0


@pytest.mark.parametrize("gap,correct", [(0.004, True), (0.04, False)])
def test_an_op_states_the_limit_of_a_number_it_checks(serve_root, gap,
                                                      correct):
    """A number that ``check`` gives with a limit of its own is held to
    that limit, not to 0: under it the run is correct, over it not."""
    cell = harness.load_cell("serve-toy", root=serve_root)
    exact = cell.op.check
    cell.op.check = lambda *a: {**exact(*a), "logit_gap": (gap, 0.01)}
    r = harness.run_cell(cell, 2**31 + 19, 0.0, False, 0.0)
    assert r["checks"]["logit_gap"] == {"value": gap, "limit": 0.01}
    assert r["checks"]["tokens_wrong"] == {"value": 0, "limit": 0}
    assert r["correct"] is correct


def test_check_holds_the_only_reference_to_the_kept_output(serve_root):
    """By the time ``check`` runs, the harness keeps no reference to the
    kept output: dropping its argument frees it before the reference
    runs."""
    import gc
    import weakref
    cell = harness.load_cell("serve-toy", root=serve_root)
    exact, freed = cell.op.check, []

    class Output(dict):
        pass

    def operation(session, state, _run=cell.op.operation):
        uids, out, facts = _run(session, state)
        return uids, Output(out), facts

    def check(state, kept, ops):
        ref = weakref.ref(kept)
        got = dict(kept)
        kept = None
        gc.collect()
        freed.append(ref() is None)
        return exact(state, got, ops)

    cell.op.operation, cell.op.check = operation, check
    r = harness.run_cell(cell, 2**31 + 23, 0.0, False, 0.0)
    assert freed == [True] and r["correct"] is True
