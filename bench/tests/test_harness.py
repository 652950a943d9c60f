"""Cell discovery by name, the client-period count, and the runs that
must fail without printing a result."""
import hashlib
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

import reference
import workload
from conftest import BENCH, ROOT, TINY, TINY_TRAFFIC, TOY_LM, add_cell


def test_every_workload_resolves():
    bench = workload.load_benchmark(ROOT)
    for w in bench["workloads"]:
        cell = workload.find_cell(ROOT, w["name"])
        assert cell.chips == w["chips"]
        assert {m["name"] for m in cell.end_to_end} >= {"setup_s"}
        assert cell.per_layer


def _digests(root):
    return {p.relative_to(root): hashlib.sha256(p.read_bytes()).hexdigest()
            for p in sorted(root.rglob("*")) if p.is_file()}


def _mlp_case(tmp_path, tr):
    tr.update(partitions=["iid"], policies=["full"], periods=7)
    cfg = json.loads((BENCH / "configs" / "feel_mlp.json").read_text())
    return "feel_mlp.gpu6_iid_full", cfg, {"loss_gap": 1, "ledger_faults": 0}


def _language_model_case(tmp_path, tr):
    """A toy language model with a reference file of its own, token text
    and ScenarioSpec keywords passed through ``spec``."""
    models = tmp_path / "bench" / "models"
    (models / "toy_lm.py").write_text((models / "mamba2.py").read_text())
    tr.update(TINY_TRAFFIC, partitions=["noniid"], policies=["full"])
    cfg = dict(TOY_LM, reference="toy_lm", compression=0.01, b_max=4,
               lr_ref_batch=4, spec={"local_steps": 1, "scheme": "feel"})
    return "toy_lm.gpu6_full", cfg, {"loss_gap_median": 0.01,
                                     "ledger_faults": 0}


@pytest.mark.parametrize("case", [_mlp_case, _language_model_case],
                         ids=["mlp", "language_model"])
def test_new_files_add_a_cell(tmp_path, case):
    """A cell is files plus BENCHMARK.json entries: no code is edited."""
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = _digests(tmp_path)
    tr = json.loads((BENCH / "traffic" / "fig45_gpu6.json").read_text())
    name, cfg, limits = case(tmp_path, tr)
    add_cell(tmp_path, name, cfg, tr, limits)
    after = _digests(tmp_path)
    assert {p for p in before if before[p] != after[p]} == {
        Path("BENCHMARK.json")}
    with pytest.raises(workload.CellError):
        workload.find_cell(ROOT, name)
    cell = workload.find_cell(tmp_path, name, tmp_path / "bench")
    assert cell.traffic["periods"] == tr["periods"]
    assert cell.config == cfg
    (spec,) = workload.make_specs(cell.config, cell.traffic, 3)
    assert (spec.partition, spec.policy, spec.k) == (
        tr["partitions"][0], "full", 6)
    assert spec.model_family == cfg["model_family"]
    models = tmp_path / "bench" / "models"
    model = reference.config_module(cell.config, models)
    ref = cfg.get("reference", cfg["model_family"])
    assert model.__file__ == str(models / f"{ref}.py")
    if "spec" in cfg:
        assert (spec.local_steps, spec.scheme) == (1, "feel")
        train, _ = workload.make_data(cell.config, 2**35 + 1)
        assert train.x.shape == (24, 11) and train.x.dtype == np.int32
        tok, lab = model.train_inputs(cell.config, train)
        np.testing.assert_array_equal(tok[:, 1:], lab[:, :-1])


def test_seeds_are_large_and_repeatable():
    a = workload.derived_seeds(2**33 + 7, 4, workload.STREAM_ROWS)
    assert a == workload.derived_seeds(2**33 + 7, 4, workload.STREAM_ROWS)
    assert a != workload.derived_seeds(2**33 + 8, 4, workload.STREAM_ROWS)
    assert all(0 <= s < 2**31 for s in a)


@pytest.mark.parametrize("sampling,expect", [(None, 8 * 3),
                                             ({"size": 3}, 3 * 3)])
def test_client_periods_count_participants_only(tiny_root, sampling,
                                                expect):
    """K=8 clients, 3 periods: every lane counts under full
    participation; with a cohort of 3, only the 3 participants do."""
    root = tiny_root("feel_mlp.fig45_gpu6")
    cfg = json.loads((BENCH / "configs" / "feel_mlp.json").read_text())
    cfg.update(TINY["feel_mlp"])
    tr = json.loads((BENCH / "traffic" / "cohort512.json").read_text())
    tr.update(TINY_TRAFFIC, sampling=sampling, fleet={
        "name": "cpu8", "k": 8, "tiers": [
            {"kind": "cpu", "f_cpu": 0.7e9}, {"kind": "cpu",
                                              "f_cpu": 2.1e9}]})
    add_cell(root, "feel_mlp.count", cfg, tr, {"ledger_faults": 0})
    from repro.api import Experiment
    cell = workload.find_cell(root, "feel_mlp.count", root / "bench")
    train, test = workload.make_data(cell.config, 11)
    spans = workload.Spans()
    Experiment(train, test, workload.make_specs(
        cell.config, cell.traffic, 11)).run(
            3, executor=workload.make_executor(cell.traffic["executor"]))
    got = workload.call_counts(spans.records)
    assert got["client_periods"] == expect
    assert got["periods"] == 3
    assert spans.span_s["plan_bucket"] > 0


def _run(cwd, env_extra):
    env = dict(os.environ, **env_extra)
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload",
         "feel_mlp.fig45_gpu6", "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=cwd, env=env, capture_output=True,
        text=True, timeout=300)


def test_no_tpu_fails_without_result():
    p = _run(ROOT, {"JAX_PLATFORMS": "cpu"})
    assert p.returncode == 3
    assert p.stdout.strip() == ""
    assert "needs a TPU" in p.stderr


def test_benchmark_files_alone_fail(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(tmp_path, {"JAX_PLATFORMS": "cpu"})
    assert p.returncode == 2
    assert p.stdout.strip() == ""
