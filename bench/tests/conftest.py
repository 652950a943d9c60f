"""Shared helpers of the benchmark's own tests (``python -m pytest
bench/tests``, on the CPU).  ``tiny_root`` builds a checkout in a
temporary directory: ``BENCHMARK.json`` and ``bench/`` copied, ``src``
linked, and one cell's configuration and traffic cut to a size a test
run holds.  The cut changes sizes only; the code path is the cell's."""
from __future__ import annotations

import json
import os
import shutil
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parent.parent
ROOT = BENCH.parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(ROOT / "src"))
os.environ.setdefault("JAX_PLATFORMS", "cpu")

TINY = {
    "feel_mlp": {"n_train": 1200, "n_test": 200, "input_dim": 64,
                 "hidden": 32},
}
TINY_TRAFFIC = {"seeds_per_spec": 1, "periods": 3, "reference_rows": 8}
# a toy Mamba-2 language model on token text, for the CPU
TOY_LM = {"model_family": "mamba2", "hidden": 16, "expand": 2, "d_state": 4,
          "head_dim": 4, "n_groups": 2, "d_conv": 4, "vocab": 40,
          "seq_len": 10, "depth": 2, "n_train": 24, "n_test": 12,
          "data": {"kind": "markov_tokens", "topics": 2, "branch": 3,
                   "zipf": 1.0}}


def make_root(tmp: Path, cell: str, cfg_over=None, traffic_over=None):
    shutil.copy(ROOT / "BENCHMARK.json", tmp)
    shutil.copytree(BENCH, tmp / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "data"))
    (tmp / "src").symlink_to(ROOT / "src")
    bench = json.loads((tmp / "BENCHMARK.json").read_text())
    w = {x["name"]: x for x in bench["workloads"]}[cell]
    cfg_file = tmp / {c["name"]: c for c in bench["configs"]}[
        w["config"]]["file"]
    cfg = json.loads(cfg_file.read_text())
    cfg.update(TINY[cfg["model_family"]], **(cfg_over or {}))
    cfg_file.write_text(json.dumps(cfg))
    tr_file = tmp / "bench" / "traffic" / f"{w['traffic']}.json"
    tr = json.loads(tr_file.read_text())
    tr.update(TINY_TRAFFIC, **(traffic_over or {}))
    tr_file.write_text(json.dumps(tr))
    return tmp


def add_cell(root: Path, name: str, config: dict, traffic: dict,
             limits: dict):
    """Register a cell in a copied checkout the way a later PR would: a
    configuration file where the configuration is new, a traffic file, a
    limits file and the ``BENCHMARK.json`` entries.  No code is edited."""
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cfg_name, traffic_name = name.split(".", 1)
    cfg_file = f"bench/configs/{cfg_name}.json"
    (root / "bench" / "traffic" / f"{traffic_name}.json").write_text(
        json.dumps(traffic))
    (root / "bench" / "limits" / f"{name}.json").write_text(
        json.dumps(limits))
    if cfg_name not in {c["name"] for c in bench["configs"]}:
        (root / cfg_file).write_text(json.dumps(config))
        bench["configs"].append({"name": cfg_name, "source": "test",
                                 "file": cfg_file, "reduced": [],
                                 "why": "test"})
    bench["workloads"].append({"name": name, "config": cfg_name,
                               "traffic": traffic_name, "chips": 1,
                               "why": "test"})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root


@pytest.fixture
def tiny_root(tmp_path):
    return lambda cell, **kw: make_root(tmp_path, cell, **kw)
