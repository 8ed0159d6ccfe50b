"""Fixtures of the benchmark's CPU tests: a benchmark root in a temporary
directory holding the repository's benchmark files with the configuration
cut to a size the CPU runs in seconds, and beside it a test cell of the
dynamic 3DG, memory aggregation and device-drawn availability paths
(``data/tiny-dyn.json`` under ``data/ln-dyn3dg.json``)."""
from __future__ import annotations

import json
import os
import shutil

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
DATA = os.path.join(REPO, "chipbench", "tests", "data")
TINY = {"fedgs-cifar10": {"n_clients": 16, "n_total": 800, "m": 4,
                          "rounds": 10, "local_steps": 2,
                          "image_shape": [16, 16, 3],
                          "conv": [[3, 8], [3, 8]], "dense": [16]}}
DYN = "tiny-dyn3dg"
DYN_LIMITS = {"set_gap": 1e-2, "loss_gap": 2.5e-3, "delta_gap": 0.03}


def make_root(dst: str) -> str:
    """A benchmark root at ``dst``: the repository's BENCHMARK.json and
    benchmark files, the configurations at the tiny sizes above, the
    dynamic test cell, and the program (``src``) linked in."""
    os.makedirs(os.path.join(dst, "chipbench", "configs"))
    for d in ("traffic", "metrics", "models", "references", "limits"):
        shutil.copytree(os.path.join(REPO, "chipbench", d),
                        os.path.join(dst, "chipbench", d))
    os.symlink(os.path.join(REPO, "src"), os.path.join(dst, "src"))
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    for name, cut in TINY.items():
        with open(os.path.join(REPO, "chipbench", "configs",
                               f"{name}.json")) as f:
            cfg = json.load(f)
        cfg.update(cut)
        with open(os.path.join(dst, "chipbench", "configs",
                               f"{name}.json"), "w") as f:
            json.dump(cfg, f)
    shutil.copy(os.path.join(DATA, "tiny-dyn.json"),
                os.path.join(dst, "chipbench", "configs"))
    shutil.copy(os.path.join(DATA, "ln-dyn3dg.json"),
                os.path.join(dst, "chipbench", "traffic"))
    with open(os.path.join(dst, "chipbench", "limits", f"{DYN}.json"),
              "w") as f:
        json.dump({"limits": DYN_LIMITS}, f)
    bench["configs"].append({
        "name": "tiny-dyn", "source": "https://arxiv.org/abs/2211.13975",
        "file": "chipbench/configs/tiny-dyn.json", "reduced": [],
        "why": "test"})
    bench["workloads"].append({"name": DYN, "config": "tiny-dyn",
                               "traffic": "ln-dyn3dg", "chips": 1,
                               "why": "test"})
    for m in bench["per_layer"]:
        m["workloads"].append(DYN)
    with open(os.path.join(dst, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    return dst


@pytest.fixture
def tiny_root(tmp_path):
    return make_root(str(tmp_path / "bench"))


def run_cell(root: str, workload: str, *, seed: int = 3000000123,
             seconds: float = 1.0, trace: int = 0) -> dict:
    """One run of the harness on the CPU (the look for a chip skipped)."""
    from chipbench import run
    return run.run(["--workload", workload, "--seed", str(seed),
                    "--seconds", str(seconds), "--trace", str(trace)],
                   root=root, require_tpu=False)
