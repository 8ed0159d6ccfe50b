"""A configuration, a traffic mix and a per-layer metric are added by new
files and a BENCHMARK.json entry alone: the harness finds them by name."""
import json
import os
import shutil

from chipbench.tests.conftest import run_cell


def test_new_config_mix_and_metric_from_files_alone(tiny_root):
    cb = os.path.join(tiny_root, "chipbench")
    shutil.copy(os.path.join(cb, "configs", "fedgs-cifar10.json"),
                os.path.join(cb, "configs", "tiny-net.json"))
    with open(os.path.join(cb, "traffic", "table1-static.json")) as f:
        mix = json.load(f)
    mix["availability"]["modes"] = ["IDL", "LN"]
    mix["seeds_per_mode"] = 1
    with open(os.path.join(cb, "traffic", "two-modes.json"), "w") as f:
        json.dump(mix, f)
    with open(os.path.join(cb, "metrics", "traced_segments.py"), "w") as f:
        f.write("def read(ctx):\n    return ctx['segments']\n")
    shutil.copy(os.path.join(cb, "limits", "paper-fedgs-static.json"),
                os.path.join(cb, "limits", "tiny-net.two-modes.json"))
    path = os.path.join(tiny_root, "BENCHMARK.json")
    with open(path) as f:
        bench = json.load(f)
    bench["configs"].append({
        "name": "tiny-net", "source": "https://arxiv.org/abs/2211.13975",
        "file": "chipbench/configs/tiny-net.json", "reduced": [],
        "why": "test"})
    bench["workloads"].append({
        "name": "tiny-net.two-modes", "config": "tiny-net",
        "traffic": "two-modes", "chips": 1, "why": "test"})
    bench["per_layer"].append({
        "name": "traced_segments", "unit": "segments", "better": "higher",
        "source": "program_counter", "layer": "Entry",
        "moves": "cell_rounds_per_s", "workloads": ["tiny-net.two-modes"]})
    with open(path, "w") as f:
        json.dump(bench, f)

    res = run_cell(tiny_root, "tiny-net.two-modes", trace=1)
    assert "traced_segments" in res["metrics"]
    assert res["correct"] is True
    res = run_cell(tiny_root, "tiny-net.two-modes", trace=0)
    assert set(res["metrics"]) == {"cell_rounds_per_s", "segment_p90_ms",
                                   "setup_s"}
    assert res["metrics"]["cell_rounds_per_s"]["value"] > 0
