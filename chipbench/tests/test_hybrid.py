"""The hybrid LM cell's benchmark files: its FLOP and parameter count, the
model adapter's flat leaves and token map, the layer-part readers, and the
control and planted faults failing the cell's limits at a small size."""
import json
import os

import numpy as np
import pytest

from chipbench import flops_hybrid, scopes
from chipbench.run import load_module
from chipbench.tests.conftest import REPO, make_root

CONFIG = os.path.join(REPO, "chipbench", "configs",
                      "granite-4.0-h-micro-xsilo.json")
WORKLOAD = "xsilo-granite-h-micro"
# a size the CPU runs in seconds: every width cut, one layer of each kind
TINY = {"hidden_size": 64, "num_attention_heads": 4,
        "num_key_value_heads": 2, "mamba_d_head": 16, "mamba_n_heads": 8,
        "mamba_d_state": 8, "mamba_chunk_size": 8, "intermediate_size": 128,
        "vocab_size": 128, "num_hidden_layers": 2,
        "layer_types": ["mamba", "attention"], "n_clients": 6,
        "n_total": 400, "image_shape": [17], "val_frac": 0.02,
        "batch_size": 3, "rounds": 4}


@pytest.fixture(scope="module")
def cfg():
    with open(CONFIG) as f:
        return json.load(f)


def module(rel, name):
    return load_module(os.path.join(REPO, "chipbench", rel), name)


def test_param_count_from_shapes(cfg):
    import jax
    assert flops_hybrid.param_count(cfg) == 797850560 == cfg["params"]
    model = module("models/granite_hybrid.py", "adapter").make(cfg, 10)
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    assert sum(int(np.prod(a.shape)) for a in shapes.values()) == 797850560
    assert cfg["memory"]["param_copy_bytes"] == 4 * 797850560


def test_flops_per_round(cfg):
    # 6 x matrix params x tokens of training dominates: 2 x 2 x 16 x 512
    # tokens x 797,573,120 matrix params (tied head included)
    train = 6 * 797573120 * 2 * 2 * 16 * 512
    f = flops_hybrid.model_flops_per_cell_round(cfg, {}, 32)
    assert train < f < 1.15 * train


def test_one_leaf_per_weight_per_layer(cfg):
    import jax
    model = module("models/granite_hybrid.py", "adapter").make(cfg, 10)
    names = set(jax.eval_shape(model.init, jax.random.PRNGKey(0)))
    per = {"mamba": {"norm", "w_z", "w_x", "w_B", "w_C", "w_dt", "dt_bias",
                     "A_log", "D", "conv_w", "conv_b", "gate_norm", "w_out"},
           "attention": {"norm", "wq", "wk", "wv", "wo"},
           "mlp": {"norm", "w_in", "w_gate", "w_out"}}
    want = {"embed", "final_norm"}
    for i, kind in enumerate(cfg["layer_types"]):
        for part in (kind, "mlp"):
            want |= {f"layers.{i}.{part}.{w}" for w in per[part]}
    assert names == want
    ref = module("references/granite_hybrid.py", "ref")
    assert set(jax.eval_shape(lambda k: ref.init_params(k, cfg),
                              jax.random.PRNGKey(0))) == want


def test_token_map(cfg):
    import jax.numpy as jnp
    v = cfg["vocab_size"]
    adapter = module("models/granite_hybrid.py", "adapter")
    ref = module("references/granite_hybrid.py", "ref")
    x = jnp.asarray([-50.0, -1.0, 0.0, 1e-3, 1.0, 50.0])
    ids = np.asarray(adapter.tokens(x, v))
    np.testing.assert_array_equal(ids, np.asarray(ref.token_ids(x, v)))
    assert ids[0] == 0 and ids[-1] == v - 1 and ids[2] == v // 2
    assert np.all(np.diff(ids) >= 0)
    r = np.random.default_rng(0).normal(size=20000)
    hist = np.bincount(np.asarray(adapter.tokens(jnp.asarray(r), v)),
                       minlength=v)
    assert hist.max() < 10 * 20000 / v       # spread over the vocabulary


def test_part_of_reads_through_transforms():
    assert scopes.part_of("jit(f)/while/body/local_train/transpose("
                          "jvp(mamba))/dot_general") == "mamba"
    assert scopes.part_of("jit(f)/local_train/jit(train)/accumulate/add") \
        == "accumulate"
    assert scopes.part_of("jit(f)/eval/cond/branch_1_fun/mlp/dot") == "mlp"
    assert scopes.part_of("jit(f)/local_train/jvp()/checkpoint") is None


HLO = """HloModule jit_run_segment

%body (p: f32[4]) -> f32[4] {
  %p = f32[4] parameter(0)
  %a = f32[4] add(%p, %p), metadata={op_name="jit(run_segment)/while/body/local_train/jvp(attention)/add"}
  ROOT %c = f32[4] copy(%a)
}

ENTRY %main (x: f32[4]) -> f32[4] {
  %x = f32[4] parameter(0)
  %m = f32[4] multiply(%x, %x), metadata={op_name="jit(run_segment)/local_train/transpose(jvp(mlp))/mul"}
  ROOT %w = f32[4] call(%m), to_apply=%body, metadata={op_name="jit(run_segment)/local_train/attention/call"}
}
"""


def test_module_parts_and_readers():
    parts = scopes.module_parts(HLO)
    assert parts["a"] == "attention" and parts["m"] == "mlp"
    assert parts["c"] == "attention"        # the caller's part
    ctx = {"ops": [["a", 0.0, 3e6], ["m", 0.0, 1e6], ["c", 0.0, 2e6],
                   ["x.9", 0.0, 5e6]], "segments": 2, "seg": 2,
           scopes.KEY: {k: v for k, v in parts.items() if v}}
    assert module("metrics/attention_ms_per_round.py", "att").read(ctx) \
        == pytest.approx(5.0 / 4)
    assert module("metrics/mlp_ms_per_round.py", "mlp").read(ctx) \
        == pytest.approx(1.0 / 4)
    assert module("metrics/mamba_ms_per_round.py", "mam").read(ctx) == 0
    assert module("metrics/accumulate_ms_per_round.py", "acc").read(
        dict(ctx, segments=0)) is None


def test_hybrid_step_mfu(cfg):
    from chipbench import peaks
    ctx = {"ops": [["x", 0.0, 1.0]], "segments": 4, "seg": 2, "cells": 1,
           "window_s": 8.0, "chips": 1, "cfg": cfg, "traffic": {},
           "n_val": 32, "peaks": peaks.chip_peaks("TPU v5 lite")}
    f = flops_hybrid.model_flops_per_cell_round(cfg, {}, 32)
    got = module("metrics/hybrid_step_mfu.py", "mfu").read(ctx)
    assert got == pytest.approx(100 * 8 * f / 8.0 / 197e12)
    assert module("metrics/hybrid_step_mfu.py", "mfu").read(
        dict(ctx, peaks=None)) is None


def test_control_and_planted_faults_fail_the_limits(tmp_path):
    """At a small size on the CPU the program reads far under the cell's
    delta_gap limit, and the bfloat16 control and each planted fault
    (half a batch, the attention layer skipped, the gated norm dropped,
    the residual multiplier at 1.0) over it; the program rebuilt with its
    SSD contractions at highest precision reads under it too."""
    root = make_root(str(tmp_path / "bench"))
    with open(CONFIG) as f:
        tiny = json.load(f)
    tiny.update(TINY)
    with open(os.path.join(root, "chipbench", "configs",
                           os.path.basename(CONFIG)), "w") as f:
        json.dump(tiny, f)
    out = str(tmp_path / "readings.json")
    from chipbench import calibrate_hybrid
    assert calibrate_hybrid.main([
        "--workload", WORKLOAD, "--seeds", "3000000111",
        "--ssd-highest-seeds", "3000000111", "--control-seeds", "3000000222", "--allow-cpu", "--root", root,
        "--out", out]) == 0
    with open(out) as f:
        got = json.load(f)
    with open(os.path.join(REPO, "chipbench", "limits",
                           f"{WORKLOAD}.json")) as f:
        lim = json.load(f)["limits"]["delta_gap"]
    assert got["program"]["3000000111"]["delta_gap"] < lim / 100
    assert got["ssd_highest"]["3000000111"]["delta_gap"] < lim / 100
    for kind in ("control", "half", "skip_attention", "no_gated_norm",
                 "residual_1"):
        assert got[kind]["3000000222"]["delta_gap"] > lim, kind
