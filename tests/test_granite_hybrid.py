"""granite-4.0-h-micro's mixed Mamba-2/attention stack, reduced, against
the benchmark's plain reference (``chipbench/references/granite_hybrid.py``,
which imports nothing of the program) on the same seeded weights.

* the program's init and the reference's give the same weights, leaf by
  leaf, under the same names;
* the loss and every gradient leaf agree to float32 round-off.  The
  program's SSD is the chunked scan, the reference's the quadratic (dual)
  form, and the two attention and loss paths order their sums
  differently, so the results differ by the round-off of a few hundred
  float32 additions: rtol 1e-5 on the loss, and on each gradient leaf
  1e-4 of its largest entry;
* the per-layer recompute does not change the loss or its gradients.
"""
import dataclasses
import importlib.util
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.configs.registry import get_config
from repro.models import lm

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def load(rel, name):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(ROOT, rel))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def refmod():
    return load("chipbench/references/granite_hybrid.py", "granite_ref")


@pytest.fixture(scope="module")
def adapter():
    return load("chipbench/models/granite_hybrid.py", "granite_adapter")


def small_arch():
    """The reduced config at chunk 8 over 32 positions (four SSD chunks),
    three layers: mamba, attention, mamba; heads of d_model / n_heads, as
    the published config has them."""
    a = get_config("granite-4.0-h-micro").reduced()
    return dataclasses.replace(
        a, n_layers=3, layer_types=("mamba", "attention", "mamba"),
        head_dim=a.d_model // a.n_heads,
        vocab_size=384, ssm=dataclasses.replace(a.ssm, chunk=8))


def hf_keys(arch) -> dict:
    """The published config.json keys of an ArchConfig (the reference's
    and the benchmark adapter's input)."""
    s = arch.ssm
    return {"name": arch.name, "source": arch.source,
            "hidden_size": arch.d_model,
            "num_attention_heads": arch.n_heads,
            "num_key_value_heads": arch.n_kv_heads,
            "intermediate_size": arch.d_ff, "vocab_size": arch.vocab_size,
            "num_hidden_layers": arch.n_layers,
            "layer_types": list(arch.layer_types),
            "mamba_expand": s.expand, "mamba_d_state": s.d_state,
            "mamba_d_head": s.head_dim,
            "mamba_n_heads": s.expand * arch.d_model // s.head_dim,
            "mamba_n_groups": 1, "mamba_d_conv": s.d_conv,
            "mamba_chunk_size": s.chunk, "mamba_conv_bias": s.conv_bias,
            "mamba_proj_bias": False, "attention_bias": False,
            "hidden_act": "silu", "num_local_experts": 0,
            "position_embedding_type": arch.position_embedding,
            "attention_multiplier": arch.attn_scale,
            "embedding_multiplier": arch.embedding_multiplier,
            "residual_multiplier": arch.residual_multiplier,
            "logits_scaling": arch.logits_scaling,
            "tie_word_embeddings": arch.tie_embeddings,
            "rms_norm_eps": arch.norm_eps}


def test_adapter_rebuilds_the_arch(adapter):
    arch = small_arch()        # window: unused by full attention
    assert adapter.arch_of(hf_keys(arch)) == dataclasses.replace(
        arch, window=4096)


def test_published_widths_and_count():
    cfg = get_config("granite-4.0-h-micro")
    assert cfg.layer_types.count("attention") == 4
    assert [i for i, k in enumerate(cfg.layer_types)
            if k == "attention"] == [5, 15, 25, 35]
    assert cfg.param_count() == 3191396096
    one = dataclasses.replace(cfg, n_layers=10, layer_types=cfg.layer_types[:10],
                              vocab_size=25088)
    assert one.param_count() == 797850560


@pytest.fixture(scope="module")
def setup(refmod, adapter):
    arch = small_arch()
    cfg = hf_keys(arch)
    params = lm.init_params(jax.random.PRNGKey(7), arch)
    ids = jax.random.randint(jax.random.PRNGKey(8), (3, 33), 0,
                             arch.vocab_size)
    return arch, cfg, params, ids


def test_init_matches_reference(setup, refmod, adapter):
    arch, cfg, params, _ = setup
    mine = adapter.flatten(params)
    ref = refmod.init_params(jax.random.PRNGKey(7), cfg)
    assert set(mine) == set(ref)
    for k in ref:
        np.testing.assert_array_equal(np.asarray(mine[k]),
                                      np.asarray(ref[k]), err_msg=k)


def ref_loss(refmod, cfg, flat, ids):
    kn = refmod.knobs_of(cfg)
    lg = refmod.logits_of(cfg, flat,
                          refmod.hidden(cfg, flat, ids[:, :-1], kn))
    logp = jax.nn.log_softmax(lg, axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, ids[:, 1:, None], -1))


@pytest.mark.parametrize("remat", [False, True])
def test_loss_and_grads_match_reference(setup, refmod, adapter, remat):
    arch, cfg, params, ids = setup
    batch = {"tokens": ids[:, :-1], "labels": ids[:, 1:]}
    loss, g = jax.value_and_grad(
        lambda p: lm.train_loss(p, arch, batch, remat=remat))(params)
    flat = adapter.flatten(params)
    rloss, rg = jax.value_and_grad(
        lambda p: ref_loss(refmod, cfg, p, ids))(flat)
    np.testing.assert_allclose(float(loss), float(rloss), rtol=1e-5)
    g = adapter.flatten(g)
    for k in rg:
        want = np.asarray(rg[k])
        np.testing.assert_allclose(np.asarray(g[k]), want, rtol=0,
                                   atol=1e-4 * np.abs(want).max() + 1e-12,
                                   err_msg=k)


def test_accuracy_and_embed(setup):
    arch, _, params, ids = setup
    from repro.fed.models import language_model
    fm = language_model(arch)
    acc = float(fm.accuracy(params, ids, None))
    hid = lm.mixed_hidden(params, arch, ids[:, :-1])
    logits = lm._lm_logits(params, arch, hid)
    want = float(jnp.mean(jnp.argmax(logits, -1) == ids[:, 1:]))
    assert acc == pytest.approx(want)
    assert fm.embed(params, ids).shape == (3, arch.d_model)


def test_scan_engine_run_matches_reference_replay(monkeypatch, refmod):
    """The benchmark cell's first segment, at a small size and forced onto
    the client-by-client path, against the reference's replay on the
    program's sets, through the benchmark's own pieces."""
    import json

    from chipbench import check, run
    from chipbench.data import make_dataset
    from chipbench.generator import (
        cell_specs, engine_cells, engine_config, fed_dataset, fed_model,
        mode_tables,
    )
    from repro.fed import scan_engine
    cell = run.load_cell(ROOT, "xsilo-granite-h-micro")
    cfg, traffic = cell["cfg"], cell["traffic"]
    cfg.update({"hidden_size": 64, "num_attention_heads": 4,
                "num_key_value_heads": 2, "mamba_d_head": 16,
                "mamba_n_heads": 8, "mamba_d_state": 8,
                "mamba_chunk_size": 8, "intermediate_size": 128,
                "vocab_size": 128, "num_hidden_layers": 2,
                "layer_types": ["mamba", "attention"], "n_clients": 6,
                "n_total": 400, "image_shape": [17], "val_frac": 0.02,
                "batch_size": 3, "rounds": 4})
    monkeypatch.setattr(scan_engine, "device_memory_bytes", lambda: 1)
    ds = make_dataset(cfg)
    tables = mode_tables(traffic, ds)
    g = traffic["graph"]
    h = np.asarray(refmod.build_h(jnp.asarray(ds.label_dist, jnp.float32),
                                  functional=False, eps=g["eps"],
                                  sigma2=g["sigma2"]))
    eng = scan_engine.ScanEngine(fed_dataset(ds), fed_model(ROOT, cfg, ds),
                                 engine_config(cfg, traffic), use_masks=True)
    specs = cell_specs(cfg, traffic, ds, 3000000321, 0, tables)
    seg = traffic["segment_rounds"]
    first = run.first_segment(eng, engine_cells(eng, traffic, specs, h), seg,
                              False)
    assert eng.runtime_stats()["local_train_seq"]["clients"] == cfg["m"]
    ref = refmod.Reference(cfg, traffic, ds)
    got = check.compare(first, [
        ref.replay(sp, seg, sets=p["sets"], h_static=h)
        for sp, p in zip(specs, first)], False)
    assert got["set_gap"] == 0.0
    assert got["delta_gap"] < 1e-4 and got["loss_gap_all"] < 1e-4
    assert json.loads(json.dumps(got))        # plain numbers
