"""Per-architecture smoke tests (required by the assignment): every one of
the 10 assigned architectures, and the federated benchmark's
granite-4.0-h-micro, instantiates a REDUCED variant (2 layers,
d_model<=512, <=4 experts) and runs one forward/train step + one decode step
on CPU, asserting output shapes and no NaNs."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.configs.base import InputShape
from repro.configs.registry import REGISTRY, get_config, list_archs
from repro.launch.specs import concrete_inputs, input_specs, variant_for_shape
from repro.models import lm

SMALL_TRAIN = InputShape("t", 32, 2, "train")
SMALL_DECODE = InputShape("d", 48, 2, "decode")

ARCHS = list_archs()


def test_registry_has_all_ten():
    """The ten assigned architectures plus granite-4.0-h-micro."""
    assert len(ARCHS) == 11
    assert "granite-4.0-h-micro" in ARCHS
    families = {REGISTRY[a].family for a in ARCHS}
    assert families == {"dense", "moe", "ssm", "hybrid", "vlm", "audio"}


@pytest.mark.parametrize("arch", ARCHS)
def test_reduced_constraints(arch):
    r = get_config(arch).reduced()
    assert r.n_layers == 2
    assert r.d_model <= 512
    if r.moe is not None:
        assert r.moe.num_experts <= 4


@pytest.fixture(scope="module")
def params_cache():
    cache = {}

    def get(arch):
        if arch not in cache:
            cfg = get_config(arch).reduced()
            cache[arch] = (cfg, lm.init_params(jax.random.PRNGKey(0), cfg))
        return cache[arch]

    return get


@pytest.mark.parametrize("arch", ARCHS)
def test_train_step_smoke(arch, params_cache):
    cfg, params = params_cache(arch)
    batch = concrete_inputs(cfg, SMALL_TRAIN)["batch"]
    loss, grads = jax.value_and_grad(
        lambda p: lm.train_loss(p, cfg, batch, remat=False))(params)
    assert np.isfinite(float(loss))
    gnorm = sum(float(jnp.sum(jnp.square(g.astype(jnp.float32))))
                for g in jax.tree_util.tree_leaves(grads))
    assert np.isfinite(gnorm) and gnorm > 0


@pytest.mark.parametrize("arch", ARCHS)
def test_decode_step_smoke(arch, params_cache):
    cfg, params = params_cache(arch)
    di = concrete_inputs(cfg, SMALL_DECODE)
    logits, cache = lm.decode_step(params, cfg, di["tokens"], di["cache"])
    assert logits.shape == (2, cfg.padded_vocab)
    assert np.all(np.isfinite(np.asarray(logits, np.float32)))
    assert int(cache["len"]) == int(di["cache"]["len"]) + 1


@pytest.mark.parametrize("arch", ARCHS)
def test_prefill_then_decode_matches_forward(arch, params_cache):
    """Teacher-forced consistency: prefill(t tokens) then decode(token t) must
    equal prefill(t+1 tokens)'s last-position logits."""
    import dataclasses
    from repro.configs.base import MoEConfig
    cfg, params = params_cache(arch)
    if cfg.moe is not None:
        # ample capacity: token-drop patterns depend on the dispatch pool size,
        # which legitimately differs between prefill(t) and prefill(t+1)
        cfg = dataclasses.replace(cfg, moe=MoEConfig(
            num_experts=cfg.moe.num_experts, top_k=cfg.moe.top_k,
            capacity_factor=16.0))
    shape = InputShape("p", 17, 2, "prefill")
    batch = concrete_inputs(cfg, shape)["batch"]
    toks = batch["tokens"]

    full = dict(batch)
    logits_full, _ = lm.prefill(params, cfg, full)

    part = dict(batch)
    part["tokens"] = toks[:, :-1]
    logits_part, cache = lm.prefill(params, cfg, part)
    # grow cache by one slot for the decoded token
    def grow(k, x):
        if k in ("k", "v") and x.ndim == 5:
            pad = [(0, 0)] * 5
            pad[2] = (0, 1)
            return jnp.pad(x, pad)
        return x
    cache = {k: grow(k, v) for k, v in cache.items()}
    logits_step, _ = lm.decode_step(params, cfg, toks[:, -1], cache)

    np.testing.assert_allclose(
        np.asarray(logits_full, np.float32), np.asarray(logits_step, np.float32),
        atol=5e-2 if cfg.dtype == "bfloat16" else 2e-3, rtol=2e-2)


@pytest.mark.parametrize("arch", ARCHS)
def test_full_config_abstract_params(arch):
    """The FULL configs are exercised via eval_shape only (no allocation)."""
    from repro.launch.specs import abstract_params
    cfg = get_config(arch)
    tree = abstract_params(cfg)
    leaves = jax.tree_util.tree_leaves(tree)
    assert all(hasattr(l, "shape") for l in leaves)
    total = sum(int(np.prod(l.shape)) for l in leaves)
    # analytic param_count agrees with the real pytree within 2%
    assert abs(total - cfg.param_count()) / cfg.param_count() < 0.02


@pytest.mark.parametrize("arch", ARCHS)
@pytest.mark.parametrize("shape_name", ["train_4k", "prefill_32k",
                                        "decode_32k", "long_500k"])
def test_input_specs_cover_all_pairs(arch, shape_name):
    from repro.configs.base import INPUT_SHAPES
    shape = INPUT_SHAPES[shape_name]
    cfg = variant_for_shape(get_config(arch), shape)
    if shape.name == "long_500k":
        assert cfg.attention in ("sliding_window", "none")
    specs = input_specs(cfg, shape)
    if shape.kind == "decode":
        assert specs["tokens"].shape == (shape.global_batch,)
        if cfg.attention != "none":
            assert specs["cache"]["k"].shape[2] == shape.seq_len
    else:
        total = specs["batch"]["tokens"].shape[1] + (
            cfg.n_image_tokens if cfg.family == "vlm" else 0)
        assert total == shape.seq_len
