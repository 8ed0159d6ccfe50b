"""The hybrid Mamba-2/attention LM a configuration describes (its keys are
those of the published ``config.json``), handed to the program as its
model: the program's own ``repro.fed.models.language_model`` over the
``ArchConfig`` these keys give.

Each data row of ``seq_len + 1`` values ``x`` becomes the token ids
``floor(Phi(x) * V)`` (``Phi`` the standard normal CDF, clipped to V-1),
read as one document.  The parameters are handed to the program as a flat
dict, one leaf per weight per layer (``layers.<i>.<part>.<weight>``), so
the check's per-leaf comparison names the layer and weight it finds at
fault."""
from __future__ import annotations

import jax
import jax.numpy as jnp


def arch_of(cfg: dict):
    """The program's ``ArchConfig`` of the configuration's keys."""
    from repro.configs.base import ArchConfig, SSMConfig
    d, heads = cfg["hidden_size"], cfg["num_attention_heads"]
    n = cfg["num_hidden_layers"]
    assert len(cfg["layer_types"]) == n and cfg["mamba_n_groups"] == 1
    assert cfg["mamba_n_heads"] * cfg["mamba_d_head"] == \
        cfg["mamba_expand"] * d
    assert cfg["hidden_act"] == "silu" and not cfg["attention_bias"]
    assert not cfg["mamba_proj_bias"] and cfg["num_local_experts"] == 0
    return ArchConfig(
        name=cfg["name"], family="hybrid", source=cfg["source"], n_layers=n,
        d_model=d, n_heads=heads, n_kv_heads=cfg["num_key_value_heads"],
        head_dim=d // heads, d_ff=cfg["intermediate_size"],
        vocab_size=cfg["vocab_size"], ffn_kind="swiglu", attention="full",
        position_embedding=cfg["position_embedding_type"],
        attn_scale=cfg["attention_multiplier"],
        layer_types=tuple(cfg["layer_types"]),
        embedding_multiplier=float(cfg["embedding_multiplier"]),
        residual_multiplier=float(cfg["residual_multiplier"]),
        logits_scaling=float(cfg["logits_scaling"]),
        ssm=SSMConfig(d_state=cfg["mamba_d_state"],
                      head_dim=cfg["mamba_d_head"],
                      expand=cfg["mamba_expand"],
                      chunk=cfg["mamba_chunk_size"],
                      d_conv=cfg["mamba_d_conv"],
                      conv_bias=cfg["mamba_conv_bias"], gated_norm=True),
        tie_embeddings=cfg["tie_word_embeddings"],
        norm_eps=cfg["rms_norm_eps"], dtype="float32")


def tokens(x, vocab: int):
    """Data values -> token ids: floor(Phi(x) * V), clipped to [0, V-1]."""
    u = jax.scipy.special.ndtr(x.astype(jnp.float32))
    return jnp.clip(jnp.floor(u * vocab), 0, vocab - 1).astype(jnp.int32)


def flatten(tree, prefix: str = "") -> dict:
    """Nested dicts and lists -> {"a.0.b": leaf}."""
    if isinstance(tree, dict):
        items = tree.items()
    elif isinstance(tree, (list, tuple)):
        items = enumerate(tree)
    else:
        return {prefix[:-1]: tree}
    out = {}
    for k, v in items:
        out.update(flatten(v, f"{prefix}{k}."))
    return out


def make(cfg: dict, classes: int):
    from repro.fed.models import FedModel, language_model
    arch = arch_of(cfg)
    lm = language_model(arch)
    template = jax.eval_shape(lm.init, jax.random.PRNGKey(0))
    leaves, treedef = jax.tree_util.tree_flatten(template)
    names = list(flatten(jax.tree_util.tree_unflatten(
        treedef, list(range(len(leaves))))).items())
    order = [name for name, _ in sorted(names, key=lambda kv: kv[1])]

    def nested(p):
        return jax.tree_util.tree_unflatten(treedef,
                                            [p[name] for name in order])

    def tok(x):
        return tokens(x, arch.vocab_size)

    return FedModel(
        init=lambda rng: flatten(lm.init(rng)),
        loss=lambda p, x, y: lm.loss(nested(p), tok(x), y),
        accuracy=lambda p, x, y: lm.accuracy(nested(p), tok(x), y),
        embed=lambda p, x: lm.embed(nested(p), tok(x)))
