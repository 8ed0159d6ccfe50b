"""The model zoo's unified language model.

One parameterized decoder (optionally with an encoder for enc-dec archs)
covering all six assigned families:

  dense   — GQA attention + (SwiGLU | squared-ReLU) FFN
  moe     — GQA attention + token-choice top-k MoE FFN
  ssm     — Mamba-2/SSD mixer, no FFN
  hybrid  — parallel attention + SSD heads, then FFN (Hymba); or, with
            ``cfg.layer_types``, a mixed stack of Mamba-2 and attention
            layers, each mixer followed by the FFN (granite-4.0-h)
  vlm     — dense/GQA decoder consuming [image-embeddings ; token-embeddings]
  audio   — enc-dec: bidirectional encoder over frame embeddings, causal
            decoder with cross-attention

Parameters are dict pytrees with per-layer leaves **stacked on a leading L
axis**; the forward is ``lax.scan`` over layers (+ ``jax.checkpoint`` remat in
training) so 96-layer models lower as fast as 1-layer models.  A mixed stack
(``cfg.layer_types``) instead keeps one param dict per layer under
``params["layers"]`` and runs the layers in order, each recomputed in the
backward pass in training (full per-layer recompute: only the layer inputs
are saved).  Its ops are named by layer part: ``mamba``,
``attention``, ``mlp`` and ``lm_head`` (embedding, final norm, logits, loss).

Three entry points (used by launch/, fed/, tests/):
  train_loss(params, cfg, batch)                -> scalar loss
  prefill(params, cfg, batch, cache_len)        -> (logits_last, cache)
  decode_step(params, cfg, token_batch, cache)  -> (logits, cache)
"""
from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ArchConfig
from repro.models import attention as attn_mod
from repro.models import ffn as ffn_mod
from repro.models import ssd as ssd_mod
from repro.models.layers import (
    dense_init, embed_init, rms_norm, rope_angles, apply_rope,
    softmax_cross_entropy,
)
from repro.sharding.ctx import shard_act


# ====================================================================== init
def _init_attn(key, cfg: ArchConfig, dtype):
    d, hq, hkv, dh = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    ks = jax.random.split(key, 4)
    return {
        "norm": jnp.ones((d,), jnp.float32),
        "wq": dense_init(ks[0], d, hq * dh, dtype),
        "wk": dense_init(ks[1], d, hkv * dh, dtype),
        "wv": dense_init(ks[2], d, hkv * dh, dtype),
        "wo": dense_init(ks[3], hq * dh, d, dtype),
    }


def _init_block(key, cfg: ArchConfig, dtype, *, cross: bool = False):
    ks = jax.random.split(key, 5)
    p: dict[str, Any] = {}
    if cfg.attention != "none":
        p["attn"] = _init_attn(ks[0], cfg, dtype)
    if cfg.ssm is not None:
        p["ssm"] = {"norm": jnp.ones((cfg.d_model,), jnp.float32),
                    **ssd_mod.init_ssd(ks[1], cfg.d_model, cfg.ssm, dtype)}
    if cross:
        p["cross"] = _init_attn(ks[2], cfg, dtype)
    if cfg.d_ff > 0:
        if cfg.moe is not None:
            p["moe"] = {"norm": jnp.ones((cfg.d_model,), jnp.float32),
                        **ffn_mod.init_moe(ks[3], cfg.d_model, cfg.d_ff,
                                           cfg.moe.num_experts, cfg.ffn_kind, dtype)}
        else:
            p["ffn"] = {"norm": jnp.ones((cfg.d_model,), jnp.float32),
                        **ffn_mod.init_ffn(ks[4], cfg.d_model, cfg.d_ff,
                                           cfg.ffn_kind, dtype)}
    return p


def _init_layer(key, cfg: ArchConfig, kind: str, dtype):
    """One layer of a mixed stack: its mixer under ``kind`` ("mamba" or
    "attention", each with its pre-norm), then the FFN under "mlp"."""
    km, kf = jax.random.split(key)
    d = cfg.d_model
    if kind == "attention":
        mixer = _init_attn(km, cfg, dtype)
    else:
        mixer = {"norm": jnp.ones((d,), jnp.float32),
                 **ssd_mod.init_ssd(km, d, cfg.ssm, dtype)}
    return {kind: mixer,
            "mlp": {"norm": jnp.ones((d,), jnp.float32),
                    **ffn_mod.init_ffn(kf, d, cfg.d_ff, cfg.ffn_kind, dtype)}}


def _init_mixed(key, cfg: ArchConfig, dtype):
    """Key i+1 of ``split(key, L + 1)`` makes layer i; key 0 the embedding
    (and, untied, ``fold_in(key 0, 1)`` the head)."""
    ks = jax.random.split(key, len(cfg.layer_types) + 1)
    v, d = cfg.padded_vocab, cfg.d_model
    params = {"embed": embed_init(ks[0], v, d, dtype),
              "layers": [_init_layer(k, cfg, kind, dtype)
                         for k, kind in zip(ks[1:], cfg.layer_types)],
              "final_norm": jnp.ones((d,), jnp.float32)}
    if not cfg.tie_embeddings:
        params["lm_head"] = dense_init(jax.random.fold_in(ks[0], 1), d, v,
                                       dtype)
    return params


def init_params(key, cfg: ArchConfig):
    dtype = jnp.dtype(cfg.dtype)
    if cfg.layer_types is not None:
        return _init_mixed(key, cfg, dtype)
    ks = jax.random.split(key, 5)
    v, d = cfg.padded_vocab, cfg.d_model

    def stack_init(key, n, **kw):
        return jax.vmap(lambda k: _init_block(k, cfg, dtype, **kw))(jax.random.split(key, n))

    params = {
        "embed": embed_init(ks[0], v, d, dtype),
        "blocks": stack_init(ks[1], cfg.n_layers, cross=cfg.enc_dec),
        "final_norm": jnp.ones((d,), jnp.float32),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = dense_init(ks[2], d, v, dtype)
    if cfg.enc_dec:
        enc_cfg = cfg  # same dims for encoder blocks
        params["enc_blocks"] = jax.vmap(
            lambda k: _init_block(k, enc_cfg, dtype))(jax.random.split(ks[3], cfg.n_enc_layers))
        params["enc_norm"] = jnp.ones((d,), jnp.float32)
    return params


def embed_params_padded(params, cfg: ArchConfig, cfg_p: ArchConfig):
    """Exact embedding of a model's weights into the head-padded layout
    (configs.base.pad_heads): real q head j goes to slot (j//n0)*n1 + j%n0 so
    the uniform repeat_kv mapping keeps it attached to its original kv head;
    pad q slots get zero wq columns and zero wo rows (their attention output
    is exactly dropped); pad kv slots get zero wk/wv (attended only by pad q
    slots).  Returns params for cfg_p with identical function."""
    hq, hkv, dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    hq_p, hkv_p = cfg_p.n_heads, cfg_p.n_kv_heads
    n0, n1 = hq // hkv, hq_p // hkv_p
    q_slot = np.array([(j // n0) * n1 + (j % n0) for j in range(hq)])

    def pad_attn(attn):
        out = dict(attn)
        L, d, _ = attn["wq"].shape
        wq = jnp.zeros((L, d, hq_p, dh), attn["wq"].dtype)
        wq = wq.at[:, :, q_slot].set(attn["wq"].reshape(L, d, hq, dh))
        out["wq"] = wq.reshape(L, d, hq_p * dh)
        wo = jnp.zeros((L, hq_p, dh, d), attn["wo"].dtype)
        wo = wo.at[:, q_slot].set(attn["wo"].reshape(L, hq, dh, d))
        out["wo"] = wo.reshape(L, hq_p * dh, d)
        for name in ("wk", "wv"):
            w = jnp.zeros((L, d, hkv_p, dh), attn[name].dtype)
            w = w.at[:, :, :hkv].set(attn[name].reshape(L, d, hkv, dh))
            out[name] = w.reshape(L, d, hkv_p * dh)
        return out

    new = dict(params)
    blocks = dict(params["blocks"])
    if "attn" in blocks:
        blocks["attn"] = pad_attn(blocks["attn"])
    if "cross" in blocks:
        blocks["cross"] = pad_attn(blocks["cross"])
    new["blocks"] = blocks
    if "enc_blocks" in params and "attn" in params["enc_blocks"]:
        enc = dict(params["enc_blocks"])
        enc["attn"] = pad_attn(enc["attn"])
        new["enc_blocks"] = enc
    return new


# ================================================================ block fwd
def _attn_fwd(p, x, cfg: ArchConfig, *, causal, window, positions,
              kv_override=None):
    """x (B,S,d). kv_override: (k, v) already-projected encoder memory (cross)."""
    b, s, d = x.shape
    hq, hkv, dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    h = rms_norm(x, p["norm"], cfg.norm_eps)
    q = (h @ p["wq"]).reshape(b, s, hq, dh)
    q = shard_act(q, "dp", None, "tp", None)
    if kv_override is None:
        k = (h @ p["wk"]).reshape(b, s, hkv, dh)
        v = (h @ p["wv"]).reshape(b, s, hkv, dh)
        if cfg.position_embedding == "rope":
            cos, sin = rope_angles(positions, dh, cfg.rope_theta)
            q = apply_rope(q, cos, sin)
            k = apply_rope(k, cos, sin)
    else:
        k, v = kv_override
    k = attn_mod._repeat_kv(k, hq // hkv)
    v = attn_mod._repeat_kv(v, hq // hkv)
    o = attn_mod.multihead_attention(q, k, v, causal=causal, window=window,
                                     scale=cfg.attn_scale)
    o = o.reshape(b, s, hq * dh) @ p["wo"]
    return shard_act(o, "dp", None, None)


def _block_fwd(p, x, cfg: ArchConfig, *, causal=True, positions=None,
               enc_kv=None, decoder=True):
    """One transformer block (pre-norm, residual). Returns (x, aux)."""
    aux = jnp.zeros((), jnp.float32)
    window = cfg.window if cfg.attention == "sliding_window" else None
    has_attn = cfg.attention != "none" and "attn" in p
    has_ssm = cfg.ssm is not None and "ssm" in p

    if has_attn and has_ssm:          # hybrid: parallel branches, mean-fused
        a = _attn_fwd(p["attn"], x, cfg, causal=causal, window=window,
                      positions=positions)
        sp = {k: v for k, v in p["ssm"].items() if k != "norm"}
        m, _ = ssd_mod.apply_ssd(sp, rms_norm(x, p["ssm"]["norm"], cfg.norm_eps), cfg.ssm)
        x = x + 0.5 * (a + m)
    elif has_attn:
        x = x + _attn_fwd(p["attn"], x, cfg, causal=causal, window=window,
                          positions=positions)
    elif has_ssm:
        sp = {k: v for k, v in p["ssm"].items() if k != "norm"}
        m, _ = ssd_mod.apply_ssd(sp, rms_norm(x, p["ssm"]["norm"], cfg.norm_eps), cfg.ssm)
        x = x + m

    if enc_kv is not None and "cross" in p:
        x = x + _attn_fwd(p["cross"], x, cfg, causal=False, window=None,
                          positions=None, kv_override=enc_kv)

    if "moe" in p:
        h = rms_norm(x, p["moe"]["norm"], cfg.norm_eps)
        mp = {k: v for k, v in p["moe"].items() if k != "norm"}
        o, a = ffn_mod.apply_moe(mp, h, top_k=cfg.moe.top_k,
                                 capacity_factor=cfg.moe.capacity_factor,
                                 kind=cfg.ffn_kind)
        x = x + o
        aux = aux + a
    elif "ffn" in p:
        h = rms_norm(x, p["ffn"]["norm"], cfg.norm_eps)
        h = shard_act(h, "dp", None, None)
        fp = {k: v for k, v in p["ffn"].items() if k != "norm"}
        x = x + ffn_mod.apply_ffn(fp, h, cfg.ffn_kind)
    return x, aux


# --- perf-variant knobs (set by repro.launch.variants around a lowering) ---
# REMAT_POLICY: which intermediates the layer-scan checkpoint saves for bwd.
#   "dots"    — dots_with_no_batch_dims_saveable (default; saves FFN matmuls)
#   "nothing" — full recompute (smallest live set, ~+1 fwd of compute)
REMAT_POLICY = "dots"
# RING_CACHE: sliding-window decode keeps only a window-sized ring buffer
# instead of the full-sequence KV cache (long_500k collective fix).
RING_CACHE = False
# REMAT_GROUP: 2-level remat — scan over L/G checkpointed groups of G layers;
# only group inputs are saved (L/G + G transient instead of L live carries).
REMAT_GROUP = 1


def _remat(fn):
    if REMAT_POLICY == "nothing":
        return jax.checkpoint(fn)
    return jax.checkpoint(
        fn, policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable)


def _run_blocks(blocks, x, cfg: ArchConfig, *, causal, positions, enc_kv=None,
                remat=False):
    """lax.scan over stacked layer params."""

    def body(carry, layer_p):
        h, aux = carry
        h2, a = _block_fwd(layer_p, h, cfg, causal=causal, positions=positions,
                           enc_kv=None if enc_kv is None else enc_kv_proj(layer_p))
        return (h2, aux + a), None

    def enc_kv_proj(layer_p):
        # project encoder memory to this layer's cross K/V
        mem = enc_kv
        b, se, d = mem.shape
        k = (mem @ layer_p["cross"]["wk"]).reshape(b, se, cfg.n_kv_heads, cfg.head_dim)
        v = (mem @ layer_p["cross"]["wv"]).reshape(b, se, cfg.n_kv_heads, cfg.head_dim)
        return (k, v)

    fn = body
    g = REMAT_GROUP
    n_layers = jax.tree_util.tree_leaves(blocks)[0].shape[0]
    if remat and g > 1 and n_layers % g == 0:
        # 2-level remat: outer checkpointed scan over groups, inner unchecked
        # scan over the g layers of each group
        def group_body(carry, group_p):
            out, _ = jax.lax.scan(body, carry, group_p)
            return out, None

        grouped = jax.tree_util.tree_map(
            lambda x_: x_.reshape(n_layers // g, g, *x_.shape[1:]), blocks)
        (x, aux), _ = jax.lax.scan(jax.checkpoint(group_body),
                                   (x, jnp.zeros((), jnp.float32)), grouped)
        return x, aux
    if remat:
        fn = _remat(body)
    (x, aux), _ = jax.lax.scan(fn, (x, jnp.zeros((), jnp.float32)), blocks)
    return x, aux


# =============================================================== embeddings
def _embed_inputs(params, cfg: ArchConfig, batch):
    """Token (+ multimodal stub) embedding. Returns (x, positions, loss_mask)."""
    tokens = batch["tokens"]
    x = params["embed"][tokens]            # gather
    if cfg.embedding_multiplier != 1.0:
        x = x * cfg.embedding_multiplier
    prefix = []
    if cfg.family == "vlm" and "image_emb" in batch:
        prefix.append(batch["image_emb"].astype(x.dtype))
    if prefix:
        x = jnp.concatenate(prefix + [x], axis=1)
    positions = jnp.arange(x.shape[1])
    return x, positions


def _lm_logits(params, cfg: ArchConfig, x):
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    logits = x @ head
    if cfg.logits_scaling != 1.0:
        logits = logits / cfg.logits_scaling
    return shard_act(logits, "dp", None, "tp")


LOSS_CHUNK = 512


def _chunked_cross_entropy(params, cfg: ArchConfig, x, labels, mask):
    """Sequence-chunked LM loss: the (B, chunk, V) logits tile is transient
    (recomputed in backward via jax.checkpoint), so the full (B, S, V) logits
    never materialize — essential for train_4k × 256k-vocab archs."""
    b, s, d = x.shape
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    chunk = LOSS_CHUNK if s % LOSS_CHUNK == 0 else s
    nc = s // chunk

    xc = x.reshape(b, nc, chunk, d).transpose(1, 0, 2, 3)
    lc = labels.reshape(b, nc, chunk).transpose(1, 0, 2)
    mc = mask.reshape(b, nc, chunk).transpose(1, 0, 2)

    def body(carry, inp):
        xs, ls, ms = inp
        logits = (xs @ head).astype(jnp.float32)
        if cfg.logits_scaling != 1.0:
            logits = logits / cfg.logits_scaling
        logits = shard_act(logits, "dp", None, "tp")
        lse = jax.scipy.special.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, ls[..., None], axis=-1)[..., 0]
        nll = (lse - gold) * ms.astype(jnp.float32)
        return (carry[0] + jnp.sum(nll), carry[1] + jnp.sum(ms)), None

    (tot, cnt), _ = jax.lax.scan(jax.checkpoint(body),
                                 (jnp.zeros((), jnp.float32),
                                  jnp.zeros((), jnp.float32)),
                                 (xc, lc, mc))
    return tot / jnp.maximum(cnt, 1.0)


def _encode(params, cfg: ArchConfig, frames):
    """Bidirectional encoder over precomputed frame embeddings (audio stub)."""
    x = frames.astype(jnp.dtype(cfg.dtype))
    pos = jnp.arange(x.shape[1])
    x, _ = _run_blocks(params["enc_blocks"], x, cfg, causal=False, positions=pos)
    return rms_norm(x, params["enc_norm"], cfg.norm_eps)


# ============================================================ mixed stacks
def _mixer(p, x, cfg: ArchConfig, kind: str, positions):
    """The mixer of one mixed-stack layer over its pre-norm of ``x``.
    Returns (out, what a decode cache keeps: (k, v) or (ssm, conv))."""
    if kind == "attention":
        pa = p["attention"]
        b, s, _ = x.shape
        hn = rms_norm(x, pa["norm"], cfg.norm_eps)
        k = (hn @ pa["wk"]).reshape(b, s, cfg.n_kv_heads, cfg.head_dim)
        v = (hn @ pa["wv"]).reshape(b, s, cfg.n_kv_heads, cfg.head_dim)
        if cfg.position_embedding == "rope":
            cos, sin = rope_angles(positions, cfg.head_dim, cfg.rope_theta)
            k = apply_rope(k, cos, sin)
        window = cfg.window if cfg.attention == "sliding_window" else None
        out = _attn_fwd(pa, x, cfg, causal=True, window=window,
                        positions=positions)
        return out, (k, v)
    sp = {k: v for k, v in p["mamba"].items() if k != "norm"}
    return ssd_mod.apply_ssd(
        sp, rms_norm(x, p["mamba"]["norm"], cfg.norm_eps), cfg.ssm)


def _mixed_layer(p, x, cfg: ArchConfig, kind: str, positions):
    """x + r * mixer(norm(x)), then x + r * mlp(norm(x)) (r the residual
    multiplier).  Returns (x, the mixer's decode-cache entries)."""
    r = cfg.residual_multiplier
    with jax.named_scope(kind):
        out, entries = _mixer(p, x, cfg, kind, positions)
        x = x + out * r
    with jax.named_scope("mlp"):
        h = rms_norm(x, p["mlp"]["norm"], cfg.norm_eps)
        fp = {k: v for k, v in p["mlp"].items() if k != "norm"}
        x = x + ffn_mod.apply_ffn(fp, h, cfg.ffn_kind) * r
    return x, entries


def mixed_hidden(params, cfg: ArchConfig, tokens, *, remat=False):
    """The mixed stack's last hidden state (before the final norm), each
    layer recomputed in the backward pass when ``remat`` (full per-layer
    recompute: the default ``dots`` policy of ``_remat`` would keep every
    matmul output, ~9 GB for ten layers at 8,192 tokens)."""
    with jax.named_scope("lm_head"):
        x, positions = _embed_inputs(params, cfg, {"tokens": tokens})
    for kind, p in zip(cfg.layer_types, params["layers"]):
        def layer(p, x, kind=kind):
            return _mixed_layer(p, x, cfg, kind, positions)[0]
        x = (jax.checkpoint(layer) if remat else layer)(p, x)
    return x


def _mixed_prefill(params, cfg: ArchConfig, batch):
    x, positions = _embed_inputs(params, cfg, batch)
    caches: dict = {"k": [], "v": [], "ssm": [], "conv": []}
    for kind, p in zip(cfg.layer_types, params["layers"]):
        x, entries = _mixed_layer(p, x, cfg, kind, positions)
        names = ("k", "v") if kind == "attention" else ("ssm", "conv")
        for name, e in zip(names, entries):
            caches[name].append(e)
    cache = {k: jnp.stack(v) for k, v in caches.items() if v}
    cache["len"] = jnp.asarray(x.shape[1], jnp.int32)
    return _lm_logits(params, cfg, x[:, -1:])[:, 0], cache


def _mixed_decode(params, cfg: ArchConfig, tokens, cache):
    b = tokens.shape[0]
    hq, hkv, dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    x, _ = _embed_inputs(params, cfg, {"tokens": tokens[:, None]})
    pos = cache["len"][None, None] + jnp.zeros((b, 1), jnp.int32)
    window = cfg.window if cfg.attention == "sliding_window" else None
    r = cfg.residual_multiplier
    new: dict = {"k": [], "v": [], "ssm": [], "conv": []}
    for kind, p in zip(cfg.layer_types, params["layers"]):
        if kind == "attention":
            pa, i = p["attention"], len(new["k"])
            hn = rms_norm(x, pa["norm"], cfg.norm_eps)
            q = (hn @ pa["wq"]).reshape(b, 1, hq, dh)
            k = (hn @ pa["wk"]).reshape(b, 1, hkv, dh)
            v = (hn @ pa["wv"]).reshape(b, 1, hkv, dh)
            if cfg.position_embedding == "rope":
                cos, sin = rope_angles(pos, dh, cfg.rope_theta)
                q = apply_rope(q, cos, sin)
                k = apply_rope(k, cos, sin)
            kc = jax.lax.dynamic_update_slice_in_dim(cache["k"][i], k,
                                                     cache["len"], axis=1)
            vc = jax.lax.dynamic_update_slice_in_dim(cache["v"][i], v,
                                                     cache["len"], axis=1)
            new["k"].append(kc)
            new["v"].append(vc)
            o = attn_mod.decode_attend(
                q, attn_mod._repeat_kv(kc, hq // hkv),
                attn_mod._repeat_kv(vc, hq // hkv), cache["len"] + 1,
                window=window, scale=cfg.attn_scale)
            out = o.reshape(b, 1, hq * dh) @ pa["wo"]
        else:
            i = len(new["ssm"])
            sp = {kk: vv for kk, vv in p["mamba"].items() if kk != "norm"}
            out, (st, cv) = ssd_mod.ssd_decode_step(
                sp, rms_norm(x, p["mamba"]["norm"], cfg.norm_eps), cfg.ssm,
                cache["ssm"][i], cache["conv"][i])
            new["ssm"].append(st)
            new["conv"].append(cv)
        x = x + out * r
        hn = rms_norm(x, p["mlp"]["norm"], cfg.norm_eps)
        fp = {kk: vv for kk, vv in p["mlp"].items() if kk != "norm"}
        x = x + ffn_mod.apply_ffn(fp, hn, cfg.ffn_kind) * r
    new_cache = {k: jnp.stack(v) for k, v in new.items() if v}
    new_cache["len"] = cache["len"] + 1
    return _lm_logits(params, cfg, x)[:, 0], new_cache


def next_token_accuracy(params, cfg: ArchConfig, batch):
    """Share of positions whose largest logit is the label, over the
    positions the loss counts; the logits are made one chunk at a time, as
    in ``_chunked_cross_entropy``."""
    x = mixed_hidden(params, cfg, batch["tokens"])
    labels = batch["labels"]
    mask = ((labels >= 0) & (labels < cfg.vocab_size)).astype(jnp.float32)
    with jax.named_scope("lm_head"):
        b, s, d = x.shape
        x = rms_norm(x, params["final_norm"], cfg.norm_eps)
        head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
        chunk = LOSS_CHUNK if s % LOSS_CHUNK == 0 else s
        nc = s // chunk

        def body(carry, inp):
            xs, ls, ms = inp
            hit = jnp.argmax(xs @ head, axis=-1) == ls
            return carry + jnp.sum(hit * ms), None

        tot, _ = jax.lax.scan(body, jnp.zeros((), jnp.float32), (
            x.reshape(b, nc, chunk, d).transpose(1, 0, 2, 3),
            labels.reshape(b, nc, chunk).transpose(1, 0, 2),
            mask.reshape(b, nc, chunk).transpose(1, 0, 2)))
        return tot / jnp.maximum(jnp.sum(mask), 1.0)


def mean_hidden(params, cfg: ArchConfig, tokens):
    """(B, d): the final-normed last hidden state, averaged over positions."""
    x = mixed_hidden(params, cfg, tokens)
    with jax.named_scope("lm_head"):
        return jnp.mean(rms_norm(x, params["final_norm"], cfg.norm_eps), 1)


# ==================================================================== train
def train_loss(params, cfg: ArchConfig, batch, *, remat=True, aux_weight=0.01):
    """Next-token LM loss.  batch: tokens (B,S), labels (B,S), optional
    image_emb (B,Ni,d) / audio_frames (B,Nf,d)."""
    if cfg.layer_types is not None:
        x = mixed_hidden(params, cfg, batch["tokens"], remat=remat)
        labels = batch["labels"]
        mask = (labels >= 0) & (labels < cfg.vocab_size)
        with jax.named_scope("lm_head"):
            return _chunked_cross_entropy(params, cfg, x,
                                          jnp.maximum(labels, 0), mask)
    enc_kv = None
    if cfg.enc_dec:
        enc_mem = _encode(params, cfg, batch["audio_frames"])
        enc_kv = enc_mem
    x, positions = _embed_inputs(params, cfg, batch)
    x = shard_act(x, "dp", None, None)
    x, aux = _run_blocks(params["blocks"], x, cfg, causal=True,
                         positions=positions, enc_kv=enc_kv, remat=remat)
    labels = batch["labels"]
    if x.shape[1] != labels.shape[1]:          # vlm: image prefix positions
        pad = x.shape[1] - labels.shape[1]
        labels = jnp.concatenate(
            [jnp.full((labels.shape[0], pad), -1, labels.dtype), labels], axis=1)
    mask = (labels >= 0) & (labels < cfg.vocab_size)
    loss = _chunked_cross_entropy(params, cfg, x, jnp.maximum(labels, 0), mask)
    if cfg.moe is not None:
        loss = loss + aux_weight * aux / cfg.n_layers
    return loss


# ============================================================ prefill/decode
def init_decode_cache(cfg: ArchConfig, batch: int, max_len: int,
                      enc_len: int = 0, dtype=None):
    """Abstract-shape-compatible cache pytree (all zeros)."""
    dtype = dtype or jnp.dtype(cfg.dtype)
    L = La = Ls = cfg.n_layers
    if cfg.layer_types is not None:      # a mixed stack caches by kind
        La = cfg.layer_types.count("attention")
        Ls = cfg.layer_types.count("mamba")
    cache: dict[str, Any] = {"len": jnp.zeros((), jnp.int32)}
    if cfg.attention != "none" and La:
        hkv, dh = cfg.n_kv_heads, cfg.head_dim
        cache["k"] = jnp.zeros((La, batch, max_len, hkv, dh), dtype)
        cache["v"] = jnp.zeros((La, batch, max_len, hkv, dh), dtype)
    if cfg.ssm is not None and Ls:
        s = cfg.ssm
        d_in = s.expand * cfg.d_model
        nh = d_in // s.head_dim
        cache["ssm"] = jnp.zeros((Ls, batch, nh, s.head_dim, s.d_state), jnp.float32)
        cache["conv"] = jnp.zeros((Ls, batch, s.d_conv - 1, d_in + 2 * s.d_state), dtype)
    if cfg.enc_dec:
        hkv, dh = cfg.n_kv_heads, cfg.head_dim
        cache["enc_k"] = jnp.zeros((L, batch, enc_len, hkv, dh), dtype)
        cache["enc_v"] = jnp.zeros((L, batch, enc_len, hkv, dh), dtype)
    return cache


def prefill(params, cfg: ArchConfig, batch):
    """Forward over a prompt; returns (last-position logits, populated cache)."""
    if cfg.layer_types is not None:
        return _mixed_prefill(params, cfg, batch)
    enc_kv = None
    enc_mem = None
    if cfg.enc_dec:
        enc_mem = _encode(params, cfg, batch["audio_frames"])
        enc_kv = enc_mem
    x, positions = _embed_inputs(params, cfg, batch)
    b, s, d = x.shape
    window = cfg.window if cfg.attention == "sliding_window" else None

    cache = init_decode_cache(cfg, b, s, enc_len=0 if enc_mem is None else enc_mem.shape[1])
    ks, vs, ssms, convs, eks, evs = [], [], [], [], [], []

    def body(carry, layer_p):
        h, aux = carry
        ys = {}
        # recompute K/V the same way _attn_fwd does, but also emit them
        if "attn" in layer_p:
            hn = rms_norm(h, layer_p["attn"]["norm"], cfg.norm_eps)
            k = (hn @ layer_p["attn"]["wk"]).reshape(b, s, cfg.n_kv_heads, cfg.head_dim)
            v = (hn @ layer_p["attn"]["wv"]).reshape(b, s, cfg.n_kv_heads, cfg.head_dim)
            cos, sin = rope_angles(positions, cfg.head_dim, cfg.rope_theta)
            ys["k"] = apply_rope(k, cos, sin)
            ys["v"] = v
        if "cross" in layer_p and enc_mem is not None:
            se = enc_mem.shape[1]
            ys["enc_k"] = (enc_mem @ layer_p["cross"]["wk"]).reshape(b, se, cfg.n_kv_heads, cfg.head_dim)
            ys["enc_v"] = (enc_mem @ layer_p["cross"]["wv"]).reshape(b, se, cfg.n_kv_heads, cfg.head_dim)
        if "ssm" in layer_p:
            sp = {kk: vv for kk, vv in layer_p["ssm"].items() if kk != "norm"}
            _, (st, cv) = ssd_mod.apply_ssd(
                sp, rms_norm(h, layer_p["ssm"]["norm"], cfg.norm_eps), cfg.ssm)
            ys["ssm"] = st
            ys["conv"] = cv
        h2, a = _block_fwd(
            layer_p, h, cfg, causal=True, positions=positions,
            enc_kv=None if enc_mem is None else (ys["enc_k"], ys["enc_v"]))
        return (h2, aux + a), ys

    (x, _), ys = jax.lax.scan(body, (x, jnp.zeros((), jnp.float32)), params["blocks"])
    for name in ("k", "v", "ssm", "conv", "enc_k", "enc_v"):
        if name in ys:
            cache[name] = ys[name]
    cache["len"] = jnp.asarray(s, jnp.int32)
    logits = _lm_logits(params, cfg, x[:, -1:])
    return logits[:, 0], cache


def decode_step(params, cfg: ArchConfig, tokens, cache, *, audio_frames=None):
    """One-token decode.  tokens (B,) int32; cache from init_decode_cache/prefill.

    Returns (logits (B, V), new cache).
    """
    if cfg.layer_types is not None:
        return _mixed_decode(params, cfg, tokens, cache)
    b = tokens.shape[0]
    x = params["embed"][tokens][:, None]                  # (B,1,d)
    if cfg.embedding_multiplier != 1.0:
        x = x * cfg.embedding_multiplier
    pos = cache["len"][None, None] + jnp.zeros((b, 1), jnp.int32)
    window = cfg.window if cfg.attention == "sliding_window" else None
    hq, hkv, dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim

    def body(carry, inp):
        h = carry
        layer_p, layer_c = inp
        new_c = {}
        if "attn" in layer_p:
            pa = layer_p["attn"]
            hn = rms_norm(h, pa["norm"], cfg.norm_eps)
            q = (hn @ pa["wq"]).reshape(b, 1, hq, dh)
            q = shard_act(q, "dp", None, "tp", None)
            k = (hn @ pa["wk"]).reshape(b, 1, hkv, dh)
            v = (hn @ pa["wv"]).reshape(b, 1, hkv, dh)
            cos, sin = rope_angles(pos, dh, cfg.rope_theta)
            q = apply_rope(q, cos, sin)
            k = apply_rope(k, cos, sin)
            if (RING_CACHE and window is not None
                    and layer_c["k"].shape[1] == window):
                # ring buffer: overwrite slot len % W; no sequence gather
                slot = jnp.mod(cache["len"], window)
                kc = jax.lax.dynamic_update_slice_in_dim(layer_c["k"], k, slot, axis=1)
                vc = jax.lax.dynamic_update_slice_in_dim(layer_c["v"], v, slot, axis=1)
                new_c["k"], new_c["v"] = kc, vc
                kr = attn_mod._repeat_kv(kc, hq // hkv)
                vr = attn_mod._repeat_kv(vc, hq // hkv)
                o = attn_mod.decode_attend_ring(q, kr, vr, cache["len"],
                                                window=window)
            else:
                kc = jax.lax.dynamic_update_slice_in_dim(layer_c["k"], k, cache["len"], axis=1)
                vc = jax.lax.dynamic_update_slice_in_dim(layer_c["v"], v, cache["len"], axis=1)
                new_c["k"], new_c["v"] = kc, vc
                kr = attn_mod._repeat_kv(kc, hq // hkv)
                vr = attn_mod._repeat_kv(vc, hq // hkv)
                o = attn_mod.decode_attend(q, kr, vr, cache["len"] + 1, window=window)
            attn_out = o.reshape(b, 1, hq * dh) @ pa["wo"]
        if "ssm" in layer_p:
            sp = {kk: vv for kk, vv in layer_p["ssm"].items() if kk != "norm"}
            m, (st, cv) = ssd_mod.ssd_decode_step(
                sp, rms_norm(h, layer_p["ssm"]["norm"], cfg.norm_eps), cfg.ssm,
                layer_c["ssm"], layer_c["conv"])
            new_c["ssm"], new_c["conv"] = st, cv
        if "attn" in layer_p and "ssm" in layer_p:
            h = h + 0.5 * (attn_out + m)
        elif "attn" in layer_p:
            h = h + attn_out
        elif "ssm" in layer_p:
            h = h + m
        if "cross" in layer_p:
            pc = layer_p["cross"]
            hn = rms_norm(h, pc["norm"], cfg.norm_eps)
            q = (hn @ pc["wq"]).reshape(b, 1, hq, dh)
            kr = attn_mod._repeat_kv(layer_c["enc_k"], hq // hkv)
            vr = attn_mod._repeat_kv(layer_c["enc_v"], hq // hkv)
            enc_len = jnp.asarray(layer_c["enc_k"].shape[1], jnp.int32)
            o = attn_mod.decode_attend(q, kr, vr, enc_len, window=None)
            h = h + o.reshape(b, 1, hq * dh) @ pc["wo"]
            # cross K/V are static during decode; pass through so the cache
            # pytree structure is stable
            new_c["enc_k"], new_c["enc_v"] = layer_c["enc_k"], layer_c["enc_v"]
        if "moe" in layer_p:
            hn = rms_norm(h, layer_p["moe"]["norm"], cfg.norm_eps)
            mp = {kk: vv for kk, vv in layer_p["moe"].items() if kk != "norm"}
            o, _ = ffn_mod.apply_moe(mp, hn, top_k=cfg.moe.top_k,
                                     capacity_factor=cfg.moe.capacity_factor,
                                     kind=cfg.ffn_kind)
            h = h + o
        elif "ffn" in layer_p:
            hn = rms_norm(h, layer_p["ffn"]["norm"], cfg.norm_eps)
            fp = {kk: vv for kk, vv in layer_p["ffn"].items() if kk != "norm"}
            h = h + ffn_mod.apply_ffn(fp, hn, cfg.ffn_kind)
        return h, new_c

    layer_caches = {k: v for k, v in cache.items() if k != "len"}
    x, new_caches = jax.lax.scan(body, x, (params["blocks"], layer_caches))
    logits = _lm_logits(params, cfg, x)[:, 0]
    new_cache = dict(new_caches)
    new_cache["len"] = cache["len"] + 1
    return logits, new_cache
