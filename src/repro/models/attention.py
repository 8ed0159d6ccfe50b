"""Attention: GQA with full-causal or sliding-window variants.

Three execution paths, all pure JAX (the Pallas sliding-window kernel in
``repro.kernels.window_attention`` is the TPU hot-spot version; these are the
portable references used for training/lowering):

* dense path (S <= DENSE_MAX): materialized (B,H,S,S) scores — fastest to
  compile, fine for smoke tests and short sequences.
* chunked path (full attention, long S): online-softmax ``lax.scan`` over KV
  chunks — memory O(S·chunk) instead of O(S²).
* windowed path (sliding window, long S): ``lax.scan`` over Q chunks, each
  attending to a static-size KV span — compute O(S·window), truly
  sub-quadratic in HLO FLOPs.

Decode: one query token against a KV cache; sliding-window decode slices the
last ``window`` cache entries (static size) so long_500k decode reads a
bounded span.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

DENSE_MAX = 8192
Q_CHUNK = 1024
KV_CHUNK = 1024

NEG_INF = -1e30

# perf-variant knob: dtype of the materialized (B,H,Sq,Sk) score/prob buffers
# in the dense path.  f32 is the numerically-safe default; bf16 halves the
# dominant HBM traffic at train_4k (max-subtracted softmax keeps exp bounded).
SCORE_DTYPE = "float32"


def _repeat_kv(k: jax.Array, n_rep: int) -> jax.Array:
    """(B,S,Hkv,D) -> (B,S,Hkv*n_rep,D) repeating each kv head."""
    if n_rep == 1:
        return k
    b, s, h, d = k.shape
    return jnp.repeat(k, n_rep, axis=2)


def attend_dense(q, k, v, *, causal: bool, window: int | None,
                 q_offset: int = 0, scale: float | None = None) -> jax.Array:
    """Materialized attention. q (B,Sq,H,D), k/v (B,Sk,H,D) (kv already repeated).
    ``scale`` None: 1/sqrt(D)."""
    b, sq, h, d = q.shape
    sk = k.shape[1]
    scale = 1.0 / np.sqrt(d) if scale is None else scale
    sdt = jnp.dtype(SCORE_DTYPE)
    neg = jnp.asarray(-6e4 if sdt == jnp.bfloat16 else NEG_INF, sdt)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(sdt) * jnp.asarray(scale, sdt)
    qpos = jnp.arange(sq) + q_offset
    kpos = jnp.arange(sk)
    mask = jnp.ones((sq, sk), dtype=bool)
    if causal:
        mask &= kpos[None, :] <= qpos[:, None]
    if window is not None:
        mask &= kpos[None, :] > qpos[:, None] - window
    scores = jnp.where(mask[None, None], scores, neg)
    # max-subtracted softmax: stable in bf16 because exp inputs are <= 0
    m = jnp.max(scores, axis=-1, keepdims=True)
    p = jnp.exp(scores - m)
    probs = (p / jnp.sum(p, axis=-1, keepdims=True)).astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


def attend_chunked_full(q, k, v, *, causal: bool = True,
                        scale: float | None = None) -> jax.Array:
    """Online-softmax over KV chunks (flash pattern).  All-queries-at-once.

    Memory: O(B·H·Sq·KV_CHUNK) transient instead of O(Sq·Sk).
    """
    b, sq, h, d = q.shape
    sk = k.shape[1]
    assert sk % KV_CHUNK == 0, (sk, KV_CHUNK)
    n_kv = sk // KV_CHUNK
    scale = 1.0 / np.sqrt(d) if scale is None else scale
    qpos = jnp.arange(sq)

    kc = k.reshape(b, n_kv, KV_CHUNK, h, d)
    vc = v.reshape(b, n_kv, KV_CHUNK, h, d)

    def step(carry, inputs):
        acc, m, l = carry                       # (B,Sq,H,D) f32, (B,H,Sq), (B,H,Sq)
        kb, vb, kv_idx = inputs
        kp = kv_idx * KV_CHUNK + jnp.arange(KV_CHUNK)
        s = jnp.einsum("bqhd,bkhd->bhqk", q, kb).astype(jnp.float32) * scale
        if causal:
            msk = kp[None, :] <= qpos[:, None]
            s = jnp.where(msk[None, None], s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l = l * corr + jnp.sum(p, axis=-1)
        acc = acc * corr.transpose(0, 2, 1)[..., None] + jnp.einsum(
            "bhqk,bkhd->bqhd", p.astype(q.dtype), vb
        ).astype(jnp.float32)
        return (acc, m_new, l), None

    acc0 = jnp.zeros((b, sq, h, d), jnp.float32)
    m0 = jnp.full((b, h, sq), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, h, sq), jnp.float32)
    (acc, m, l), _ = jax.lax.scan(
        step, (acc0, m0, l0),
        (kc.transpose(1, 0, 2, 3, 4), vc.transpose(1, 0, 2, 3, 4), jnp.arange(n_kv)),
    )
    out = acc / jnp.maximum(l, 1e-30).transpose(0, 2, 1)[..., None]
    return out.astype(q.dtype)


def attend_windowed(q, k, v, *, window: int,
                    scale: float | None = None) -> jax.Array:
    """Causal sliding-window attention via Q-chunk scan over static KV spans.

    Query chunk i (length C) attends to kv span of static length W+C ending at
    the chunk's last position — O(S·(W+C)) compute.
    """
    b, sq, h, d = q.shape
    sk = k.shape[1]
    c = min(Q_CHUNK, sq)
    assert sq % c == 0
    n_q = sq // c
    span = window + c

    # left-pad K/V so every span slice is in-bounds and static-size
    pad = span
    kp = jnp.pad(k, ((0, 0), (pad, 0), (0, 0), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (pad, 0), (0, 0), (0, 0)))

    qc = q.reshape(b, n_q, c, h, d).transpose(1, 0, 2, 3, 4)

    def step(_, inputs):
        qb, i = inputs
        end = (i + 1) * c + pad                 # exclusive end in padded coords
        start = end - span
        kb = jax.lax.dynamic_slice_in_dim(kp, start, span, axis=1)
        vb = jax.lax.dynamic_slice_in_dim(vp, start, span, axis=1)
        qpos = i * c + jnp.arange(c)
        kpos = start - pad + jnp.arange(span)   # true positions (can be negative)
        s = jnp.einsum("bqhd,bkhd->bhqk", qb, kb).astype(jnp.float32)
        s = s / np.sqrt(d) if scale is None else s * scale
        msk = (kpos[None, :] <= qpos[:, None]) & (kpos[None, :] > qpos[:, None] - window) & (kpos[None, :] >= 0)
        s = jnp.where(msk[None, None], s, NEG_INF)
        p = jax.nn.softmax(s, axis=-1).astype(q.dtype)
        return None, jnp.einsum("bhqk,bkhd->bqhd", p, vb)

    _, out = jax.lax.scan(step, None, (qc, jnp.arange(n_q)))
    return out.transpose(1, 0, 2, 3, 4).reshape(b, sq, h, d)


def multihead_attention(q, k, v, *, causal: bool, window: int | None,
                        scale: float | None = None) -> jax.Array:
    """Dispatch on sequence length / window. kv heads already repeated to q
    heads.  ``scale`` (None: 1/sqrt(D)) is the score multiplier."""
    sq, sk = q.shape[1], k.shape[1]
    if window is not None and sk > window + Q_CHUNK and sq == sk:
        return attend_windowed(q, k, v, window=window, scale=scale)
    if max(sq, sk) <= DENSE_MAX:
        return attend_dense(q, k, v, causal=causal, window=window, scale=scale)
    return attend_chunked_full(q, k, v, causal=causal, scale=scale)


def decode_attend_ring(q, k_ring, v_ring, cache_len, *, window: int) -> jax.Array:
    """One-token decode over a ring-buffer cache of exactly ``window`` slots.

    Slot j holds absolute position  pos_j = L - ((L % W - j) mod W)  where L is
    the position of the just-written token (= cache_len).  Slots with pos < 0
    (cold start) are masked.  No sequence gather: the ring is the window.
    """
    b, _, h, d = q.shape
    w = k_ring.shape[1]
    assert w == window, (w, window)
    slot = jnp.mod(cache_len, w)
    j = jnp.arange(w)
    pos = cache_len - jnp.mod(slot - j, w)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k_ring).astype(jnp.float32) / np.sqrt(d)
    s = jnp.where((pos >= 0)[None, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v_ring)


def decode_attend(q, k_cache, v_cache, cache_len, *, window: int | None,
                  scale: float | None = None) -> jax.Array:
    """One-token decode. q (B,1,H,D); caches (B,Smax,Hkv_rep,D); cache_len scalar.

    For windowed attention only the last ``window`` entries are read
    (static-size dynamic slice) — the long_500k path.
    """
    b, _, h, d = q.shape
    smax = k_cache.shape[1]
    if window is not None and smax > window:
        # slice [cache_len - window, cache_len) clamped; positions tracked for mask
        start = jnp.maximum(cache_len - window, 0)
        kb = jax.lax.dynamic_slice_in_dim(k_cache, start, window, axis=1)
        vb = jax.lax.dynamic_slice_in_dim(v_cache, start, window, axis=1)
        kpos = start + jnp.arange(window)
    else:
        kb, vb = k_cache, v_cache
        kpos = jnp.arange(smax)
    if scale is None:
        s = jnp.einsum("bqhd,bkhd->bhqk", q, kb).astype(jnp.float32) / np.sqrt(d)
    else:
        s = jnp.einsum("bqhd,bkhd->bhqk", q, kb).astype(jnp.float32) * scale
    msk = kpos < cache_len
    s = jnp.where(msk[None, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", p, vb)
