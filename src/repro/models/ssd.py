"""Mamba-2 / SSD (state-space duality) block, chunked parallel scan.

Follows arXiv:2405.21060 (Dao & Gu, "Transformers are SSMs"):
  h_t = exp(dt_t·A) h_{t-1} + dt_t · B_t ⊗ x_t        (per head, state N)
  y_t = C_t · h_t + D ⊙ x_t
Chunked form: within-chunk attention-like term + cross-chunk state recurrence
(``lax.scan`` over chunks).  Single B/C group (ngroups=1) as in mamba2-780m.

Params are separate projections (w_z/w_x/w_B/w_C/w_dt) instead of the fused
in_proj so tensor-parallel sharding can target head-aligned dims — see
DESIGN.md hardware-adaptation notes.

``SSMConfig.conv_bias`` adds the causal conv's bias (``conv_b``) and
``SSMConfig.gated_norm`` the published mixer's gated RMSNorm before
``out_proj`` (``gate_norm``): ``rms_norm(y * silu(z)) * w`` in place of the
plain gate ``y * silu(z)``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.layers import dense_init, rms_norm


def init_ssd(key, d_model: int, cfg, dtype):
    """cfg: SSMConfig."""
    d_in = cfg.expand * d_model
    nheads = d_in // cfg.head_dim
    ks = jax.random.split(key, 8)
    dt_init = jnp.log(jnp.expm1(jnp.exp(
        jax.random.uniform(ks[5], (nheads,), jnp.float32,
                           np.log(1e-3), np.log(1e-1)))))
    extra = {}
    if cfg.conv_bias:
        extra["conv_b"] = jnp.zeros((d_in + 2 * cfg.d_state,), dtype)
    if cfg.gated_norm:
        extra["gate_norm"] = jnp.ones((d_in,), jnp.float32)
    return {**extra,
        "w_z": dense_init(ks[0], d_model, d_in, dtype),
        "w_x": dense_init(ks[1], d_model, d_in, dtype),
        "w_B": dense_init(ks[2], d_model, cfg.d_state, dtype),
        "w_C": dense_init(ks[3], d_model, cfg.d_state, dtype),
        "w_dt": dense_init(ks[4], d_model, nheads, dtype),
        "dt_bias": dt_init.astype(jnp.float32),
        "A_log": jnp.log(jnp.linspace(1.0, 16.0, nheads)).astype(jnp.float32),
        "D": jnp.ones((nheads,), jnp.float32),
        "conv_w": (jax.random.normal(ks[6], (cfg.d_conv, d_in + 2 * cfg.d_state), jnp.float32)
                   * 0.1).astype(dtype),
        "w_out": dense_init(ks[7], d_in, d_model, dtype),
    }


def _causal_conv(u: jax.Array, w: jax.Array, init_state: jax.Array | None = None,
                 bias: jax.Array | None = None):
    """Depthwise causal conv (+ bias), SiLU. u (B,S,C), w (K,C).
    Returns (y, last K-1 inputs)."""
    k = w.shape[0]
    if init_state is None:
        init_state = jnp.zeros((u.shape[0], k - 1, u.shape[2]), u.dtype)
    up = jnp.concatenate([init_state, u], axis=1)
    y = sum(up[:, i:i + u.shape[1]] * w[i][None, None] for i in range(k))
    if bias is not None:
        y = y + bias
    return jax.nn.silu(y), up[:, -(k - 1):]


def _gate(params, y, z):
    """The output gate: the plain ``y * silu(z)``, or with ``gate_norm``
    the published gated RMSNorm ``rms_norm(y * silu(z)) * w``."""
    y = y * jax.nn.silu(z)
    if "gate_norm" in params:
        y = rms_norm(y, params["gate_norm"])
    return y


def _segsum(a: jax.Array) -> jax.Array:
    """a: (..., Q) -> (..., Q, Q) lower-triangular segment sums:
    out[i,j] = sum_{j<k<=i} a[k], -inf above diagonal."""
    q = a.shape[-1]
    cs = jnp.cumsum(a, axis=-1)
    out = cs[..., :, None] - cs[..., None, :]
    mask = jnp.tril(jnp.ones((q, q), bool), k=0)
    return jnp.where(mask, out, -jnp.inf)


def ssd_chunked(x, dt, A, B, C, D, *, chunk: int, init_state=None):
    """SSD forward.

    x  (b, s, h, p)   dt (b, s, h)    A (h,) [negative]
    B  (b, s, n)      C (b, s, n)     D (h,)
    Returns y (b, s, h, p), final_state (b, h, p, n).
    """
    b, s, h, p = x.shape
    n = B.shape[-1]
    assert s % chunk == 0, (s, chunk)
    nc = s // chunk

    xc = x.reshape(b, nc, chunk, h, p)
    dtc = dt.reshape(b, nc, chunk, h)
    Bc = B.reshape(b, nc, chunk, n)
    Cc = C.reshape(b, nc, chunk, n)

    a = dtc * A[None, None, None]                      # (b,nc,q,h) log-decay
    a_h = a.transpose(0, 1, 3, 2)                      # (b,nc,h,q)
    a_cum = jnp.cumsum(a_h, axis=-1)                   # within-chunk cumulative

    # ---- intra-chunk (diagonal blocks): attention-like with decay mask
    L = jnp.exp(_segsum(a_h))                          # (b,nc,h,q,q)
    scores = jnp.einsum("bcin,bcjn->bcij", Cc.astype(jnp.float32), Bc.astype(jnp.float32))
    Ydiag = jnp.einsum("bchij,bcij,bcjh,bcjhp->bcihp",
                       L, scores, dtc.astype(jnp.float32), xc.astype(jnp.float32))

    # ---- chunk states: state contributed by each chunk
    decay_to_end = jnp.exp(a_cum[..., -1:] - a_cum)    # (b,nc,h,q)
    states = jnp.einsum("bcqn,bchq,bcqh,bcqhp->bchpn",
                        Bc.astype(jnp.float32), decay_to_end, dtc.astype(jnp.float32),
                        xc.astype(jnp.float32))

    # ---- inter-chunk recurrence over chunk index
    chunk_decay = jnp.exp(a_cum[..., -1])              # (b,nc,h) total chunk decay
    if init_state is None:
        init_state = jnp.zeros((b, h, p, n), jnp.float32)

    def step(carry, inp):
        st_in = carry                                  # (b,h,p,n)
        dec, st_c = inp                                # (b,h), (b,h,p,n)
        new = st_in * dec[..., None, None] + st_c
        return new, st_in                              # emit state seen by chunk

    _, prev_states = jax.lax.scan(
        step, init_state,
        (chunk_decay.transpose(1, 0, 2), states.transpose(1, 0, 2, 3, 4)),
    )
    final_state = init_state * 0  # placeholder replaced below
    # recompute final state (scan emitted the *incoming* state of each chunk)
    last_in = prev_states[-1]
    final_state = last_in * chunk_decay[:, -1][..., None, None] + states[:, -1]
    prev_states = prev_states.transpose(1, 0, 2, 3, 4)  # (b,nc,h,p,n)

    # ---- inter-chunk output: decayed incoming state read by C
    in_decay = jnp.exp(a_cum)                          # decay from chunk start to q
    Yoff = jnp.einsum("bcqn,bchq,bchpn->bcqhp", Cc.astype(jnp.float32), in_decay, prev_states)

    y = Ydiag + Yoff + (x.astype(jnp.float32) * D[None, None, :, None]).reshape(b, nc, chunk, h, p)
    return y.reshape(b, s, h, p).astype(x.dtype), final_state


def apply_ssd(params, x, cfg, *, state=None, conv_state=None):
    """Full mamba2 mixer. x (b, s, d_model) -> (b, s, d_model).

    Returns (y, (ssm_state, conv_state)) for decode continuation.
    """
    d_model = x.shape[-1]
    d_in = cfg.expand * d_model
    h = d_in // cfg.head_dim
    n = cfg.d_state

    z = x @ params["w_z"]                               # gate
    xbc = jnp.concatenate(
        [x @ params["w_x"], x @ params["w_B"], x @ params["w_C"]], axis=-1)
    xbc, new_conv = _causal_conv(xbc, params["conv_w"], conv_state,
                                 params.get("conv_b"))
    xs, B, C = jnp.split(xbc, [d_in, d_in + n], axis=-1)

    dt = jax.nn.softplus((x @ params["w_dt"]).astype(jnp.float32)
                         + params["dt_bias"][None, None])
    A = -jnp.exp(params["A_log"])

    # pad sequence to a chunk multiple; dt=0 on padding makes padded steps
    # identity transitions (decay=1, zero contribution), so the final state is
    # exact for decode continuation.
    s_len = xs.shape[1]
    chunk = min(cfg.chunk, s_len)
    pad = (-s_len) % chunk
    if pad:
        xs_p = jnp.pad(xs, ((0, 0), (0, pad), (0, 0)))
        B = jnp.pad(B, ((0, 0), (0, pad), (0, 0)))
        C = jnp.pad(C, ((0, 0), (0, pad), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
    else:
        xs_p = xs

    xh = xs_p.reshape(*xs_p.shape[:-1], h, cfg.head_dim)
    y, new_state = ssd_chunked(xh, dt, A, B, C, params["D"],
                               chunk=chunk, init_state=state)
    y = y.reshape(xs_p.shape[0], xs_p.shape[1], d_in)[:, :s_len]
    return _gate(params, y, z) @ params["w_out"], (new_state, new_conv)


def ssd_decode_step(params, x, cfg, state, conv_state):
    """Single-token recurrent step. x (b, 1, d_model)."""
    d_model = x.shape[-1]
    d_in = cfg.expand * d_model
    h = d_in // cfg.head_dim
    n = cfg.d_state

    z = x @ params["w_z"]
    xbc = jnp.concatenate(
        [x @ params["w_x"], x @ params["w_B"], x @ params["w_C"]], axis=-1)
    xbc, new_conv = _causal_conv(xbc, params["conv_w"], conv_state,
                                 params.get("conv_b"))
    xs, B, C = jnp.split(xbc, [d_in, d_in + n], axis=-1)

    dt = jax.nn.softplus((x @ params["w_dt"]).astype(jnp.float32)
                         + params["dt_bias"][None, None])[:, 0]      # (b,h)
    A = -jnp.exp(params["A_log"])
    xh = xs[:, 0].reshape(-1, h, cfg.head_dim).astype(jnp.float32)   # (b,h,p)
    Bt = B[:, 0].astype(jnp.float32)                                 # (b,n)
    Ct = C[:, 0].astype(jnp.float32)

    decay = jnp.exp(dt * A[None])                                    # (b,h)
    upd = jnp.einsum("bh,bhp,bn->bhpn", dt, xh, Bt)
    new_state = state * decay[..., None, None] + upd
    y = jnp.einsum("bhpn,bn->bhp", new_state, Ct) + xh * params["D"][None, :, None]
    y = y.reshape(x.shape[0], 1, d_in).astype(x.dtype)
    return _gate(params, y, z) @ params["w_out"], (new_state, new_conv)
