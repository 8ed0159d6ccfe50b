"""Pallas kernels for the FedGS Eq. 16 p-dispersion solver.

The reference solver (``core/sampler_device.fedgs_solve``) materializes a
dense (N, N) swap-gain matrix every local-search sweep and re-scans it with
a flat argmax — O(N²) HBM traffic per sweep that dominates the solve past
N ≈ 1k.  These kernels tile the three hot stages so nothing bigger than a
VMEM tile is ever materialized — including Q itself: since PR 7 the pallas
path never builds the (N, N) ``Q = sym(alpha/N · H) − diag(z)`` at all.
The factored form (H, z, alpha/N) is carried instead and Q entries are
reconstructed exactly where they are consumed:

``q_diag / q_row``  host-side jnp helpers reconstructing the diagonal and
                single rows (for the greedy ``r`` accumulator) with the ref
                path's exact op order ``0.5·((a·H_ij − δz) + (a·H_ji − δz))``
                — bit-identical to gathering from a materialized Q.

``masked_argmax``  the greedy step: gain ``diag + 2r`` is computed, masked
                (unavailable / already-selected / NaN ↦ −1e18) and arg-maxed
                in one pass over (1, T) lane blocks, carrying the running
                (best, index) pair across the sequential grid.  Strict ``>``
                combining + first-position-within-block reproduces
                ``jnp.argmax``'s first-max tie-break bit for bit.

``swap_gain_fused``  the best-swap sweep fused end-to-end: the kernel takes
                the (m, N) H row/column panels of the SELECTED clients plus
                (z[sel], alpha/N), rebuilds the Q tile in VREGs, and reduces
                ``delta = a_i + b_j − 2 Q_ij`` to a running (best, flat
                index) — solve→select→swap with no (N, N) and not even an
                (m, N) Q panel in HBM.  Ties combine on the GLOBAL flat
                index (not grid order), matching the ref path's row-major
                flat argmax exactly.

``swap_gain``   the same sweep for callers that already hold a Q panel
                (``fedgs_solve``'s public (N, N)-Q API).

All tiles are f32; min tile (8, 128) per the TPU tiling constraints — the
(1, T) argmax rows and (1, 1) accumulator outputs are sub-tile but legal
(the compiler pads sublanes).  The accumulators are read and written as
(1, 1) vectors, never as scalars: Mosaic cannot store a scalar to VMEM.  The running-reduction outputs use a constant
``index_map`` so the accumulator tile stays resident across the sequential
grid (the same revisiting-accumulator pattern as ``pairwise_similarity``).
On CPU the kernels run under ``interpret=True`` (see ``kernels/ops.py``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

TILE_Q = 512        # legacy q-panel tile (kept for the dense-Q swap path)
TILE_V = 2048       # masked-argmax lane-block width (1, T)
SWAP_TM = 128       # swap panel tile rows (selected-client ranks)
SWAP_TN = 2048      # swap panel tile cols (incoming candidates)

NEG = -1e18         # the solver's masked-entry sentinel (== sampler_device)


# ----------------------------------------------------- factored-Q providers
def q_diag(h: jax.Array, z: jax.Array, a) -> jax.Array:
    """diag(Q) for Q = sym(a·H) − diag(z), without building Q.

    Ref op order: ``Q_kk = 0.5·((a·H_kk − z_k) + (a·H_kk − z_k))`` — both
    addends are the same float, so this is bit-identical to the ref build's
    diagonal (0.5·(t+t) is exact)."""
    t = a * jnp.diagonal(h) - z
    return 0.5 * (t + t)


def q_row(h: jax.Array, z: jax.Array, a, k) -> jax.Array:
    """Row k of Q = sym(a·H) − diag(z) (the greedy ``r`` update), rebuilt
    with the ref op order so it is bit-identical to ``Q[k]`` of the
    materialized build: the δ-term subtracts z_k at column k in BOTH the
    H-row and H-column addends (z_i = z_j = z_k on the diagonal)."""
    n = h.shape[0]
    zc = jnp.where(jnp.arange(n) == k, z[k], 0.0)
    t1 = a * h[k, :] - zc
    t2 = a * h[:, k] - zc
    return 0.5 * (t1 + t2)


# ----------------------------------------------------------- masked argmax
def _masked_argmax_kernel(diag_ref, r_ref, mask_ref, val_ref, idx_ref):
    b = pl.program_id(0)
    t = diag_ref.shape[1]
    gain = diag_ref[...] + 2.0 * r_ref[...]           # (1, T)
    gain = jnp.where(mask_ref[...] > 0.5, gain, NEG)
    gain = jnp.where(jnp.isnan(gain), NEG, gain)      # NaN guard (== ref)

    @pl.when(b == 0)
    def _init():
        val_ref[...] = jnp.full((1, 1), -jnp.inf, jnp.float32)
        idx_ref[...] = jnp.zeros((1, 1), jnp.int32)

    mx = jnp.max(gain)
    cols = jax.lax.broadcasted_iota(jnp.int32, (1, t), 1)
    pos = jnp.min(jnp.where(gain == mx, cols, t))     # first max in block
    # strict > + left-to-right grid order == jnp.argmax first-max tie-break;
    # the accumulators are updated as (1, 1) vectors: Mosaic cannot store
    # scalars to VMEM
    cur_v, cur_i = val_ref[...], idx_ref[...]
    better = mx > cur_v
    idx_ref[...] = jnp.where(better, b * t + pos, cur_i)
    val_ref[...] = jnp.where(better, mx, cur_v)


@functools.partial(jax.jit, static_argnames=("tile", "interpret"))
def masked_argmax_pallas(diag: jax.Array, r: jax.Array, mask: jax.Array, *,
                         tile: int = TILE_V, interpret: bool = False):
    """Fused greedy gain + blocked masked argmax.

    diag, r, mask: (1, N) f32 (mask 1.0 = addable).  Returns the running
    ((1, 1) best gain, (1, 1) flat index) pair.
    """
    n = diag.shape[1]
    assert n % tile == 0 and r.shape == diag.shape == mask.shape
    return pl.pallas_call(
        _masked_argmax_kernel,
        name="masked_argmax_pallas",
        grid=(n // tile,),
        in_specs=[pl.BlockSpec((1, tile), lambda b: (0, b)),
                  pl.BlockSpec((1, tile), lambda b: (0, b)),
                  pl.BlockSpec((1, tile), lambda b: (0, b))],
        out_specs=[pl.BlockSpec((1, 1), lambda b: (0, 0)),
                   pl.BlockSpec((1, 1), lambda b: (0, 0))],
        out_shape=[jax.ShapeDtypeStruct((1, 1), jnp.float32),
                   jax.ShapeDtypeStruct((1, 1), jnp.int32)],
        interpret=interpret,
    )(diag, r, mask)


# -------------------------------------------------------------- swap sweep
def _best_swap_update(delta, bi, bj, np_cols, val_ref, flat_ref):
    """Shared running reduction: fold a (tm, tn) delta tile into the
    resident ((1,1) best, (1,1) flat-index) accumulators.  Ties compare on
    the GLOBAL flat index over the (M, N) panel, NOT grid order — a later
    column tile can hold an earlier PANEL row than a tile already visited —
    matching the ref path's row-major flat argmax exactly."""
    tm, tn = delta.shape

    @pl.when((bi == 0) & (bj == 0))
    def _init():
        val_ref[...] = jnp.full((1, 1), -jnp.inf, jnp.float32)
        flat_ref[...] = jnp.zeros((1, 1), jnp.int32)

    mx = jnp.max(delta)
    rows = jax.lax.broadcasted_iota(jnp.int32, (tm, tn), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (tm, tn), 1)
    flat = (rows + bi * tm) * np_cols + (cols + bj * tn)
    pos = jnp.min(jnp.where(delta == mx, flat, jnp.int32(2 ** 31 - 1)))
    cur_v, cur_f = val_ref[...], flat_ref[...]        # (1, 1) vectors
    better = (mx > cur_v) | ((mx == cur_v) & (pos < cur_f))
    flat_ref[...] = jnp.where(better, pos, cur_f)
    val_ref[...] = jnp.where(better, mx, cur_v)


def _swap_gain_kernel(a_ref, b_ref, q_ref, val_ref, flat_ref):
    bi, bj = pl.program_id(0), pl.program_id(1)
    tn = q_ref.shape[1]
    delta = (a_ref[...] + b_ref[...]) - 2.0 * q_ref[...]   # (tm,1)+(1,tn)
    delta = jnp.where(jnp.isnan(delta), NEG, delta)        # NaN guard (== ref)
    _best_swap_update(delta, bi, bj, pl.num_programs(1) * tn,
                      val_ref, flat_ref)


@functools.partial(jax.jit, static_argnames=("tile_m", "tile_n", "interpret"))
def swap_gain_pallas(qs: jax.Array, a: jax.Array, b: jax.Array, *,
                     tile_m: int = SWAP_TM, tile_n: int = SWAP_TN,
                     interpret: bool = False):
    """Best swap over a MATERIALIZED selected-row panel.

    qs (M, N) f32 = gathered selected rows of Q; a (M, 1) out-gain terms
    (−1e18 on invalid/pad rows); b (1, N) in-gain terms (−1e18 on
    non-addable/pad cols).  Returns ((1, 1) best delta, (1, 1) flat index
    into the (M, N) panel).
    """
    m, n = qs.shape
    assert m % tile_m == 0 and n % tile_n == 0, (qs.shape, tile_m, tile_n)
    assert a.shape == (m, 1) and b.shape == (1, n)
    grid = (m // tile_m, n // tile_n)
    return pl.pallas_call(
        _swap_gain_kernel,
        name="swap_gain_pallas",
        grid=grid,
        in_specs=[pl.BlockSpec((tile_m, 1), lambda i, j: (i, 0)),
                  pl.BlockSpec((1, tile_n), lambda i, j: (0, j)),
                  pl.BlockSpec((tile_m, tile_n), lambda i, j: (i, j))],
        out_specs=[pl.BlockSpec((1, 1), lambda i, j: (0, 0)),
                   pl.BlockSpec((1, 1), lambda i, j: (0, 0))],
        out_shape=[jax.ShapeDtypeStruct((1, 1), jnp.float32),
                   jax.ShapeDtypeStruct((1, 1), jnp.int32)],
        interpret=interpret,
    )(a, b, qs)


def _swap_fused_kernel(a_ref, b_ref, hs_ref, hts_ref, sel_ref, zsel_ref,
                       scal_ref, val_ref, flat_ref):
    """Q-free best swap: rebuild the Q tile in VREGs from the H panels.

    Q_sr,j = 0.5·((a·H[sr,j] − δ z[sr]) + (a·H[j,sr] − δ z[sr])) with
    δ = (j == sel_r) — the exact ref op order (z_j = z_sr on the diagonal),
    so ``delta = (a_i + b_j) − 2 Q`` is bit-identical to the dense-panel
    kernel fed by a materialized Q."""
    bi, bj = pl.program_id(0), pl.program_id(1)
    tm, tn = hs_ref.shape
    al = scal_ref[0, 0]
    cols = jax.lax.broadcasted_iota(jnp.int32, (tm, tn), 1) + bj * tn
    zc = jnp.where(sel_ref[...] == cols, zsel_ref[...], 0.0)
    t1 = al * hs_ref[...] - zc
    t2 = al * hts_ref[...] - zc
    q = 0.5 * (t1 + t2)
    delta = (a_ref[...] + b_ref[...]) - 2.0 * q
    delta = jnp.where(jnp.isnan(delta), NEG, delta)        # NaN guard (== ref)
    _best_swap_update(delta, bi, bj, pl.num_programs(1) * tn,
                      val_ref, flat_ref)


@functools.partial(jax.jit, static_argnames=("tile_m", "tile_n", "interpret"))
def swap_gain_fused_pallas(hs: jax.Array, hts: jax.Array, a: jax.Array,
                           b: jax.Array, sel: jax.Array, zsel: jax.Array,
                           scal: jax.Array, *, tile_m: int = SWAP_TM,
                           tile_n: int = SWAP_TN, interpret: bool = False):
    """Fused best swap over the factored Q.

    hs (M, N) = H[sel, :], hts (M, N) = H[:, sel]ᵀ, a (M, 1) out-gain,
    b (1, N) in-gain (both −1e18-masked), sel (M, 1) int32 global indices
    of the panel rows (−1 on pad rows — matches no column), zsel (M, 1) =
    z[sel], scal (1, 1) = [alpha/N].  Returns ((1, 1) best delta, (1, 1)
    flat index into the (M, N) panel)."""
    m, n = hs.shape
    assert m % tile_m == 0 and n % tile_n == 0, (hs.shape, tile_m, tile_n)
    assert hts.shape == (m, n) and a.shape == (m, 1) and b.shape == (1, n)
    assert sel.shape == (m, 1) and zsel.shape == (m, 1)
    grid = (m // tile_m, n // tile_n)
    return pl.pallas_call(
        _swap_fused_kernel,
        name="swap_gain_fused_pallas",
        grid=grid,
        in_specs=[pl.BlockSpec((tile_m, 1), lambda i, j: (i, 0)),
                  pl.BlockSpec((1, tile_n), lambda i, j: (0, j)),
                  pl.BlockSpec((tile_m, tile_n), lambda i, j: (i, j)),
                  pl.BlockSpec((tile_m, tile_n), lambda i, j: (i, j)),
                  pl.BlockSpec((tile_m, 1), lambda i, j: (i, 0)),
                  pl.BlockSpec((tile_m, 1), lambda i, j: (i, 0)),
                  pl.BlockSpec((1, 1), lambda i, j: (0, 0))],
        out_specs=[pl.BlockSpec((1, 1), lambda i, j: (0, 0)),
                   pl.BlockSpec((1, 1), lambda i, j: (0, 0))],
        out_shape=[jax.ShapeDtypeStruct((1, 1), jnp.float32),
                   jax.ShapeDtypeStruct((1, 1), jnp.int32)],
        interpret=interpret,
    )(a, b, hs, hts, sel, zsel, scal)
