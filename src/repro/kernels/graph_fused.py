"""Fused 3DG megakernel: similarity -> min-max stats -> adjacency in ONE grid.

The staged pallas path (``pairwise_similarity`` + ``adjacency_pallas``)
round-trips the (N, N) similarity matrix V through HBM three times: the
matmul writes it, the host-side ``jnp.min``/``jnp.max`` normalization stats
read it, and the adjacency epilogue reads it again.  This kernel keeps V
tile-resident: a two-phase sequential grid ``(phase, N/T, N/T)`` where

  phase 0  computes each V tile from the (T, d) feature row panels
           (MXU dot, optional max(·, 0) clamp for the Eq. 11/12 functional
           similarity) and folds its min/max into a RESIDENT (1, 2) stats
           accumulator (constant ``index_map`` — the same revisiting
           pattern as ``kernels/solver.py``'s running argmax).  min/max
           are exactly associative, so the tiled reduction is bit-identical
           to ``jnp.min``/``jnp.max`` over the unpadded V.
  phase 1  RE-computes the V tile (features stay in VMEM; for the small
           feature dims of the 3DG build the extra FLOPs are far cheaper
           than an HBM round-trip of the (N, N) matrix) and applies the
           fused epilogue in VREGs: min-max normalize with the phase-0
           stats, threshold at eps, ``exp(-Vn/sigma2)``, inf for no-edge,
           0 diagonal.

V never exists in HBM.  Pad lanes (rows/cols >= n) are excluded from the
stats and written as isolated nodes (inf off-diagonal, 0 diagonal), so the
output is directly Floyd–Warshall-ready at the padded size — the unpad/
re-pad round-trip between the staged adjacency and APSP wrappers disappears
too.  Epilogue op order matches ``core/graph_device.minmax01`` +
``to_adjacency`` exactly, so finite entries are bit-identical to the ref
stages given bit-identical V (pinned by ``tests/test_kernels.py``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

FUSED_TILE = 128


def _fused_kernel(n, clamp, u_ref, ut_ref, scal_ref, out_ref, stat_ref):
    phase, i, j = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    t = out_ref.shape[0]
    # float32 precision, like the ref matmul: V feeds exp(-V/σ²)
    v = jax.lax.dot_general(u_ref[...], ut_ref[...], (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32,
                            precision=jax.lax.Precision.HIGHEST)
    if clamp:
        v = jnp.maximum(v, 0.0)
    rows = jax.lax.broadcasted_iota(jnp.int32, (t, t), 0) + i * t
    cols = jax.lax.broadcasted_iota(jnp.int32, (t, t), 1) + j * t
    valid = (rows < n) & (cols < n)

    # the (1, 2) [lo, hi] accumulator is updated as a vector: Mosaic
    # cannot store scalars to VMEM
    is_lo = jax.lax.broadcasted_iota(jnp.int32, (1, 2), 1) == 0

    @pl.when((phase == 0) & (i == 0) & (j == 0))
    def _init():
        stat_ref[...] = jnp.where(is_lo, jnp.inf, -jnp.inf)

    @pl.when(phase == 0)
    def _stats():
        cur = stat_ref[...]
        stat_ref[...] = jnp.where(
            is_lo, jnp.minimum(cur, jnp.min(jnp.where(valid, v, jnp.inf))),
            jnp.maximum(cur, jnp.max(jnp.where(valid, v, -jnp.inf))))
        out_ref[...] = jnp.zeros_like(out_ref)

    @pl.when(phase == 1)
    def _epilogue():
        lo, hi = stat_ref[0, 0], stat_ref[0, 1]
        eps, sigma2 = scal_ref[0, 0], scal_ref[0, 1]
        vn = (v - lo) / jnp.maximum(hi - lo, 1e-12)
        r = jnp.where(vn >= eps, jnp.exp(-vn / sigma2), jnp.inf)
        # pad rows/cols become isolated nodes: the output is FW-ready at the
        # padded size (diagonal 0 INCLUDING pads, inf elsewhere off-region)
        out_ref[...] = jnp.where(rows == cols, 0.0,
                                 jnp.where(valid, r, jnp.inf))


@functools.partial(jax.jit,
                   static_argnames=("n", "clamp", "tile_n", "interpret"))
def fused_adjacency_pallas(u: jax.Array, scal: jax.Array, *, n: int,
                           clamp: bool = False, tile_n: int = FUSED_TILE,
                           interpret: bool = False):
    """u (M, d) f32 feature rows padded to tile multiples (zero pad rows),
    scal (1, 2) = [eps, sigma2], ``n`` the true (unpadded) client count.
    Returns (R (M, M) FW-ready padded adjacency, stats (1, 2) = [lo, hi])."""
    m, d = u.shape
    assert m % tile_n == 0 and d % 128 == 0, (u.shape, tile_n)
    grid = (2, m // tile_n, m // tile_n)
    ut = u.T.copy()
    return pl.pallas_call(
        functools.partial(_fused_kernel, n, clamp),
        grid=grid,
        in_specs=[pl.BlockSpec((tile_n, d), lambda p, i, j: (i, 0)),
                  pl.BlockSpec((d, tile_n), lambda p, i, j: (0, j)),
                  pl.BlockSpec((1, 2), lambda p, i, j: (0, 0))],
        out_specs=[pl.BlockSpec((tile_n, tile_n), lambda p, i, j: (i, j)),
                   pl.BlockSpec((1, 2), lambda p, i, j: (0, 0))],
        out_shape=[jax.ShapeDtypeStruct((m, m), jnp.float32),
                   jax.ShapeDtypeStruct((1, 2), jnp.float32)],
        interpret=interpret,
    )(u, ut, scal)
