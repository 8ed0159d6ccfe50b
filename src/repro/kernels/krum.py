"""Tiled pairwise squared-distance panel for the Krum aggregator.

Krum's score (Blanchard et al., NeurIPS 2017) is, per sampled update i,
the sum of its n − f − 2 smallest squared distances ||θ_i − θ_j||².  The
hot part is the (m, m) distance panel over the (m, P) flattened update
matrix: this kernel computes it as the classic expansion

    D[i, j] = ||x_i||² + ||x_j||² − 2 x_i · x_jᵀ

tiled exactly like ``pairwise_similarity`` — grid (m/T, m/T, P/Tk) with a
revisiting accumulator, the cross term on the MXU via ``dot_general`` with
f32 accumulation, and the row/col squared norms reduced per P-tile in
VREGs so each (T, Tk) panel of x is touched ONCE per grid step (no
separate norm pass over HBM).  The per-tile partials ``ri + rj − 2 x xᵀ``
accumulate over k, which reassociates the f32 sums vs the ref's
full-norm-then-subtract order — the panel agrees to f32 roundoff, and the
SELECTION (sorted score ranks) is pinned bit-identical in tests (Krum's
decision margin dwarfs the reassociation noise).

Zero-padding is safe end-to-end: padded P columns contribute 0 to every
term, and padded rows only add distance entries that the caller slices
off (``kernels/ops.krum_distances``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

KRUM_TM = 128       # (m, m) panel tile — min f32 sublane/lane tile is (8, 128)
KRUM_TK = 128       # P reduction tile


def _krum_kernel(x_ref, xt_ref, out_ref):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    xi = x_ref[...]                                   # (T, Tk) rows i
    xtj = xt_ref[...]                                 # (Tk, T) rows j, transposed
    ri = jnp.sum(xi * xi, axis=1, keepdims=True)      # (T, 1) partial ||x_i||²
    rj = jnp.sum(xtj * xtj, axis=0, keepdims=True)    # (1, T) partial ||x_j||²
    out_ref[...] += (ri + rj) - 2.0 * jax.lax.dot_general(
        xi, xtj, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.HIGHEST)        # as the ref cross term


@functools.partial(jax.jit, static_argnames=("tile_m", "tile_k", "interpret"))
def krum_pallas(x: jax.Array, *, tile_m: int = KRUM_TM,
                tile_k: int = KRUM_TK, interpret: bool = False) -> jax.Array:
    """x (m, P) f32 -> D (m, m) f32 squared distances. m, P tile multiples."""
    m, p = x.shape
    assert m % tile_m == 0 and p % tile_k == 0, (m, p)
    xt = x.T.copy()
    grid = (m // tile_m, m // tile_m, p // tile_k)
    return pl.pallas_call(
        _krum_kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((tile_m, tile_k), lambda i, j, k: (i, k)),
                  pl.BlockSpec((tile_k, tile_m), lambda i, j, k: (k, j))],
        out_specs=pl.BlockSpec((tile_m, tile_m), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, m), jnp.float32),
        interpret=interpret,
    )(x, xt)
