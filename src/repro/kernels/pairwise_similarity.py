"""Fused pairwise-similarity -> 3DG-adjacency Pallas kernels.

``similarity``: tiled U·Uᵀ on the MXU (f32 accumulate), grid (N/T, N/T, d/Tk)
with a revisiting accumulator — the standard TPU matmul pattern.

``adjacency``: elementwise epilogue V -> R (min-max normalize with
host-provided lo/hi scalars, threshold eps, exp(-V/sigma2), inf for no-edge,
zero diagonal) fused in VREGs so V never round-trips HBM twice.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

TILE_N = 128
TILE_K = 128


def _sim_kernel(u_ref, ut_ref, out_ref):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    out_ref[...] += jax.lax.dot_general(
        u_ref[...], ut_ref[...], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.HIGHEST)        # as the ref matmul


@functools.partial(jax.jit, static_argnames=("tile_n", "tile_k", "interpret"))
def similarity_pallas(u: jax.Array, *, tile_n: int = TILE_N,
                      tile_k: int = TILE_K, interpret: bool = False) -> jax.Array:
    """u (N, d) f32 -> V = U U^T (N, N) f32. N, d padded to tile multiples."""
    n, d = u.shape
    assert n % tile_n == 0 and d % tile_k == 0, (n, d)
    ut = u.T.copy()
    grid = (n // tile_n, n // tile_n, d // tile_k)
    return pl.pallas_call(
        _sim_kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((tile_n, tile_k), lambda i, j, k: (i, k)),
                  pl.BlockSpec((tile_k, tile_n), lambda i, j, k: (k, j))],
        out_specs=pl.BlockSpec((tile_n, tile_n), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((n, n), jnp.float32),
        interpret=interpret,
    )(u, ut)


def _adj_kernel(v_ref, scal_ref, out_ref):
    lo, hi, eps, sigma2 = (scal_ref[0, 0], scal_ref[0, 1],
                           scal_ref[0, 2], scal_ref[0, 3])
    v = (v_ref[...] - lo) / jnp.maximum(hi - lo, 1e-12)
    r = jnp.where(v >= eps, jnp.exp(-v / sigma2), jnp.inf)
    i, j = pl.program_id(0), pl.program_id(1)
    t = out_ref.shape[0]
    rows = jax.lax.broadcasted_iota(jnp.int32, (t, t), 0) + i * t
    cols = jax.lax.broadcasted_iota(jnp.int32, (t, t), 1) + j * t
    out_ref[...] = jnp.where(rows == cols, 0.0, r)


@functools.partial(jax.jit, static_argnames=("tile_n", "interpret"))
def adjacency_pallas(v: jax.Array, scalars: jax.Array, *, tile_n: int = TILE_N,
                     interpret: bool = False) -> jax.Array:
    """v (N,N) raw similarity; scalars = [lo, hi, eps, sigma2] f32 (shape (1,4))."""
    n = v.shape[0]
    assert n % tile_n == 0
    grid = (n // tile_n, n // tile_n)
    scalars = scalars.reshape(1, 4).astype(jnp.float32)
    return pl.pallas_call(
        _adj_kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((tile_n, tile_n), lambda i, j: (i, j)),
                  pl.BlockSpec((1, 4), lambda i, j: (0, 0))],
        out_specs=pl.BlockSpec((tile_n, tile_n), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((n, n), jnp.float32),
        interpret=interpret,
    )(v, scalars)
