"""Blocked Floyd–Warshall APSP — Pallas TPU kernel, fused per-pivot round.

The classic cache-blocked FW (pivot / row panel / col panel / rest phases)
used to be FOUR ``pallas_call``s per pivot block, each re-streaming the
pivot panels from HBM.  This rewrite fuses one full pivot round into a
SINGLE call with a remapped grid: for pivot block ``kb`` the (nb, nb) grid
visits blocks at ``(ri, rj) = ((kb+i) % nb, (kb+j) % nb)``, so step (0,0)
is the pivot tile, row i=0 is the pivot row panel, column j=0 is the pivot
column panel, and everything else is the independent rank-T update.  The
updated pivot row/column panels are carried between steps in two RESIDENT
accumulator outputs (constant ``index_map`` — (T, N) and (N, T) buffers
that stay in VMEM for the whole round, double-buffered against the streamed
(T, T) tiles), so phase-3 steps read their panels via ``pl.ds`` dynamic
slices instead of HBM re-reads.  Every input block is read exactly once per
round and only its own block is rewritten (``input_output_aliases``), which
keeps the in/out pipelining race-free.

min-plus is not an MXU semiring, so the inner update is a VPU
broadcast-min-add.  VMEM per round ≈ 2·T·N·4 B of resident panels + a few
(T, T) tiles and scratch.  Compiled for a v5e at T=128, the largest padded
N inside the compiler's 16 MiB scoped-VMEM default is 15872 (16000 is
refused); past it use T≤64 or shard the matrix first (DESIGN.md §14).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

TILE = 128


def _fw_tile(tile, col_t=None, row=None):
    """In-tile FW sweep: tile = min(tile, col_k + row_k) for every pivot k.

    ``col_t`` is a ref holding the column source TRANSPOSED (its row k is
    column k), ``row`` a ref holding the row source; None means the tile
    itself (the pivot / panel sweeps are self-referential, so they must
    run in k order on the updated tile).  Mosaic slices neither a value at
    a dynamic offset nor a ref at a dynamic lane offset, so fixed sources
    are read from full-width refs at a dynamic SUBLANE offset k, and the
    tile's own row/column k is picked out with a masked min — exact, since
    every other entry is +inf."""
    t = tile.shape[0]
    lanes = jax.lax.broadcasted_iota(jnp.int32, (t, t), 1)
    subl = jax.lax.broadcasted_iota(jnp.int32, (t, t), 0)

    def body(k, cur):
        if col_t is None:
            col = jnp.min(jnp.where(lanes == k, cur, jnp.inf), axis=1,
                          keepdims=True)                             # (T,1)
        else:
            col = col_t[pl.ds(k, 1), :].T                            # (T,1)
        if row is None:
            rw = jnp.min(jnp.where(subl == k, cur, jnp.inf), axis=0,
                         keepdims=True)                              # (1,T)
        else:
            rw = row[pl.ds(k, 1), :]                                 # (1,T)
        return jnp.minimum(cur, col + rw)

    return jax.lax.fori_loop(0, t, body, tile)


def _fw_round_kernel(kb, nb, h_ref, out_ref, rowp_ref, colp_ref, ct_ref,
                     rt_ref):
    """One full pivot round.  Grid (nb, nb); block (i, j) maps to matrix
    block ((kb+i) % nb, (kb+j) % nb).  rowp (T, N) / colp (N, T) are the
    resident pivot row/col panels, indexed by REAL block coordinates;
    ct / rt (T, T) are scratch copies of a transposed column source and of
    a row source cut from the row panel (a lane-offset view of rowp cannot
    be read at a dynamic sublane)."""
    i, j = pl.program_id(0), pl.program_id(1)
    t = out_ref.shape[0]
    cur = h_ref[...]
    pivot_lo = kb * t  # static

    def block(r):
        return pl.ds(pl.multiple_of(r * t, t), t)

    @pl.when((i == 0) & (j == 0))
    def _pivot():
        res = _fw_tile(cur)
        out_ref[...] = res
        rowp_ref[:, pl.ds(pivot_lo, t)] = res
        colp_ref[pl.ds(pivot_lo, t), :] = res

    @pl.when((i == 0) & (j > 0))
    def _row_panel():
        rj = (kb + j) % nb
        ct_ref[...] = rowp_ref[:, pl.ds(pivot_lo, t)].T
        res = _fw_tile(cur, col_t=ct_ref)
        out_ref[...] = res
        rowp_ref[:, block(rj)] = res

    @pl.when((i > 0) & (j == 0))
    def _col_panel():
        ri = (kb + i) % nb
        res = _fw_tile(cur, row=colp_ref.at[pl.ds(pivot_lo, t), :])
        out_ref[...] = res
        colp_ref[block(ri), :] = res

    @pl.when((i > 0) & (j > 0))
    def _rest():
        ri = (kb + i) % nb
        rj = (kb + j) % nb
        ct_ref[...] = colp_ref[block(ri), :].T
        rt_ref[...] = rowp_ref[:, block(rj)]
        out_ref[...] = _fw_tile(cur, col_t=ct_ref, row=rt_ref)


@functools.partial(jax.jit, static_argnames=("tile", "interpret"))
def floyd_warshall_pallas(h: jax.Array, *, tile: int = TILE,
                          interpret: bool = False) -> jax.Array:
    """h (N, N) f32 adjacency (inf = no edge, 0 diag) -> shortest paths."""
    n = h.shape[0]
    assert n % tile == 0, f"pad N={n} to a multiple of {tile}"
    nb = n // tile
    t = tile

    for kb in range(nb):
        remap = lambda i, j, kb=kb: ((kb + i) % nb, (kb + j) % nb)
        h, _, _ = pl.pallas_call(
            functools.partial(_fw_round_kernel, kb, nb),
            name="floyd_warshall_pallas",
            grid=(nb, nb),
            in_specs=[pl.BlockSpec((t, t), remap)],
            out_specs=[pl.BlockSpec((t, t), remap),
                       pl.BlockSpec((t, n), lambda i, j: (0, 0)),
                       pl.BlockSpec((n, t), lambda i, j: (0, 0))],
            out_shape=[jax.ShapeDtypeStruct((n, n), jnp.float32),
                       jax.ShapeDtypeStruct((t, n), jnp.float32),
                       jax.ShapeDtypeStruct((n, t), jnp.float32)],
            scratch_shapes=[pltpu.VMEM((t, t), jnp.float32)] * 2,
            input_output_aliases={0: 0},
            interpret=interpret,
        )(h)
    return h
