"""Pallas kernel for the memory-rectified server aggregation.

The memory aggregator (``fed/aggregator_device.py``, family ``memory``)
keeps an (N, P) panel of every client's last flattened update and, each
round, (a) scatters the m sampled clients' fresh updates into their rows
and (b) reduces the panel with staleness-discounted weights into the new
global params.  Done naively per params leaf this is the heaviest per-round
data movement in the simulation — an (N, leaf)-shaped gather/scatter and
reduction for every leaf.  This kernel fuses both stages over the ONE flat
panel:

``memagg``  grid (P/Tp, N/Tn, M/Tm) — update chunks innermost, so the
            (Tn, Tp) output tile is REVISITED across the Tm-chunks of the
            sampled-update matrix (m scales with N, so the (M, Tp) block
            must be tiled too or it alone would blow VMEM at datacenter
            m).  Chunk step k: the scatter is a one-hot MXU matmul —
            ``onehot (Tn, Tm) @ upd_k (Tm, Tp)`` with ``onehot[r, c] =
            (row r == sel_k[c])`` — overwriting exactly the hit rows of
            the carried tile (the one-hot products are 1·x + 0·…, so the
            scattered panel is BIT-identical to the jnp ``.at[sel].set``
            reference; sel chunks are disjoint so chunk order cannot
            conflict).  On the LAST chunk the finished tile feeds the
            weighted row reduction ``w (1, Tn) @ tile (Tn, Tp)``
            accumulated into a revisited (1, Tp) output block (the same
            running-accumulator pattern as ``kernels/solver.py``) — the
            post-scatter panel is reduced where it is produced and never
            re-read from HBM.

Per-round HBM traffic: the O(mP) update rows + one tiled O(NP) panel
read/write + the O(P) reduction — nothing (N, P)-sized is ever
materialized per params leaf (the pytree is raveled to one flat axis by
the caller).  The reduction's tile-order partial sums differ from the ref
path's single (N,)·(N, P) tensordot, so reduction parity is NUMERICAL
(allclose, pinned by ``tests/test_aggregator_device.py``), while the
scattered panel is bit-identical.

Invalid/pad scatter slots are encoded as ``sel = -1`` (never equal to a
row id); pad rows of the panel carry zero weight, pad columns are sliced
off by the ``kernels/ops.py`` wrapper.  Tiles are f32; the (1, Tp)
accumulator and (1, Tm) sel row are sub-tile but legal (the compiler pads
sublanes).  VMEM at the (512, 2048) panel tile with Tm = 256 update
chunks: mem + new_mem double-buffered 16 MiB + upd 4 MiB + one-hot and
scatter temporaries — over the compiler's 16 MiB scoped default, so the
call raises its limit to ``AGG_VMEM_LIMIT``.  On CPU the kernel runs under
``interpret=True`` — tiles scale up at large panels to keep the grid
small (every interpret grid step re-writes the (N, P) output; see the
perf note in ``kernels/ops.py``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

AGG_TN = 256        # memory-panel tile rows (clients)
AGG_TP = 512        # memory-panel tile cols (flat params)
AGG_TM = 256        # sampled-update chunk rows
# scoped-VMEM limit for the kernel: the (512, 2048) heuristic tile
# double-buffers mem and new_mem (16 MiB) beside the update chunk, the
# one-hot and the scatter temporaries — over the compiler's 16 MiB
# default, well inside a v5e core's 128 MiB of VMEM
AGG_VMEM_LIMIT = 48 * 2 ** 20


def _memagg_kernel(sel_ref, w_ref, upd_ref, mem_ref, newmem_ref, red_ref):
    i, k = pl.program_id(1), pl.program_id(2)      # row tile, update chunk
    nk = pl.num_programs(2)
    tn, tp = newmem_ref.shape
    tm = upd_ref.shape[0]

    @pl.when(k == 0)
    def _load():
        newmem_ref[...] = mem_ref[...]

    # one-hot scatter of this update chunk: row ids are exact in f32
    # (N < 2^24), sel = -1 for invalid/pad slots never matches.  The
    # matmul must not see non-finite update entries — 0 · NaN = NaN would
    # leak one diverged client's NaN into every other scattered row of the
    # chunk — so they are zeroed for the dot and restored as NaN through a
    # second one-hot dot on the non-finite mask (DESIGN.md §12: finite
    # panels are bit-identical; a client's non-finite entries land as NaN
    # in that client's row only, as a NaN-poisoned row marks itself).
    # int32 iota converted to f32: Mosaic's iota is integer-only
    rows = (jax.lax.broadcasted_iota(jnp.int32, (tn, tm), 0)
            + i * tn).astype(jnp.float32)
    onehot = (rows == sel_ref[...]).astype(jnp.float32)
    u = upd_ref[...]
    finite = jnp.isfinite(u)
    # every dot asks for float32 precision: at a lower one the MXU would
    # round the scattered updates to bf16 and the scatter stops being exact
    scat = jnp.dot(onehot, jnp.where(finite, u, 0.0),
                   preferred_element_type=jnp.float32,
                   precision=jax.lax.Precision.HIGHEST)
    bad = jnp.dot(onehot, 1.0 - finite.astype(jnp.float32),
                  preferred_element_type=jnp.float32,
                  precision=jax.lax.Precision.HIGHEST)
    scat = jnp.where(bad > 0.0, jnp.float32(jnp.nan), scat)
    hit = jnp.sum(onehot, axis=1, keepdims=True) > 0.5
    newmem_ref[...] = jnp.where(hit, scat, newmem_ref[...])

    @pl.when((i == 0) & (k == 0))
    def _init():
        red_ref[...] = jnp.zeros_like(red_ref)

    @pl.when(k == nk - 1)                          # tile fully scattered
    def _reduce():
        red_ref[...] += jnp.dot(w_ref[...], newmem_ref[...],
                                preferred_element_type=jnp.float32,
                                precision=jax.lax.Precision.HIGHEST)


@functools.partial(jax.jit,
                   static_argnames=("tile_n", "tile_p", "tile_m", "interpret"))
def memagg_pallas(mem: jax.Array, upd: jax.Array, sel: jax.Array,
                  w: jax.Array, *, tile_n: int = AGG_TN,
                  tile_p: int = AGG_TP, tile_m: int = AGG_TM,
                  interpret: bool = False):
    """mem (N, P) f32 panel, upd (M, P) f32 sampled updates, sel (1, M) f32
    target rows (−1 = invalid), w (1, N) f32 reduction weights ->
    (new_mem (N, P), red (1, P))."""
    n, p = mem.shape
    mm = upd.shape[0]
    assert n % tile_n == 0 and p % tile_p == 0 and mm % tile_m == 0, \
        (mem.shape, upd.shape, tile_n, tile_p, tile_m)
    assert upd.shape == (mm, p) and sel.shape == (1, mm) and w.shape == (1, n)
    grid = (p // tile_p, n // tile_n, mm // tile_m)   # chunks innermost
    return pl.pallas_call(
        _memagg_kernel,
        name="memagg_pallas",
        grid=grid,
        in_specs=[pl.BlockSpec((1, tile_m), lambda j, i, k: (0, k)),
                  pl.BlockSpec((1, tile_n), lambda j, i, k: (0, i)),
                  pl.BlockSpec((tile_m, tile_p), lambda j, i, k: (k, j)),
                  pl.BlockSpec((tile_n, tile_p), lambda j, i, k: (i, j))],
        out_specs=[pl.BlockSpec((tile_n, tile_p), lambda j, i, k: (i, j)),
                   pl.BlockSpec((1, tile_p), lambda j, i, k: (0, j))],
        out_shape=[jax.ShapeDtypeStruct((n, p), jnp.float32),
                   jax.ShapeDtypeStruct((1, p), jnp.float32)],
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=AGG_VMEM_LIMIT),
        interpret=interpret,
    )(sel, w, upd, mem)
