"""Device-native aggregator subsystem.

FedGS fights long-term bias on the *sampling* side (Eq. 6 -> Eq. 16); under
arbitrary availability the server update is the other bias lever: FedAR
(Jiang et al., 2024) and MIFA-style memory aggregation keep and rectify the
last update of EVERY client — including unavailable ones — which directly
reduces the participation bias FedGS targets.  Until this module the server
side was one hard-coded FedAvg ``aggregate()`` (Eq. 18) — the last per-round
step that was not a subsystem.  This is the graph/availability/sampler
unification applied to aggregation (DESIGN.md §12): ONE pure,
jit/vmap/scan-traceable implementation of every server-update rule that the
scan engine carries through ``lax.scan``, the host engine wraps eagerly
(``fed/server.py::ServerAggregator``), and mixed-aggregator sweep cells
batch through a single ``run_batch`` program.

An :class:`AggregatorProcess` is

    ``init(params0, n_clients) -> state``                      (eager, host)
    ``apply(state, key, stacked_updates, weights, s, avail, t)
        -> (params, state)``                              (pure, traceable)

where ``stacked_updates`` is the (M, ...) pytree of locally-trained client
params, ``weights`` the (M,) Eq. 18 weights (``n_k * valid_k`` — pads carry
zero), ``s``/``avail`` the (N,) selection/availability masks, and every
family compiles to ONE ``lax.switch`` branch index
(:func:`make_aggregator_step`) so cells of DIFFERENT aggregators vmap-batch
together — previously the aggregation rule was not even a knob.

Families (``FAMILIES`` — the switch order; == ``scan_engine.AGGREGATORS``):

  ========= ================== ===========================================
  family    process            server update
  ========= ================== ===========================================
  fedavg    FedAvgProcess      Eq. 18 ``theta = sum w_k theta_k / sum w``
                               (bit-parity with the legacy ``aggregate()``
                               pinned), zero-weight guard -> params kept
  fedavgm   FedAvgMProcess     server momentum (Hsu et al. 2019):
                               ``mom = beta mom + (prev - avg)``,
                               ``theta = prev - lr_s mom``
  fedadam   FedAdamProcess     adaptive server step (Reddi et al. 2021,
                               no bias correction, per the paper):
                               ``m = b1 m + (1-b1) d``, ``v = b2 v +
                               (1-b2) d^2``, ``theta = prev + lr_s m /
                               (sqrt(v) + eps)`` with ``d = avg - prev``
  fedprox_w FedProxWProcess    proximal-weighted averaging: Eq. 18 with
                               ``w_k / (1 + mu ||theta_k - prev||^2)`` —
                               far-drifted clients are down-weighted
  memory    MemoryProcess      FedAR/MIFA-style rectification: a per-client
                               (N, P) last-update table; participants
                               overwrite their row, then ``theta = sum_k
                               w_k mem_k`` over ALL N clients with
                               staleness-discounted weights
                               ``w_k ∝ n_k gamma^(t - tau_k)``
  median    MedianProcess      coordinate-wise lower median of the valid
                               updates — 1/2 breakdown point per
                               coordinate (robust-statistics classic)
  trimmed_  TrimmedMeanProcess per-coordinate beta-trimmed mean: drop the
  mean                         k = floor(beta v) smallest and largest
                               entries, average the rest (Yin et al. 2018)
  krum      KrumProcess        Krum / multi-Krum (Blanchard et al. 2017):
                               score_i = sum of the v − f − 2 smallest
                               squared distances to the other updates;
                               keep the k lowest-scoring updates and
                               average them uniformly.  The (m, m)
                               distance panel dispatches ``ref | pallas``
                               (``kernels/krum.py``)
  ========= ================== ===========================================

The three robust families are the fault-tolerance counterpart of the
``fed/faults_device.py`` injection seam: they deliberately IGNORE the
Eq. 18 size weights (a data-rich Byzantine client must not buy itself
extra mass) and map NaN-poisoned coordinates to +inf before sorting, so
the PR-5 NaN-containment semantics hold for them too — a poisoned update
is an extreme order statistic, trimmed/out-voted like any other outlier.

The runtime representation is a uniform *params* pytree (family index,
packed ``theta`` knobs) plus a uniform *state* pytree (``prev`` global
params, two params-shaped moment slots ``m1``/``m2``, the flat ``mem``
(N, P) update-memory panel and its ``tau`` (N,) last-participation
vector), so heterogeneous aggregators stack along a vmap batch axis
(``scan_engine.stack_cells``).  ``prev`` doubles as the global-parameter
scan carry: the engines read ``state["prev"]`` instead of carrying params
twice.

The memory family dispatches ``backend="ref" | "pallas"`` exactly like
``fedgs_solve``: ``ref`` is the pure-jnp O(mP) row scatter + one (N,) @
(N, P) reduction; ``pallas`` routes both through
``kernels/ops.memory_aggregate`` (``kernels/aggregate.py``) — the masked
scatter of the m sampled rows is fused in-tile (one-hot MXU matmul) with
the staleness-weighted row reduction, so the post-scatter panel is
consumed where it is produced and nothing (N, P)-sized is materialized per
params leaf (the pytree is raveled to ONE flat (P,) axis).  The scattered
panel is BIT-identical across backends; the reduction is numerically equal
(tile-order partial sums — asserted in tests and BENCH_aggregator.json).
"""
from __future__ import annotations

from dataclasses import dataclass

import numpy as np

import jax
import jax.numpy as jnp
from jax import flatten_util

from repro.core.sampler_device import select_k

FAMILIES = ("fedavg", "fedavgm", "fedadam", "fedprox_w", "memory",
            "median", "trimmed_mean", "krum")
BACKENDS = ("ref", "pallas")

THETA_DIM = 6          # packed per-family scalar knobs (see the branch readers)


# ------------------------------------------------------------ shared helpers
def _flat_template(params_like):
    """(ravel, unravel, P) for a params pytree of arrays OR ShapeDtypeStructs
    — the one flattening convention every memory-panel consumer shares."""
    zeros = jax.tree_util.tree_map(
        lambda x: jnp.zeros(x.shape, x.dtype), params_like)
    flat0, unravel = flatten_util.ravel_pytree(zeros)

    def ravel(pt):
        return flatten_util.ravel_pytree(pt)[0].astype(jnp.float32)

    return ravel, unravel, int(flat0.shape[0])


def guard_zero_weight(avg, prev, total):
    """The ONE zero-weight guard (assumption log #15): keep ``avg`` when
    any weight fired, fall back to the previous params on an all-zero
    round — shared by ``fedavg_combine`` and the memory branch so the
    guard semantics cannot diverge between families."""
    return jax.tree_util.tree_map(
        lambda a, p0: jnp.where(total > 0, a, p0.astype(a.dtype)),
        avg, prev)


def fedavg_combine(stacked_params, weights, prev_params=None):
    """Eq. 18: ``theta = sum_k w_k theta_k, w_k = n_k / sum n`` — the EXACT
    legacy ``fed/server.aggregate`` op order (bit-parity pinned by
    ``tests/test_aggregator_device.py``), plus the zero-weight guard: with
    ``prev_params`` given and all weights zero (a forced all-unavailable
    round), the previous global params are returned instead of the all-zero
    pytree ``0 / 1e-12`` used to produce.  ``prev_params=None`` keeps the
    unguarded legacy behaviour for callers without a previous model."""
    total = jnp.sum(weights)
    w = weights / jnp.maximum(total, 1e-12)

    def wsum(p):
        return jnp.tensordot(w.astype(p.dtype), p, axes=(0, 0))

    avg = jax.tree_util.tree_map(wsum, stacked_params)
    if prev_params is None:
        return avg
    return guard_zero_weight(avg, prev_params, total)


def init_agg_state(params0, n_clients: int,
                   memory_rows: int | None = None,
                   tau_rows: int | None = None) -> dict:
    """The uniform carried state every family shares (family-INDEPENDENT, so
    the engines build it without knowing the cell's aggregator):

      ``prev``  the global params (this slot IS the engines' param carry)
      ``m1``    momentum / Adam first moment        (zeros)
      ``m2``    Adam second moment                  (zeros)
      ``mem``   (N, P) per-client last-update panel, every row initialized
                to flat(params0) — a never-seen client contributes the
                INITIAL model, discounted by its staleness (DESIGN.md
                assumption log #15)
      ``tau``   (N,) last participation round, init 0 (the memory rows are
                treated as a round-0 pseudo-update)

    ``memory_rows`` overrides the panel row count: the eager host path
    passes 0 for non-memory families so a big-model FedAvg run never
    materializes the (N, P) panel (the pytree KEYS stay — uniformity is
    about structure; the scan path keeps the full panel because cells of
    any family share one switch program).  ``tau_rows`` decouples the
    ``tau`` vector length from the panel rows: the shard_map'd engine's
    psum mode keeps ``tau`` global (N,) while each silo shard holds only
    its (N/s, P) panel slice (DESIGN.md §13).
    """
    rows = n_clients if memory_rows is None else memory_rows
    trows = rows if tau_rows is None else tau_rows
    ravel, _, _ = _flat_template(params0)
    zeros = jax.tree_util.tree_map(jnp.zeros_like, params0)
    flat0 = ravel(params0)
    return {"prev": params0,
            "m1": zeros,
            "m2": zeros,
            "mem": jnp.tile(flat0[None, :], (rows, 1)),
            "tau": jnp.zeros((trows,), jnp.float32)}


def memory_scatter_reduce_ref(mem, upd, sel, valid, w):
    """The memory family's REF backend, shared by the switch branch, the
    benchmark and the parity tests (so 'ref vs pallas' always compares the
    shipped path): O(mP) masked row scatter + one (N,)·(N, P) tensordot,
    at float32 precision (a TPU's default f32 contraction rounds operands
    to bf16)."""
    mem2 = mem.at[sel].set(jnp.where(valid[:, None], upd, mem[sel]))
    return mem2, jnp.tensordot(w, mem2, axes=(0, 0),
                               precision=jax.lax.Precision.HIGHEST)


# ----------------------------------------------------- robust combine rules
# Shared by the switch branches, the numpy-oracle tests and the robustness
# bench, so "branch vs oracle" always pins the shipped math.  All three
# operate on the flat (M, P) update panel with a (M,) valid mask, map
# NaN-poisoned coordinates and pad rows to +inf before sorting (one mapping
# buys both NaN containment and the Byzantine breakdown bound), and ignore
# the Eq. 18 size weights (see the module docstring).
def coordinate_median(updf, valid):
    """Coordinate-wise LOWER median — sorted index ``(v − 1) // 2`` of the
    v valid entries per coordinate.  With f < v/2 arbitrarily corrupted
    rows (±inf included) the median index always lands on an honest order
    statistic: at most f entries sort below it and at most f above.
    Returns ``(median (P,), v)``."""
    v = jnp.sum(valid.astype(jnp.int32))
    x = jnp.where(jnp.isnan(updf), jnp.inf, updf)
    x = jnp.where(valid[:, None], x, jnp.inf)
    srt = jnp.sort(x, axis=0)
    return srt[jnp.maximum((v - 1) // 2, 0)], v


def trimmed_mean_combine(updf, valid, beta):
    """Per-coordinate beta-trimmed mean (Yin et al. 2018): sort the v valid
    entries, drop the ``k = min(floor(beta v), (v − 1) // 2)`` smallest and
    largest, average the rest — op order is sum-then-divide over the kept
    window (assumption log #21; the oracle mirrors it).  ``k >= f`` removes
    every one-sided corruption; the f32 product ``beta * v`` floors exactly
    like the numpy-f32 oracle.  Returns ``(mean (P,), v)``."""
    v = jnp.sum(valid.astype(jnp.int32))
    x = jnp.where(jnp.isnan(updf), jnp.inf, updf)
    x = jnp.where(valid[:, None], x, jnp.inf)
    srt = jnp.sort(x, axis=0)
    k = jnp.maximum(jnp.minimum(
        jnp.floor(beta * v.astype(jnp.float32)).astype(jnp.int32),
        (v - 1) // 2), 0)
    ii = jnp.arange(updf.shape[0])[:, None]
    keep = (ii >= k) & (ii < v - k)
    kept = jnp.sum(jnp.where(keep, srt, 0.0), axis=0)
    return kept / jnp.maximum(v - 2 * k, 1).astype(jnp.float32), v


def krum_pairwise_ref(updf):
    """REF backend of the Krum squared-distance panel, shared by the switch
    branch, the bench and the ref-vs-pallas parity tests:
    ``D = ||x_i||² + ||x_j||² − 2 X Xᵀ``, the cross term at float32
    precision (a TPU's default f32 matmul rounds operands to bf16)."""
    x = updf.astype(jnp.float32)
    n2 = jnp.sum(x * x, axis=1)
    xx = jnp.matmul(x, x.T, precision=jax.lax.Precision.HIGHEST)
    return n2[:, None] + n2[None, :] - 2.0 * xx


def krum_select(updf, valid, f_byz, multi, *, backend: str = "ref",
                interpret: bool | None = None):
    """Krum / multi-Krum selection (Blanchard et al., NeurIPS 2017) over
    the valid rows of the flat (M, P) panel.

    score_i = sum of the ``nn = clip(v − f − 2, 1, m − 1)`` smallest
    squared distances from row i to the other valid rows; the ``k =
    clip(multi, 1, v)`` lowest-scoring rows win.  Rank ties break by row
    index (double STABLE argsort — ``jnp.argsort`` is stable, matching the
    ``np.argsort(kind="stable")`` oracle bit-for-bit).  Distance hygiene:
    the expansion is clamped at 0, NaN entries (inf − inf of ±inf-poisoned
    pairs, or NaN-poisoned rows) map to +inf, and diagonal / invalid pairs
    are +inf — so a poisoned row's score is +inf and it can only be chosen
    when k exceeds the finite-score rows (``chosen`` is additionally
    masked by ``valid`` so pad rows NEVER win a tie against a real row).
    ``v < f + 3`` (outside Blanchard's m >= 2f + 3 regime) degrades
    gracefully to nearest-neighbor scoring via the nn clamp.  Returns
    ``(chosen (M,) bool, scores (M,) f32)``."""
    m = updf.shape[0]
    if backend == "pallas":
        from repro.kernels.ops import krum_distances
        d = krum_distances(updf.astype(jnp.float32), interpret=interpret)
    else:
        d = krum_pairwise_ref(updf)
    d = jnp.maximum(d, 0.0)
    d = jnp.where(jnp.isnan(d), jnp.inf, d)
    pair_ok = valid[:, None] & valid[None, :] & ~jnp.eye(m, dtype=bool)
    d = jnp.where(pair_ok, d, jnp.inf)
    v = jnp.sum(valid.astype(jnp.int32))
    nn = jnp.clip(v - f_byz - 2, 1, max(m - 1, 1))
    ds = jnp.sort(d, axis=1)
    take = jnp.arange(m)[None, :] < nn
    scores = jnp.sum(jnp.where(take, ds, 0.0), axis=1)
    scores = jnp.where(valid, scores, jnp.inf)
    kk = jnp.clip(multi, 1, jnp.maximum(v, 1))
    rank = jnp.argsort(jnp.argsort(scores))
    chosen = (rank < kk) & valid
    return chosen, scores


def krum_combine(updf, valid, f_byz, multi, *, backend: str = "ref",
                 interpret: bool | None = None):
    """:func:`krum_select` + the UNWEIGHTED mean of the chosen rows
    (multi-Krum averages uniformly).  Returns ``(combined (P,), chosen,
    scores)``."""
    chosen, scores = krum_select(updf, valid, f_byz, multi,
                                 backend=backend, interpret=interpret)
    cnt = jnp.sum(chosen.astype(jnp.float32))
    out = jnp.sum(jnp.where(chosen[:, None], updf.astype(jnp.float32), 0.0),
                  axis=0) / jnp.maximum(cnt, 1.0)
    return out, chosen, scores


# ------------------------------------------------------- the switch step
def make_aggregator_step(n: int, m: int, params_like, *, data_sizes=None,
                         backend: str = "ref",
                         interpret: bool | None = None,
                         family: str | None = None,
                         memory_enabled: bool = True,
                         panel_axis: str | None = None):
    """Compile-time constructor of the ONE per-round aggregator step

        ``step(aparams, state, key, stacked_updates, weights, s, avail, t)
            -> (params, state)``

    dispatching ``lax.switch`` on the cell's family index, so cells of
    DIFFERENT aggregators batch through one vmapped program (under vmap the
    switch lowers to a select over all branches; the extra branches' cost is
    small next to local training — DESIGN.md §12).

    ``params_like`` is a template pytree (arrays or ShapeDtypeStructs) that
    fixes the flat memory-panel layout; ``data_sizes`` the (N,) per-client
    sizes the memory family's rectified weights use (all-ones when omitted);
    ``backend`` routes the memory scatter+reduction (``ref`` | ``pallas``).
    ``key`` is the per-round aggregator key — reserved for stochastic
    families; none of the current five consumes it.

    ``family=None`` builds the full switch (the scan path); naming a
    family builds that single branch directly — SAME branch code, so
    numerics are identical, but the other branches never trace, which is
    what lets the eager host path (``fed/server.ServerAggregator``) skip
    the (N, P) memory panel for non-memory families.  ``memory_enabled=
    False`` aliases the switch's memory slot to the fedavg branch so a
    NO-memory-cell scan program can carry a 0-row panel
    (``init_agg_state(memory_rows=0)``) without tracing the scatter —
    callers (``ScanEngine``) must dispatch memory cells to a
    memory-enabled program.

    ``panel_axis`` names a shard_map mesh axis over which the (N, P)
    memory panel is ROW-sharded (the scan engine's "silo" axis, DESIGN.md
    §13): the step then sees only the local (N/s, P) slice in
    ``state["mem"]`` (``tau`` stays global (N,)), scatters the sampled
    rows that land in its slice (out-of-range indices drop, XLA scatter
    semantics), reduces its partial staleness-weighted sum and ``psum``s
    across the axis — the per-tile locality of the fused kernel turned
    into a collective.  Only meaningful inside ``shard_map``.
    """
    if backend not in BACKENDS:
        raise ValueError(f"backend must be one of {BACKENDS}, not {backend!r}")
    if family is not None and family not in FAMILIES:
        raise ValueError(f"family must be one of {FAMILIES}, not {family!r}")
    if family == "memory" and not memory_enabled:
        raise ValueError("family='memory' requires memory_enabled=True")
    ravel, unravel, _ = _flat_template(params_like)
    sizes = (jnp.ones((n,), jnp.float32) if data_sizes is None
             else jnp.asarray(data_sizes, jnp.float32))

    def _fedavg(ap, state, key, upd, w, s, avail, t, sel, valid):
        new = fedavg_combine(upd, w, state["prev"])
        return new, {**state, "prev": new}

    def _fedavgm(ap, state, key, upd, w, s, avail, t, sel, valid):
        """Server momentum on the pseudo-gradient ``prev - avg`` (a
        zero-weight round contributes a zero pseudo-gradient: the momentum
        keeps decaying, the params keep drifting along it)."""
        lr_s, beta = ap["theta"][0], ap["theta"][1]
        avg = fedavg_combine(upd, w, state["prev"])
        m1 = jax.tree_util.tree_map(
            lambda mo, p0, a: beta * mo + (p0 - a), state["m1"],
            state["prev"], avg)
        new = jax.tree_util.tree_map(
            lambda p0, mo: p0 - lr_s * mo, state["prev"], m1)
        return new, {**state, "prev": new, "m1": m1}

    def _fedadam(ap, state, key, upd, w, s, avail, t, sel, valid):
        """Reddi et al. 2021 FedAdam (no bias correction, per the paper)."""
        lr_s, b1, b2 = ap["theta"][0], ap["theta"][1], ap["theta"][2]
        eps = ap["theta"][3]
        avg = fedavg_combine(upd, w, state["prev"])
        delta = jax.tree_util.tree_map(
            lambda a, p0: a - p0, avg, state["prev"])
        m1 = jax.tree_util.tree_map(
            lambda mo, d: b1 * mo + (1.0 - b1) * d, state["m1"], delta)
        m2 = jax.tree_util.tree_map(
            lambda vo, d: b2 * vo + (1.0 - b2) * d * d, state["m2"], delta)
        new = jax.tree_util.tree_map(
            lambda p0, mo, vo: p0 + lr_s * mo / (jnp.sqrt(vo) + eps),
            state["prev"], m1, m2)
        return new, {**state, "prev": new, "m1": m1, "m2": m2}

    def _fedprox_w(ap, state, key, upd, w, s, avail, t, sel, valid):
        """Eq. 18 with each weight damped by the client's squared drift from
        the previous global model — far-drifted (non-iid-shocked) updates
        pull less.  Pads keep zero weight (0 / (1 + mu·drift) = 0)."""
        mu = ap["theta"][0]
        prevf = ravel(state["prev"])
        updf = jax.vmap(ravel)(upd)                       # (M, P)
        drift = jnp.sum((updf - prevf[None, :]) ** 2, axis=1)
        w2 = w / (1.0 + mu * drift)
        new = fedavg_combine(upd, w2, state["prev"])
        return new, {**state, "prev": new}

    def _memory(ap, state, key, upd, w, s, avail, t, sel, valid):
        """FedAR/MIFA-style memory rectification over ALL N clients: the m
        sampled rows are scattered into the (N, P) panel, then the new
        params are the staleness-discounted, size-weighted row reduction
        ``sum_k n_k gamma^(t - tau_k) mem_k / Z`` — unavailable clients'
        last updates keep pulling the average, which is the bias
        correction (DESIGN.md assumption log #14).  gamma -> 0 recovers
        FedAvg over the sampled set; gamma = 1 is full MIFA memory."""
        gamma = ap["theta"][0]
        updf = jax.vmap(ravel)(upd)                       # (M, P)
        tf = t.astype(jnp.float32)
        tau = jnp.where(s, tf, state["tau"])
        age = jnp.maximum(tf - tau, 0.0)
        wmem = sizes * gamma ** age                       # (N,)
        total = jnp.sum(wmem)
        wn = wmem / jnp.maximum(total, 1e-12)
        if panel_axis is not None:
            # row-sharded panel: scatter the sampled rows that fall in this
            # shard's slice (out-of-range indices drop), partial-reduce the
            # local rows, psum the (P,) partials across the silo axis
            rows = state["mem"].shape[0]
            off = jax.lax.axis_index(panel_axis) * rows
            lsel = sel - off
            hit = valid & (lsel >= 0) & (lsel < rows)
            mem = state["mem"].at[jnp.where(hit, lsel, rows)].set(updf)
            wn_l = jax.lax.dynamic_slice_in_dim(wn, off, rows)
            red = jax.lax.psum(
                jnp.tensordot(wn_l, mem, axes=(0, 0),
                              precision=jax.lax.Precision.HIGHEST),
                panel_axis)
        elif backend == "pallas":
            from repro.kernels.ops import memory_aggregate
            mem, red = memory_aggregate(state["mem"], updf, sel, valid, wn,
                                        interpret=interpret)
        else:
            mem, red = memory_scatter_reduce_ref(state["mem"], updf, sel,
                                                 valid, wn)
        new = guard_zero_weight(unravel(red), state["prev"], total)
        return new, {**state, "prev": new, "mem": mem, "tau": tau}

    def _median(ap, state, key, upd, w, s, avail, t, sel, valid):
        """Coordinate-wise lower median of the valid updates (weights
        ignored — see :func:`coordinate_median`)."""
        med, v = coordinate_median(jax.vmap(ravel)(upd), valid)
        new = guard_zero_weight(unravel(med), state["prev"], v)
        return new, {**state, "prev": new}

    def _trimmed_mean(ap, state, key, upd, w, s, avail, t, sel, valid):
        """Per-coordinate beta-trimmed mean, ``beta = theta[0]``."""
        beta = ap["theta"][0]
        tm, v = trimmed_mean_combine(jax.vmap(ravel)(upd), valid, beta)
        new = guard_zero_weight(unravel(tm), state["prev"], v)
        return new, {**state, "prev": new}

    def _krum(ap, state, key, upd, w, s, avail, t, sel, valid):
        """Krum / multi-Krum, ``f = theta[0]``, ``k = theta[1]``; the
        distance panel routes through the module ``backend`` knob (the
        same ``agg_backend`` that routes the memory scatter)."""
        f_byz = jnp.round(ap["theta"][0]).astype(jnp.int32)
        multi = jnp.round(ap["theta"][1]).astype(jnp.int32)
        out, chosen, _ = krum_combine(jax.vmap(ravel)(upd), valid, f_byz,
                                      multi, backend=backend,
                                      interpret=interpret)
        new = guard_zero_weight(unravel(out), state["prev"],
                                jnp.sum(chosen.astype(jnp.int32)))
        return new, {**state, "prev": new}

    branches = {"fedavg": _fedavg, "fedavgm": _fedavgm, "fedadam": _fedadam,
                "fedprox_w": _fedprox_w,
                "memory": _memory if memory_enabled else _fedavg,
                "median": _median, "trimmed_mean": _trimmed_mean,
                "krum": _krum}

    def step(aparams, state, key, stacked_updates, weights, s, avail, t,
             sel=None, valid=None):
        """``sel``/``valid`` (the ``select_k(s, m)`` gather of the engines)
        can be passed when the caller already computed them — otherwise
        they are derived here (same helper, same order)."""
        t = jnp.asarray(t, jnp.int32)
        if sel is None:
            sel, valid = select_k(s, m)
        if family is not None:
            return branches[family](aparams, state, key, stacked_updates,
                                    weights, s, avail, t, sel, valid)
        return jax.lax.switch(aparams["family"],
                              [branches[f] for f in FAMILIES],
                              aparams, state, key, stacked_updates,
                              weights, s, avail, t, sel, valid)

    return step


# ------------------------------------------------------------ the processes
@dataclass
class AggregatorProcess:
    """Base class.  ``params()``/``init(params0, n)`` are eager host-side
    constructors of the per-cell runtime pytrees; :meth:`apply` is the pure
    traceable entry point (single-process convenience over the switch step,
    guaranteed identical because it IS the switch path).  Every family fills
    the SAME params pytree (family index, packed theta) so heterogeneous
    aggregator cells stack along a vmap batch axis
    (``scan_engine.stack_cells``)."""

    family = "fedavg"
    name = "process"

    def _theta(self) -> np.ndarray:
        return np.zeros(0)

    def params(self) -> dict:
        theta = np.zeros(THETA_DIM, np.float32)
        th = np.asarray(self._theta(), np.float32)
        theta[:th.shape[0]] = th
        return {"family": jnp.int32(FAMILIES.index(self.family)),
                "theta": jnp.asarray(theta)}

    def init(self, params0, n_clients: int) -> dict:
        """Initial carried state — family-independent (the uniform pytree
        of :func:`init_agg_state`), so the engines can build it without
        inspecting the process."""
        return init_agg_state(params0, n_clients)

    # -- traceable entry point --------------------------------------------
    def apply(self, state, key, stacked_updates, weights, s, avail, t, *,
              data_sizes=None, backend: str = "ref",
              interpret: bool | None = None):
        """Single-shot convenience; ``m`` is read off the stacked leading
        axis.  ``data_sizes`` feeds the memory family's rectified weights —
        without it they fall back to all-ones."""
        n = s.shape[-1]
        m = int(jax.tree_util.tree_leaves(stacked_updates)[0].shape[0])
        step = make_aggregator_step(n, m, state["prev"],
                                    data_sizes=data_sizes, backend=backend,
                                    interpret=interpret)
        return step(self.params(), state, key, stacked_updates, weights,
                    s, avail, t)


@dataclass
class FedAvgProcess(AggregatorProcess):
    """Eq. 18, bit-parity with the legacy ``aggregate()`` (plus the
    zero-weight guard)."""
    name: str = "fedavg"
    family = "fedavg"


@dataclass
class FedAvgMProcess(AggregatorProcess):
    """Hsu et al. 2019 server momentum."""
    server_lr: float = 1.0
    beta: float = 0.9
    name: str = "fedavgm"
    family = "fedavgm"

    def _theta(self):
        return np.array([self.server_lr, self.beta])


@dataclass
class FedAdamProcess(AggregatorProcess):
    """Reddi et al. 2021 adaptive federated optimization (FedAdam)."""
    server_lr: float = 0.1
    beta1: float = 0.9
    beta2: float = 0.99
    eps: float = 1e-3
    name: str = "fedadam"
    family = "fedadam"

    def _theta(self):
        return np.array([self.server_lr, self.beta1, self.beta2, self.eps])


@dataclass
class FedProxWProcess(AggregatorProcess):
    """Proximal-weighted averaging: ``w_k <- w_k / (1 + mu ||d_k||^2)``."""
    mu: float = 0.1
    name: str = "fedprox_w"
    family = "fedprox_w"

    def _theta(self):
        return np.array([self.mu])


@dataclass
class MemoryProcess(AggregatorProcess):
    """FedAR/MIFA-style per-client update memory with staleness-discounted
    rectification; ``gamma`` is the per-round staleness discount (per-cell
    traced, so gamma-variants batch together)."""
    gamma: float = 0.9
    name: str = "memory"
    family = "memory"

    def __post_init__(self):
        self.name = f"memory(gamma={self.gamma})"

    def _theta(self):
        return np.array([max(self.gamma, 1e-6)])


@dataclass
class MedianProcess(AggregatorProcess):
    """Coordinate-wise lower median (1/2 breakdown per coordinate)."""
    name: str = "median"
    family = "median"


@dataclass
class TrimmedMeanProcess(AggregatorProcess):
    """Per-coordinate beta-trimmed mean (Yin et al. 2018); ``beta`` is the
    per-side trim fraction (per-cell traced, so beta-variants batch)."""
    beta: float = 0.2
    name: str = "trimmed_mean"
    family = "trimmed_mean"

    def __post_init__(self):
        self.name = f"trimmed_mean(beta={self.beta})"

    def _theta(self):
        return np.array([self.beta])


@dataclass
class KrumProcess(AggregatorProcess):
    """Krum / multi-Krum (Blanchard et al. 2017): ``f`` is the Byzantine
    budget the score defends against, ``multi`` the number of selected
    updates averaged (1 = classic Krum)."""
    f: int = 1
    multi: int = 1
    name: str = "krum"
    family = "krum"

    def __post_init__(self):
        self.name = (f"krum(f={self.f})" if self.multi <= 1
                     else f"multikrum(f={self.f},k={self.multi})")

    def _theta(self):
        return np.array([float(self.f), float(self.multi)])


def make_aggregator_process(name: str, *, server_lr: float | None = None,
                            beta: float = 0.9, mu: float = 0.1,
                            gamma: float = 0.9, beta_trim: float = 0.2,
                            krum_f: int = 1,
                            krum_multi: int = 1) -> AggregatorProcess:
    """Family names (= ``scan_engine.AGGREGATORS``) -> processes."""
    name = name.lower()
    if name == "fedavg":
        return FedAvgProcess()
    if name == "fedavgm":
        return FedAvgMProcess(server_lr=1.0 if server_lr is None
                              else server_lr, beta=beta)
    if name == "fedadam":
        return FedAdamProcess(server_lr=0.1 if server_lr is None
                              else server_lr)
    if name in ("fedprox_w", "fedproxw"):
        return FedProxWProcess(mu=mu)
    if name == "memory":
        return MemoryProcess(gamma=gamma)
    if name == "median":
        return MedianProcess()
    if name in ("trimmed_mean", "trimmedmean"):
        return TrimmedMeanProcess(beta=beta_trim)
    if name in ("krum", "multikrum"):
        return KrumProcess(f=krum_f,
                           multi=krum_multi if name == "krum" else
                           max(krum_multi, 2))
    raise ValueError(f"unknown aggregator family {name!r}")
