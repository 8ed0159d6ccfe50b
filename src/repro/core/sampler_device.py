"""Device-native sampler subsystem.

The availability refactor's sampler twin (DESIGN.md §11): ONE pure,
jit/vmap/scan-traceable implementation of every client sampler — the paper's
FedGS Eq. 16 solver and the Table-2 baselines — that the scan engine carries
through ``lax.scan``, the host classes wrap in numpy (``core/sampler.py``),
and mixed-sampler sweep cells batch through a single ``run_batch`` program.

A :class:`SamplerProcess` is

    ``init(key) -> state``                                    (eager, host)
    ``select(state, key, inputs, avail, t) -> (s, state)``    (pure, traceable)

where ``inputs`` is the per-round context dict the engine assembles
(``{"h", "counts", "params", ...}``), ``s`` is the (N,) bool selection mask
with ``|s| = min(m, |A_t|)``, and every family compiles to ONE
``lax.switch`` branch index (:func:`make_sampler_step`) so cells of
DIFFERENT samplers vmap-batch together — previously sampler choice was a
per-cell Python branch and only availability heterogeneity batched.

Families (``FAMILIES`` — the switch order; it matches the scan engine's
``SAMPLERS`` knob):

  ======== ==================== ==========================================
  family   process              selection rule
  ======== ==================== ==========================================
  fedgs    FedGSProcess         Eq. 14/16: Q = sym(α/N·H) − diag(z), then
                                the greedy + best-swap p-dispersion solve
                                (α-variants batch via the per-cell alpha)
  uniform  UniformProcess       Gumbel top-m, equal weights (McMahan 2017)
  md       MDProcess            Gumbel top-m, weights ∝ data size (Li 2020)
  poc      PoCProcess           Gumbel top-d·m candidates by size, keep the
                                top-m by probed loss (Cho et al. 2020)
  ======== ==================== ==========================================

The FedGS solver itself dispatches ``backend="ref" | "pallas"`` exactly like
``core/graph_device.build_h``: ``ref`` is the pure-jnp greedy + best-swap
(dense (N, N) Q and delta per sweep); ``pallas`` is Q-FREE — the solve runs
on the factored (H, z, alpha/N) via ``kernels/solver.q_diag``/``q_row``
providers, the greedy blocked masked argmax, and the fused swap kernel that
rebuilds Q tiles in VREGs (``kernels/ops.swap_best_fused``) — neither Q nor
anything else N² is ever materialized, which is what lets the solve run at
N ∈ {4096, 16384} (``benchmarks/sampler_scaling.py``).
Both backends produce BIT-IDENTICAL selected sets (tie-breaks and the NaN
guard are pinned by ``tests/test_sampler_device.py``; DESIGN.md assumption
log #12/#13).
"""
from __future__ import annotations

from dataclasses import dataclass

import numpy as np

import jax
import jax.numpy as jnp
from functools import partial

FAMILIES = ("fedgs", "uniform", "md", "poc")
BACKENDS = ("ref", "pallas")

# masked-entry sentinel (== kernels/solver.NEG).  A PYTHON float, never a
# module-level jnp constant: this module may first be imported inside an
# active jit trace (launch.fedsim defers its import), where jnp scalars
# materialize as tracers and would leak out of the trace.
NEG = -1e18
SWAP_TOL = 1e-9             # a swap must improve Eq. 16 by more than this


# ------------------------------------------------------------ shared helpers
def select_k(s: jax.Array, k: int):
    """Mask (N,) bool -> (sorted selected indices (k,), valid (k,)) — the
    static-shape gather order every layer shares: selected indices ascending,
    then pad slots (``valid`` False) ascending."""
    n = s.shape[0]
    order = jnp.argsort(jnp.where(s, jnp.arange(n), n + jnp.arange(n)))
    sel = order[:k]
    return sel, s[sel]


def log_size_weights(data_sizes) -> jax.Array:
    """The MD/PoC Gumbel log-weights with the degenerate-size guard: the
    ``maximum(·, 1e-12)`` floor turns all-zero data sizes into EQUAL finite
    weights (uniform sampling) instead of NaNs, and zero-size clients keep a
    finite score so they can still fill the mask when fewer than m
    positive-size clients are available."""
    return jnp.log(jnp.maximum(jnp.asarray(data_sizes).astype(jnp.float32),
                               1e-12))


# ------------------------------------------- device-side baseline sampling
def gumbel_topk_select(key: jax.Array, log_weights: jax.Array,
                       avail: jax.Array, m: int) -> jax.Array:
    """Weighted sampling WITHOUT replacement among available clients, fully
    on-device (Gumbel top-k): adding i.i.d. Gumbel noise to log-weights and
    taking the top-m reproduces successive draws without replacement with
    probabilities proportional to the weights.  With uniform weights this is
    ``UniformSampler``; with ``log(data_sizes)`` it is ``MDSampler`` — the
    jit-compatible counterparts used inside ``repro.fed.scan_engine``.

    Returns s (N,) bool with exactly min(m, |avail|) True entries.
    """
    g = jax.random.gumbel(key, log_weights.shape, dtype=jnp.float32)
    scores = jnp.where(avail, log_weights + g, -jnp.inf)
    _, idx = jax.lax.top_k(scores, m)
    valid = avail[idx]                      # fewer than m available -> drop pads
    s = jnp.zeros(log_weights.shape, bool)
    return s.at[idx].set(valid)


def uniform_select(key, avail, m: int):
    """Device-side UniformSampler: uniform without replacement among A_t."""
    return gumbel_topk_select(key, jnp.zeros(avail.shape, jnp.float32), avail, m)


def md_select(key, data_sizes, avail, m: int):
    """Device-side MDSampler: without replacement, P(k) ∝ n_k, among A_t
    (degenerate sizes handled by the :func:`log_size_weights` floor)."""
    return gumbel_topk_select(key, log_size_weights(data_sizes), avail, m)


# ------------------------------------------------------------- FedGS solver
def _solve_ref(q: jax.Array, avail: jax.Array, *, m: int, max_sweeps: int):
    """The pure-jnp oracle: greedy construction + dense best-swap sweeps."""
    n = q.shape[0]
    neg = jnp.float32(NEG)

    # ---------------- greedy construction --------------------------------
    def greedy_step(carry, _):
        s, r = carry                       # s: (N,) bool, r_k = sum_{i in S} Q_ik
        gain = q.diagonal() + 2.0 * r      # marginal gain of adding k
        gain = jnp.where(s | ~avail, neg, gain)
        gain = jnp.where(jnp.isnan(gain), neg, gain)   # NaN guard (log #13)
        k = jnp.argmax(gain)
        ok = gain[k] > neg / 2             # no addable client left => no-op
        s = s.at[k].set(ok | s[k])
        r = r + jnp.where(ok, q[k], 0.0)
        return (s, r), None

    s0 = jnp.zeros((n,), bool)
    r0 = jnp.zeros((n,), jnp.float32)
    (s, r), _ = jax.lax.scan(greedy_step, (s0, r0), None, length=m)

    # ---------------- best-swap local search -----------------------------
    diag = q.diagonal()

    def sweep(carry, _):
        s, r = carry
        # delta(i -> j) = -2 r_i + Q_ii + 2 (r_j - Q_ij) + Q_jj
        out_term = (-2.0 * r + diag)                          # (N,) for i in S
        in_term = (2.0 * r + diag)                            # (N,) for j notin S
        delta = out_term[:, None] + in_term[None, :] - 2.0 * q
        delta = jnp.where(s[:, None], delta, neg)             # i must be in S
        delta = jnp.where((~s & avail)[None, :], delta, neg)  # j must be addable
        delta = jnp.where(jnp.isnan(delta), neg, delta)       # NaN guard
        flat = jnp.argmax(delta)
        i, j = flat // n, flat % n
        best = delta[i, j]

        def do_swap(args):
            s, r = args
            s2 = s.at[i].set(False).at[j].set(True)
            r2 = r - q[i] + q[j]
            return s2, r2

        s, r = jax.lax.cond(best > SWAP_TOL, do_swap, lambda a: a, (s, r))
        return (s, r), best

    (s, r), _ = jax.lax.scan(sweep, (s, r), None, length=max_sweeps)
    return s


def _solve_pallas(diag: jax.Array, row_fn, swap_fn, avail: jax.Array, *,
                  m: int, max_sweeps: int, interpret: bool | None = None):
    """The tiled solve over a PROVIDED Q: same math, same tie-breaks, no
    dense (N, N) intermediates per sweep — and, on the factored path, no
    (N, N) Q at all.  Q enters through three providers:

    diag     (N,) = diag(Q), computed once.
    row_fn   ``row_fn(k) -> (N,)`` row k of Q (the greedy/swap ``r``
             accumulator updates — one row gather per step).
    swap_fn  ``swap_fn(sel, valid, a, b) -> (best, rank, j)`` the best-swap
             reduction over the |S| ≤ m selected rows (``sel`` ascending,
             clamped; ``valid`` marks real rows) — ``kernels/ops.swap_best``
             on a materialized Q panel or ``kernels/ops.swap_best_fused``
             rebuilding Q tiles in VREGs from (H, z, alpha/N).

    greedy   ``kernels/ops.greedy_argmax`` fuses gain + mask + argmax over
             lane blocks; only the selected row of Q is gathered per step.
    sweep    the delta matrix is restricted to the |S| ≤ m SELECTED rows
             (ascending index order keeps the ref path's row-major
             tie-break) — O(mN) traffic instead of O(N²) per sweep.
    """
    from repro.kernels.ops import greedy_argmax
    n = diag.shape[0]
    if m == 0:
        return jnp.zeros((n,), bool)
    neg = jnp.float32(NEG)
    iota = jnp.arange(n)

    def greedy_step(carry, _):
        s, r = carry
        val, k = greedy_argmax(diag, r, avail & ~s, interpret=interpret)
        ok = val > neg / 2
        s = s.at[k].set(ok | s[k])
        r = r + jnp.where(ok, row_fn(k), 0.0)
        return (s, r), None

    s0 = jnp.zeros((n,), bool)
    r0 = jnp.zeros((n,), jnp.float32)
    (s, r), _ = jax.lax.scan(greedy_step, (s0, r0), None, length=m)

    def sweep(carry, _):
        s, r = carry
        out_term = (-2.0 * r + diag)
        in_term = (2.0 * r + diag)
        sel = jnp.sort(jnp.where(s, iota, n))[:m]     # |S| rows, ascending
        valid = sel < n
        selc = jnp.minimum(sel, n - 1)
        a = jnp.where(valid, out_term[selc], neg)     # pad rows can't win
        b = jnp.where(~s & avail, in_term, neg)       # j must be addable
        best, rank, j = swap_fn(selc, valid, a, b)
        i = selc[jnp.minimum(rank, m - 1)]

        def do_swap(args):
            s, r = args
            s2 = s.at[i].set(False).at[j].set(True)
            r2 = r - row_fn(i) + row_fn(j)
            return s2, r2

        s, r = jax.lax.cond(best > SWAP_TOL, do_swap, lambda a_: a_, (s, r))
        return (s, r), best

    (s, r), _ = jax.lax.scan(sweep, (s, r), None, length=max_sweeps)
    return s


def fedgs_solve(q: jax.Array, avail: jax.Array, *, m: int, max_sweeps: int,
                backend: str = "ref", interpret: bool | None = None):
    """Greedy + best-swap local search on  max s^T Q s,  |s| = m,  s <= avail.

    Pure (unjitted) so it can be inlined into larger jit programs — the
    per-round host path wraps it as ``_fedgs_solve`` below; the scan engine
    (``repro.fed.scan_engine``) and the production dry-run
    (``repro.launch.fedsim.graph_pipeline``) call it directly inside their
    own jit scopes.  If fewer than ``m`` clients are available it selects all
    of them (|S| = min(m, |A|)).

    q: (N, N) symmetric with diagonal = -z (counts penalty).
    backend: ``ref`` (pure jnp) or ``pallas`` (tiled kernels; bit-identical
    selected sets, pinned by tests/test_sampler_device.py).
    Returns s (N,) bool.
    """
    if backend == "pallas":
        from repro.kernels.ops import swap_best

        def swap_fn(selc, valid, a, b):
            return swap_best(q[selc], a, b, interpret=interpret)

        return _solve_pallas(q.diagonal(), lambda k: q[k], swap_fn, avail,
                             m=m, max_sweeps=max_sweeps, interpret=interpret)
    if backend != "ref":
        raise ValueError(f"backend must be one of {BACKENDS}, not {backend!r}")
    return _solve_ref(q, avail, m=m, max_sweeps=max_sweeps)


# jit'd entry point for the per-round host path (FedGSSampler.sample).
_fedgs_solve = partial(jax.jit, static_argnames=(
    "m", "max_sweeps", "backend", "interpret"))(fedgs_solve)


def fedgs_select(h: jax.Array, counts: jax.Array, avail: jax.Array,
                 alpha: jax.Array, *, m: int, max_sweeps: int,
                 m_target: int | None = None, backend: str = "ref",
                 interpret: bool | None = None):
    """Eq. 14/16 end-to-end: build Q from (H, counts) and run the solver.

    Pure and float32 throughout — the ONE q-construction both the host
    sampler and the scan engine (repro.fed.scan_engine) trace, so greedy
    argmax near-ties resolve identically on both paths.  ``m`` is the solver
    budget (min(M, |A_t|) on the host path); ``m_target`` is the M used in
    the count-balance penalty z (defaults to ``m``).  The pallas backend is
    Q-FREE: Q never materializes at (N, N) — the solve runs on the factored
    (H, z, alpha/N) via ``kernels/solver.q_diag``/``q_row`` (ref-op-order
    row rebuilds for the greedy accumulator) and the fused swap kernel
    ``kernels/ops.swap_best_fused`` (Q tiles rebuilt in VREGs) —
    bit-identical selected sets by op-order design (pinned by
    tests/test_sampler_device.py).
    """
    n = h.shape[0]
    mt = m if m_target is None else m_target
    z = 2.0 * (counts - counts.mean() - mt / n) + 1.0
    if backend == "pallas":
        from repro.kernels.ops import swap_best_fused
        from repro.kernels.solver import q_diag, q_row
        hf = h.astype(jnp.float32)
        zf = z.astype(jnp.float32)
        al = jnp.float32(alpha / n)

        def swap_fn(selc, valid, a, b):
            return swap_best_fused(hf, zf, al, selc, valid, a, b,
                                   interpret=interpret)

        return _solve_pallas(q_diag(hf, zf, al).astype(jnp.float32),
                             lambda k: q_row(hf, zf, al, k), swap_fn,
                             avail, m=m, max_sweeps=max_sweeps,
                             interpret=interpret)
    if backend != "ref":
        raise ValueError(f"backend must be one of {BACKENDS}, not {backend!r}")
    q = (alpha / n) * h - jnp.diag(z)
    q = 0.5 * (q + q.T)                               # symmetrize (H should be)
    return fedgs_solve(q.astype(jnp.float32), avail, m=m,
                       max_sweeps=max_sweeps, backend=backend,
                       interpret=interpret)


_fedgs_select = partial(jax.jit, static_argnames=(
    "m", "max_sweeps", "m_target", "backend", "interpret"))(fedgs_select)


# ------------------------------------------------------- the switch step
def make_sampler_step(n: int, m: int, *, max_sweeps: int = 32,
                      d_cand: int | None = None, probe_losses=None,
                      solver_backend: str = "ref",
                      families: tuple | None = None):
    """Compile-time constructor of the ONE per-round sampler step

        ``step(sparams, state, key, inputs, avail, t) -> (s, state)``

    dispatching ``lax.switch`` on the cell's family index, so cells of
    DIFFERENT samplers batch through one vmapped program (under vmap the
    switch lowers to a select over all branches; the extra branches' cost is
    small next to local training — DESIGN.md §11).

    ``inputs`` carries the engine-supplied round context: ``h`` (N, N)
    normalized H and ``counts`` (N,) for FedGS, plus whatever
    ``probe_losses(inputs, cidx, keys) -> (d,)`` consumes for the PoC loss
    probe (the scan engine closes over the model and reads
    ``inputs["params"]``; the default reads a precomputed ``inputs
    ["losses"]`` (N,) vector).  ``key`` is the per-round sampler key —
    ``fold_in(sampler_key, t)`` in the scan stream; FedGS ignores it
    (deterministic given (H, counts, A_t)).
    """
    d = int(n if d_cand is None else d_cand)
    if probe_losses is None:
        probe_losses = lambda inputs, cidx, keys: inputs["losses"][cidx]

    def _fedgs(sp, state, key, inputs, avail, t):
        s = fedgs_select(inputs["h"], inputs["counts"], avail, sp["alpha"],
                         m=m, max_sweeps=max_sweeps, backend=solver_backend)
        return s, state

    def _uniform(sp, state, key, inputs, avail, t):
        return uniform_select(key, avail, m), state

    def _md(sp, state, key, inputs, avail, t):
        return gumbel_topk_select(key, sp["log_sizes"], avail, m), state

    def _poc(sp, state, key, inputs, avail, t):
        """Cho et al. 2020 on-device: d·m candidates by data size (Gumbel
        top-k), then keep the top-m highest-loss candidates.  Key layout:
        the candidate draw consumes ``key``, the probe ``fold_in(key, 1)``
        (bit-compatible with the PR-2 in-scan PoC stream)."""
        cand = gumbel_topk_select(key, sp["log_sizes"], avail, d)
        cidx, cvalid = select_k(cand, d)
        losses = probe_losses(
            inputs, cidx, jax.random.split(jax.random.fold_in(key, 1), d))
        _, kk = jax.lax.top_k(jnp.where(cvalid, losses, -jnp.inf), m)
        # cidx entries are distinct, so invalid slots never overwrite a
        # kept candidate
        return jnp.zeros((n,), bool).at[cidx[kk]].set(cvalid[kk]), state

    branches = {"fedgs": _fedgs, "uniform": _uniform, "md": _md, "poc": _poc}

    def step(sparams, state, key, inputs, avail, t):
        return jax.lax.switch(sparams["family"],
                              [branches[f] for f in FAMILIES],
                              sparams, state, key, inputs, avail, t)

    if families is None:
        return step
    families = tuple(families)
    if not families or any(f not in FAMILIES for f in families):
        raise ValueError(f"families must be a non-empty subset of "
                         f"{FAMILIES}, not {families!r}")
    if len(families) == 1:
        return branches[families[0]]
    slot = np.asarray([families.index(f) if f in families else 0
                       for f in FAMILIES], np.int32)

    def subset_step(sparams, state, key, inputs, avail, t):
        return jax.lax.switch(jnp.asarray(slot)[sparams["family"]],
                              [branches[f] for f in families],
                              sparams, state, key, inputs, avail, t)
    return subset_step


# ------------------------------------------------------------ the processes
@dataclass
class SamplerProcess:
    """Base class.  ``params(data_sizes)``/``init(key)`` are eager host-side
    constructors of the per-cell runtime pytrees; :meth:`select` is the pure
    traceable entry point (single-process convenience over the switch step,
    guaranteed identical because it IS the switch path).  Every family fills
    the SAME params pytree (family index, alpha, log-size weights) so
    heterogeneous sampler cells stack along a vmap batch axis
    (``scan_engine.stack_cells``)."""

    family = "uniform"
    name = "process"

    def _alpha(self) -> float:
        return 0.0

    def params(self, *, data_sizes=None, n_clients: int | None = None) -> dict:
        """The cell-ready param pytree.  ``data_sizes`` defaults to all-ones
        — uniform MD/PoC weights — when only ``n_clients`` is known."""
        if data_sizes is None:
            assert n_clients is not None, "need data_sizes or n_clients"
            data_sizes = np.ones(n_clients)
        return {"family": jnp.int32(FAMILIES.index(self.family)),
                "alpha": jnp.float32(self._alpha()),
                "log_sizes": log_size_weights(data_sizes)}

    def init(self, key: jax.Array) -> dict:
        """Initial carried state — today's samplers are stateless per round,
        so this is the empty pytree (the protocol slot exists so stateful
        samplers ride the scan carry like availability processes do)."""
        return {}

    # -- traceable entry point --------------------------------------------
    def select(self, state, key, inputs, avail, t, *, m: int,
               data_sizes=None, max_sweeps: int = 32,
               d_cand: int | None = None, probe_losses=None,
               solver_backend: str = "ref"):
        """``data_sizes`` feeds the MD/PoC size weights — without it they
        fall back to all-ones (uniform), which is only right for samplers
        that ignore sizes."""
        n = avail.shape[-1]
        # every switch branch TRACES, so the round context must be complete
        # even for families this process never dispatches to — fill neutral
        # defaults for whatever the caller didn't supply
        inputs = {"h": jnp.zeros((n, n), jnp.float32),
                  "counts": jnp.zeros((n,), jnp.float32),
                  "losses": jnp.zeros((n,), jnp.float32),
                  "params": (), **inputs}
        step = make_sampler_step(n, m, max_sweeps=max_sweeps,
                                 d_cand=d_cand, probe_losses=probe_losses,
                                 solver_backend=solver_backend)
        return step(self.params(data_sizes=data_sizes, n_clients=n),
                    state, key, inputs, avail, t)


@dataclass
class UniformProcess(SamplerProcess):
    """McMahan et al. 2017: uniform without replacement among available."""
    name: str = "uniform"
    family = "uniform"


@dataclass
class MDProcess(SamplerProcess):
    """Li et al. 2020: without replacement, P(k) ∝ n_k, among available."""
    name: str = "md"
    family = "md"


@dataclass
class PoCProcess(SamplerProcess):
    """Cho et al. 2020 Power-of-Choice.  ``d_factor`` documents the intended
    candidate multiplier; the static candidate count itself is an engine
    compile-time knob (``ScanConfig.poc_d_factor`` / ``d_cand``)."""
    d_factor: int = 2
    name: str = "poc"
    family = "poc"


@dataclass
class FedGSProcess(SamplerProcess):
    """The paper's method; ``alpha`` weighs graph dispersion vs count
    balance and is a per-cell traced knob — α-variants batch together."""
    alpha: float = 1.0
    name: str = "fedgs"
    family = "fedgs"

    def __post_init__(self):
        self.name = f"fedgs(alpha={self.alpha})"

    def _alpha(self) -> float:
        return self.alpha


def make_sampler_process(name: str, *, alpha: float = 1.0,
                         d_factor: int = 2) -> SamplerProcess:
    """Family names (= ``scan_engine.SAMPLERS``) -> processes."""
    name = name.lower()
    if name in ("uniform", "uniformsample"):
        return UniformProcess()
    if name in ("md", "mdsample"):
        return MDProcess()
    if name in ("poc", "power-of-choice", "powerofchoice"):
        return PoCProcess(d_factor=d_factor)
    if name == "fedgs":
        return FedGSProcess(alpha=alpha)
    raise ValueError(f"unknown sampler family {name!r}")
