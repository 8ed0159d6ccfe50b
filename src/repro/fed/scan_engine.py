"""Fully jit-compiled federated simulation: scan-over-rounds, vmap-over-cells.

``FLEngine.run`` (fed/engine.py) is a per-round Python loop: every round pays
a dispatch + host<->device sync for the sampler, the trainer and the eval, and
a sweep like Table 2 (samplers x availability modes x seeds) runs each cell
serially.  This module moves the *entire* round loop onto the device:

  one ``lax.scan`` step = availability draw -> sampler -> vmap'd local
  training (E SGD steps) -> server update (aggregator switch; Eq. 18
  default) -> count update -> eval,

all with static shapes, and the scanned program is then ``vmap``-ed over a
batch axis of *cells* — (seed, availability mode, FedGS alpha) triples — so a
whole sweep row executes as ONE XLA program (DESIGN.md §5).

Static-shape formulation
  The sampler emits a boolean mask s (N,) with |s| = min(M, |A_t|); the M
  sorted selected indices (padded with zero-weight slots when |A_t| < M) are
  gathered so local training always runs on exactly M stacked clients, and
  Eq. 18 weights ``n_k * valid_k`` zero out the pads.

Seed streams (parity with FLEngine)
  The training stream replicates FLEngine.run exactly: ``key_t = fold_in(
  PRNGKey(seed), t)``, then ``_, sub = split(key_t)`` and per-client keys
  ``split(sub, M)`` — so with the same sampled sets the parameter trajectory
  matches the host engine to float32 round-off, PROVIDED every round has
  |A_t| >= M: FLEngine splits ``split(sub, |S_t|)`` and threefry key prefixes
  depend on the split count, so rounds where fewer than M clients are
  available draw different local-training batches (still a valid simulation,
  just not bit-parity — the parity tests assert the precondition).  Availability either comes
  from host-precomputed masks (``precompute_masks`` = the shared host
  wrapper ``availability.host_trace``, bit-identical to FLEngine's numpy
  SeedSequence([avail_seed, t]) stream — the parity-test path) or is drawn
  on-device by an ``AvailabilityProcess``
  (``core.availability_device``): the cell carries the process params +
  carried state, the scan body calls the one shared ``proc_draw`` (family
  step -> Bernoulli -> force-one), and because every family compiles to the
  same ``lax.switch`` program, cells of DIFFERENT scenario families —
  legacy periodic tables, Gilbert–Elliott churn, cluster outages, drift,
  deadlines — vmap-batch through one ``run_batch`` program.  The SAMPLER is
  the same kind of per-cell switch (``core.sampler_device``): each cell
  carries a ``SamplerProcess`` params pytree + in-scan state, and the one
  ``make_sampler_step`` program dispatches Uniform / MD (Gumbel top-k),
  Power-of-Choice (d·m Gumbel candidates + in-scan loss probe + top-m
  keep) and FedGS (the deterministic ``fedgs_solve``, so FedGS cells match
  the host engine's sampled sets exactly; ``ScanConfig.solver_backend``
  routes the Eq. 16 solve through the tiled Pallas kernels) — so
  MIXED-SAMPLER cell batches execute as one XLA program too.  The SERVER
  UPDATE is the third per-cell switch (``fed.aggregator_device``): each
  cell carries an ``AggregatorProcess`` params pytree and the in-scan
  aggregator state (previous params — which double as the param carry —
  momentum/Adam moments, the (N, P) update-memory panel), and the one
  ``make_aggregator_step`` program dispatches FedAvg (bit-parity with the
  legacy Eq. 18 path), FedAvgM, FedAdam, proximal-weighted averaging and
  the FedAR/MIFA-style memory-rectified reduction
  (``ScanConfig.agg_backend`` routes the memory scatter+reduce through the
  tiled Pallas kernel) — so MIXED-AGGREGATOR cell batches are one XLA
  program as well.

Dynamic 3DG
  With ``graph_refresh_every > 0`` the 3DG is maintained *inside* the scan:
  participants' post-training probe embeddings update a carried (N, C)
  embedding table and every K rounds ``core.graph_device.build_h`` (the one
  shared functional-similarity -> adjacency -> Floyd–Warshall -> finite-cap
  pipeline) rebuilds the carried H under ``lax.cond``.
  ``ScanConfig.graph_backend="pallas"`` routes the rebuild's similarity
  matmul and APSP through the tiled kernels for large-N sweeps.

Mesh scale-out (DESIGN.md §13)
  ``ScanConfig.mesh=(cells,)`` or ``(cells, silo)`` runs ``run_batch``
  under ``jax.shard_map`` on ``launch.mesh.make_engine_mesh``:
  sweep cells shard over the "cells" axis (embarrassingly parallel —
  per-cell subsystem state stays device-local; uneven batches are padded by
  repeating the last cell and the pad trajectories dropped), and the "silo"
  axis row-shards the vmap'd local-training client axis (each silo trains
  its M/s chunk and ``all_gather``s the stacked updates — bitwise equal to
  the single-device program by construction).  ``silo_reduce="psum"``
  additionally row-shards the memory aggregator's (N, P) panel, turning the
  staleness reduction into partial tensordots + a ``psum`` (numerically
  equal, not bitwise — same contract as the Pallas backend's tile-order
  partial sums).

Exact-resume checkpointing (DESIGN.md §13)
  ``run_batch(cells, ckpt_path=..., ckpt_every=k, resume=...)`` executes
  the scan in k-round segments (``lax.scan`` over a ``t0 + arange(k)``
  window — every per-round stream is keyed ``fold_in(key, t)`` with NO
  cross-round rng state, so a resume replays the identical per-round
  computation) and checkpoints the FULL carry — aggregator slots incl.
  momentum/Adam/memory panel, availability-chain state, sampler state,
  counts, H, embeddings — plus the accumulated trajectory and round index
  through ``checkpoint.ckpt``.  A same-mesh same-cadence resume is bitwise
  equal to the uninterrupted segmented run.  Saving gathers shards to host
  npz (device-layout-free), so a run may resume on a DIFFERENT device
  count / mesh (the loaded carry is resharded to the target program's
  specs); cross-device-count runs are bitwise at ``ckpt_every=1`` — XLA
  fuses a multi-round scan's while-body differently per SPMD partition
  count and scan length (ulp-level eval drift, decisions unaffected), but
  one-round segments compile identically everywhere and chain exactly.

Typical use::

    eng = ScanEngine(ds, model, ScanConfig(rounds=60, m=6, sampler="fedgs"))
    cells = [eng.cell(seed=s, mode=mode, alpha=1.0, h=h) for s in (0, 1, 2)]
    hists = eng.run_batch(cells)          # one compiled program, B cells
"""
from __future__ import annotations

import os
from dataclasses import dataclass
from typing import Optional

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.checkpoint.ckpt import load_checkpoint, save_checkpoint
from repro.fed.runtime import (
    AsyncCheckpointWriter, CarryHandle, ProgramCache,
)
from repro.fed.telemetry import (
    NULL_TRACER, fault_corruption_norm, round_telemetry, runtime_snapshot,
)
from repro.core.availability import AvailabilityMode, host_trace
from repro.core.availability_device import AvailabilityProcess, proc_draw
from repro.core.graph_device import (
    BACKENDS, GraphConfig, build_h, cap_and_normalize,
)
from repro.core.sampler_device import (
    FAMILIES, SamplerProcess, make_sampler_process, make_sampler_step,
    select_k,
)
from repro.core.fairness import count_variance_device, gini_device
from repro.data.fed_dataset import FedDataset
from repro.fed.aggregator_device import (
    AggregatorProcess, _flat_template, init_agg_state,
    make_aggregator_process, make_aggregator_step,
)
from repro.fed.aggregator_device import FAMILIES as AGG_FAMILIES
from repro.fed.faults_device import (
    FaultProcess, init_fault_state, make_fault_process, make_fault_step,
)
from repro.fed.faults_device import FAMILIES as FAULT_FAMILIES
from repro.fed.client import make_local_trainer
from repro.fed.models import FedModel
from repro.launch.mesh import make_engine_mesh
from repro.sharding.rules import (
    ENGINE_SILO_AXIS, engine_batch_spec, engine_carry_specs,
)

SAMPLERS = FAMILIES            # ("fedgs", "uniform", "md", "poc")
AGGREGATORS = AGG_FAMILIES     # ("fedavg", "fedavgm", "fedadam",
                               #  "fedprox_w", "memory", "median",
                               #  "trimmed_mean", "krum")
FAULTS = FAULT_FAMILIES        # ("none", "sign_flip", "gaussian_noise",
                               #  "scaled", "straggler_stale")
SILO_REDUCES = ("gather", "psum")
# clients per block of the dynamic 3DG's all-clients init probe round
INIT_BLOCK = 512


@dataclass(frozen=True)
class ScanConfig:
    """Static (compile-time) configuration of the scanned program."""
    rounds: int = 200
    m: int = 3                     # sampled clients per round (static shape M)
    local_steps: int = 10          # E
    batch_size: int = 10
    lr: float = 0.1
    lr_decay: float = 0.998
    prox_mu: float = 0.0
    eval_every: int = 1            # in-scan eval cadence (NaN on off rounds)
    sampler: str = "fedgs"         # fedgs | uniform | md | poc
    max_sweeps: int = 32           # FedGS local-search budget
    # Power-of-Choice: d·m candidates by data size, in-scan loss probe
    poc_d_factor: int = 2
    poc_probe: int = 64            # loss-probe batch per candidate
    # dynamic 3DG: rebuild H in-scan from participants' probe embeddings
    # every K rounds (0 = static graph installed via the cell's ``h``)
    graph_refresh_every: int = 0
    graph_eps: float = 0.1
    graph_sigma2: float = 0.01
    graph_backend: str = "ref"     # ref | pallas (dynamic-3DG rebuild path)
    solver_backend: str = "ref"    # ref | pallas (FedGS Eq. 16 solve)
    aggregator: str = "fedavg"     # fedavg | fedavgm | fedadam | fedprox_w
                                   # | memory | median | trimmed_mean | krum
                                   # (per-cell overridable)
    agg_backend: str = "ref"       # ref | pallas (memory scatter+reduce and
                                   # krum distance panel)
    # fault injection (fed/faults_device): engine-level default family +
    # Byzantine fraction, per-cell overridable via cell(fault_process=...)
    fault: str = "none"            # none | sign_flip | gaussian_noise |
                                   # scaled | straggler_stale
    fault_frac: float = 0.0        # adversarial client fraction (ceil(f*N))
    probe_size: int = 64
    probe_seed: int = 777
    # mesh scale-out (DESIGN.md §13): (cells,) or (cells, silo) device grid
    # for shard_map'd run_batch; None = single-device (the default)
    mesh: Optional[tuple] = None
    cell_sharding: bool = True     # shard the cell-batch axis over "cells"
    silo_reduce: str = "gather"    # gather (bitwise) | psum (panel-sharded)
    # runtime layer (DESIGN.md §15): donate the scan carry into each
    # segment program (in-place HBM reuse; use-after-donation raises via
    # CarryHandle), overlap device compute with host traj fetch + async
    # checkpoint writes, and bound the in-process program cache
    donate_carry: bool = True
    async_pipeline: bool = True
    program_cache_size: int = 32
    # in-scan telemetry channel (DESIGN.md §17): opt-in per-round stage
    # health metrics (update norms / NaN fraction / clip rate, sampler
    # dispersion, availability rate, weight entropy, staleness histogram,
    # fault magnitude) captured alongside the ScanHistory trajectory.
    # Gated like the fault carry: telemetry=False programs, outputs and
    # checkpoints are bitwise untouched (assumption log #24)
    telemetry: bool = False
    telemetry_clip_thresh: float = 10.0   # client-update-norm clip probe

    def __post_init__(self):
        if self.sampler not in SAMPLERS:
            raise ValueError(f"scan engine supports {SAMPLERS}, "
                             f"not {self.sampler!r}")
        if self.aggregator not in AGGREGATORS:
            raise ValueError(f"scan engine supports {AGGREGATORS}, "
                             f"not {self.aggregator!r}")
        for knob in ("graph_backend", "solver_backend", "agg_backend"):
            if getattr(self, knob) not in BACKENDS:
                raise ValueError(f"{knob} must be one of {BACKENDS}, "
                                 f"not {getattr(self, knob)!r}")
        if self.silo_reduce not in SILO_REDUCES:
            raise ValueError(f"silo_reduce must be one of {SILO_REDUCES}, "
                             f"not {self.silo_reduce!r}")
        if self.fault not in FAULTS:
            raise ValueError(f"scan engine supports faults {FAULTS}, "
                             f"not {self.fault!r}")
        if not 0.0 <= self.fault_frac <= 1.0:
            raise ValueError(f"fault_frac must be in [0, 1], "
                             f"not {self.fault_frac!r}")
        if self.program_cache_size < 1:
            raise ValueError(f"program_cache_size must be >= 1, "
                             f"not {self.program_cache_size!r}")
        if self.mesh is not None:
            shape = tuple(int(s) for s in self.mesh)
            if len(shape) not in (1, 2) or any(s < 1 for s in shape):
                raise ValueError(f"mesh must be (cells,) or (cells, silo) "
                                 f"with positive sizes, not {self.mesh!r}")
            object.__setattr__(self, "mesh",
                               shape if len(shape) == 2 else shape + (1,))


# --------------------------------------------------------------- host helpers
def precompute_masks(mode, rounds: int, avail_seed: int = 1234) -> np.ndarray:
    """(rounds, N) bool availability trace, bit-identical to the stream
    FLEngine.run draws — both route through the ONE host wrapper
    ``availability.host_draw`` / ``host_trace``.  ``mode`` is anything with
    ``sample(t, rng)``: an ``AvailabilityMode`` or a ``ProcessMode`` over a
    stateful scenario family."""
    return host_trace(mode, rounds, avail_seed)


def normalized_h(h: np.ndarray) -> np.ndarray:
    """Finite-cap + [0, 1]-normalize a shortest-path matrix — the SAME
    ``graph_device.cap_and_normalize`` stage FedGSSampler.set_graph runs
    (DESIGN.md assumption log)."""
    return np.asarray(cap_and_normalize(jnp.asarray(h, jnp.float32)))


def oracle_h(features: np.ndarray, *, eps: float = 0.1, sigma2: float = 0.01,
             backend: str = "ref") -> np.ndarray:
    """Oracle 3DG -> normalized H (the scan-engine analogue of
    FLEngine.install_oracle_graph)."""
    cfg = GraphConfig(eps=eps, sigma2=sigma2, similarity="dot")
    return np.asarray(build_h(jnp.asarray(features, jnp.float32), cfg,
                              backend=backend))


def stack_cells(cells: list[dict]) -> dict:
    """Stack per-cell pytrees along a new leading batch axis, zero-padding
    the availability-process tables to a common period (rows beyond a
    cell's own period are never indexed because lookups are
    ``table[t % period]``) — this is what lets cells of different scenario
    families, with different table periods, batch into ONE program."""
    if "proc" in cells[0]:
        pmax = max(int(c["proc"]["table"].shape[0]) for c in cells)
        cells = [dict(c, proc=dict(c["proc"])) for c in cells]
        for c in cells:
            for k in ("table", "table_b"):
                tab = c["proc"][k]
                p = int(tab.shape[0])
                if p < pmax:
                    c["proc"][k] = jnp.concatenate(
                        [tab, jnp.zeros((pmax - p,) + tab.shape[1:],
                                        tab.dtype)])
    return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *cells)


# ------------------------------------------------------------------ histories
@dataclass
class ScanHistory:
    """Device-side trajectory of one cell (full-round resolution; eval
    entries are NaN on rounds skipped by ``eval_every``)."""
    val_loss: np.ndarray       # (T,)
    val_acc: np.ndarray        # (T,)
    count_var: np.ndarray      # (T,)
    gini: np.ndarray           # (T,) Gini coefficient of the counts
    sel: np.ndarray            # (T, M) sorted selected indices (padded)
    valid: np.ndarray          # (T, M) pad mask (False = zero-weight slot)
    counts: np.ndarray         # (N,) final participation counts
    # opt-in per-round stage-health metrics (ScanConfig.telemetry;
    # DESIGN.md §17): {name: (T,) or (T, bins) array} — None when the
    # telemetry channel is off.  Rounds before a resume point are NaN
    # (telemetry is observability, not state: it is NOT checkpointed)
    telemetry: Optional[dict] = None

    @property
    def best_loss(self) -> float:
        return float(np.nanmin(self.val_loss))

    @property
    def rounds(self) -> np.ndarray:
        """Rounds with recorded eval."""
        return np.flatnonzero(np.isfinite(self.val_loss))

    def sampled(self, t: int) -> np.ndarray:
        """The round-t sampled set (pads stripped)."""
        return self.sel[t][self.valid[t]]


# ---------------------------------------------------------------- the program
def _build_simulate(ds: FedDataset, model: FedModel, cfg: ScanConfig,
                    use_masks: bool, with_memory: bool = False, *,
                    with_fault: bool = False, with_stale: bool = False,
                    with_telemetry: bool = False,
                    silo: int = 1, panel_axis: Optional[str] = None):
    """Closure-captures the (cell-shared) dataset and returns the pure
    per-cell closures the engine jit/vmap/shard_maps:

      ``init(cell) -> carry``            the full scan carry (dict pytree:
                                         aggregator state incl. params,
                                         counts, H, embeddings, availability
                                         + sampler state)
      ``segment(seg_len)(cell, carry, t0) -> (carry, traj)``
                                         ``seg_len`` rounds starting at
                                         ``t0`` — the checkpoint/resume unit
      ``simulate(cell) -> out``          init + one full-run segment

    Segmenting is exact because every per-round stream is ``fold_in(key,
    t)``-keyed off the round index alone (no cross-round rng carry), and
    the lr schedule / mask table are indexed by the global ``t`` inside the
    step body — a ``(k)+(T-k)`` split replays the identical per-round
    computation.

    ``with_memory`` statically sizes the aggregator state's (N, P)
    update-memory panel: the engine compiles the panel-carrying variant
    only when a memory-family cell is actually in play (the common
    fedavg sweep keeps the pre-subsystem carry: params + counts + H).
    ``with_fault`` / ``with_stale`` gate the fault-injection seam the same
    way: only a batch with an actual fault cell carries the fault state
    (and only a straggler cell carries the (N, P) stale-update panel), so
    the benign default program — and its checkpoints — are unchanged.
    ``with_telemetry`` gates the in-scan health channel identically
    (``ScanConfig.telemetry``): the step emits an extra per-round metrics
    pytree under ``out["telemetry"]`` — pure reductions over
    intermediates the step already materializes, NO new carry state — so
    a telemetry-off program, its history fields and its checkpoints are
    bitwise untouched (DESIGN.md §17, assumption log #24).

    ``silo > 1`` chunks the vmap'd local-training client axis over the
    shard_map "silo" mesh axis (each silo trains ceil(M/s) clients with the
    SAME per-client fold_in keys, then ``all_gather``s the stacked updates
    — bitwise equal to the unsharded program); ``panel_axis`` additionally
    row-shards the memory panel (see ``make_aggregator_step``)."""
    n = int(ds.n_clients)
    m = int(cfg.m)
    xs = jnp.asarray(ds.x)
    ys = jnp.asarray(ds.y)
    sizes_i = jnp.asarray(ds.sizes)
    sizes_f = jnp.asarray(ds.sizes, jnp.float32)
    xv = jnp.asarray(ds.x_val)
    yv = jnp.asarray(ds.y_val)
    # host-side f64 schedule cast to f32: bit-identical to FLEngine's
    # per-round ``jnp.float32(lr * decay ** t)``
    lrs = jnp.asarray([np.float32(cfg.lr * cfg.lr_decay ** t)
                       for t in range(cfg.rounds)])
    trainer = make_local_trainer(model.loss, local_steps=cfg.local_steps,
                                 batch_size=cfg.batch_size,
                                 prox_mu=cfg.prox_mu)
    dynamic = cfg.graph_refresh_every > 0
    if dynamic:
        # shared Gaussian probe batch (Eq. 12), engine-level constant —
        # FLEngine re-draws it per run seed; the scan engine fixes probe_seed
        # so one compiled program serves every cell (DESIGN.md §5)
        rng = np.random.default_rng(cfg.probe_seed)
        flat = np.asarray(ds.x_val, np.float64).reshape(len(ds.x_val), -1)
        mu, cov = flat.mean(0), np.cov(flat.T) + 1e-4 * np.eye(flat.shape[1])
        probe = rng.multivariate_normal(mu, cov, cfg.probe_size)
        probe = jnp.asarray(
            probe.reshape(cfg.probe_size, *ds.x_val.shape[1:]), jnp.float32)

    # the shared device-native 3DG pipeline (core/graph_device.py) — the same
    # stages engine._rebuild_dynamic_graph / fedsim.graph_pipeline compose
    gcfg = GraphConfig(eps=cfg.graph_eps, sigma2=cfg.graph_sigma2,
                       similarity="functional")

    def rebuild_h(emb):
        return build_h(emb, gcfg, backend=cfg.graph_backend)

    def embed_mean(stacked):
        return jax.vmap(lambda p: jnp.mean(model.embed(p, probe), 0))(stacked)

    def select(s):
        return select_k(s, m)

    d_cand = int(min(n, max(m, cfg.poc_d_factor * m)))

    def probe_losses(inputs, idx, keys):
        """Global-model loss on a probe batch of each candidate's local
        data — the in-scan analogue of fed.client.make_loss_prober (the
        PoC branch of the sampler switch calls this)."""
        params = inputs["params"]

        def one(x, y, n_k, key):
            b = jax.random.randint(key, (cfg.poc_probe,), 0,
                                   jnp.maximum(n_k, 1))
            return model.loss(params, x[b], y[b])
        return jax.vmap(one)(xs[idx], ys[idx], sizes_i[idx], keys)

    # the ONE sampler step — lax.switch over the cell's family index, so
    # cells of DIFFERENT samplers batch through one run_batch program
    # (core/sampler_device.make_sampler_step)
    sampler_step = make_sampler_step(
        n, m, max_sweeps=cfg.max_sweeps, d_cand=d_cand,
        probe_losses=probe_losses, solver_backend=cfg.solver_backend)

    # ... and the ONE aggregator step (fed/aggregator_device): the server
    # update is a per-cell lax.switch too, so mixed-aggregator cells batch;
    # the aggregator state's ``prev`` slot doubles as the param carry
    agg_step = make_aggregator_step(
        n, m, jax.eval_shape(model.init, jax.random.PRNGKey(0)),
        data_sizes=ds.sizes, backend=cfg.agg_backend,
        memory_enabled=with_memory, panel_axis=panel_axis)

    # ... and the fault-injection seam (fed/faults_device) BETWEEN local
    # training and aggregation: per-cell lax.switch over the fault family,
    # operating on the flat (M, P) update panel.  The ravel->unravel
    # round-trip is a bitwise identity, so benign cells inside a faulted
    # batch match their no-fault program bitwise.
    if with_fault:
        fault_step = make_fault_step(n, m, stale_enabled=with_stale)
        fravel, funravel, _ = _flat_template(
            jax.eval_shape(model.init, jax.random.PRNGKey(0)))
    if panel_axis is not None and n % silo:
        raise ValueError(f"silo_reduce='psum' row-shards the (N, P) memory "
                         f"panel: N={n} must divide by silo={silo}")
    mem_rows = (n // silo if panel_axis is not None else n) \
        if with_memory else 0
    chunk = -(-m // silo)              # per-silo local-training clients

    def init_embeddings(ikey):
        """Embeddings after one all-clients probe round from a fresh model
        (the paper's everyone-available-at-init assumption), as in
        FLEngine.install_dynamic_graph.  Past ``INIT_BLOCK`` clients the
        round trains and embeds one block of clients at a time: a single
        vmap over every client holds all N clients' activations at once
        (N=4096 small-CNN clients x 4 cells asks a 16 GB v5e for 23 GB)."""
        params = model.init(ikey)
        keys = jax.random.split(ikey, n)
        lr0 = jnp.float32(cfg.lr)
        if n <= INIT_BLOCK:
            return embed_mean(trainer(params, xs, ys, sizes_i, lr0, keys))

        def block(ix):
            return embed_mean(trainer(params, xs[ix], ys[ix], sizes_i[ix],
                                      lr0, keys[ix]))
        nb = -(-n // INIT_BLOCK)
        ix = jnp.minimum(jnp.arange(nb * INIT_BLOCK), n - 1)
        emb = jax.lax.map(block, ix.reshape(nb, INIT_BLOCK))
        return emb.reshape(nb * INIT_BLOCK, -1)[:n]

    def init(cell):
        """The FULL scan carry — everything a bitwise-exact resume needs
        (plus the round index and rng cell keys, which live in the cell /
        checkpoint metadata)."""
        key0 = cell["key"]
        params0 = model.init(key0)

        if dynamic:
            emb0 = init_embeddings(cell["init_key"])
            h0 = rebuild_h(emb0)
        else:
            emb0 = jnp.zeros((1, 1), jnp.float32)
            h0 = cell["h"]
        astate0 = init_agg_state(params0, n, memory_rows=mem_rows,
                                 tau_rows=n if with_memory else 0)
        carry0 = {"agg": astate0,
                  "counts": jnp.zeros((n,), jnp.float32),
                  "h": h0, "emb": emb0,
                  "proc": cell.get("proc_state", {}),
                  "sampler": cell.get("sampler_state", {})}
        if with_fault:
            # latency chain from the cell's eager init; the (rows, P)
            # stale-update panel is sized here because P is model-dependent
            carry0["fault"] = init_fault_state(
                cell["fault_state"], params0, n if with_stale else 0)
        return carry0

    def step(cell, carry, t):
        astate, counts = carry["agg"], carry["counts"]
        h, emb = carry["h"], carry["emb"]
        pstate, sstate = carry["proc"], carry["sampler"]
        params = astate["prev"]        # the aggregator state IS the
        lr = lrs[t]                    # global-params carry
        key = jax.random.fold_in(cell["key"], t)

        # 1. availability A_t — the shared device-native process draw
        # (core/availability_device.proc_draw: family step -> Bernoulli
        # -> force-one); the process state rides the scan carry
        if use_masks:
            avail = cell["masks"][t]
        else:
            avail, pstate = proc_draw(
                cell["proc"], pstate,
                jax.random.fold_in(cell["avail_key"], t), t)

        # 2. sampler: S_t subset of A_t, |S_t| = min(M, |A_t|) — the
        # switch step dispatches on the cell's family; the sampler
        # state rides the scan carry like the availability state
        skey = jax.random.fold_in(cell["sampler_key"], t)
        s, sstate = sampler_step(
            cell["sampler"], sstate, skey,
            {"h": h, "counts": counts, "params": params}, avail, t)
        sel, valid = select(s)

        # 3. vmap'd local training on the M gathered clients — under a
        # silo'd mesh each shard trains its ceil(M/s) chunk (same
        # per-client keys) and all_gathers the stacked updates
        key, sub = jax.random.split(key)
        keys_m = jax.random.split(sub, m)
        if silo > 1:
            pad = chunk * silo - m
            sel_p = jnp.concatenate([sel, sel[-1:].repeat(pad, 0)]) \
                if pad else sel
            keys_p = jnp.concatenate([keys_m, keys_m[-1:].repeat(pad, 0)]) \
                if pad else keys_m
            i0 = jax.lax.axis_index(ENGINE_SILO_AXIS) * chunk
            sel_l = jax.lax.dynamic_slice_in_dim(sel_p, i0, chunk)
            keys_l = jax.lax.dynamic_slice_in_dim(keys_p, i0, chunk)
            local_l = trainer(params, xs[sel_l], ys[sel_l], sizes_i[sel_l],
                              lr, keys_l)
            local = jax.tree_util.tree_map(
                lambda a: jax.lax.all_gather(
                    a, ENGINE_SILO_AXIS, axis=0, tiled=True)[:m], local_l)
        else:
            local = trainer(params, xs[sel], ys[sel], sizes_i[sel], lr,
                            keys_m)

        # 3b. fault injection — the per-cell fault switch corrupts the
        # byz slots of the flat (M, P) update panel BETWEEN training and
        # aggregation (sign flips, noise, boosting, stale straggler
        # replays); benign cells pass through the identity branch
        fault_mag = None
        if with_fault:
            fstate = carry["fault"]
            cleanf = jax.vmap(fravel)(local)
            updf, fstate = fault_step(
                cell["fault"], fstate,
                jax.random.fold_in(cell["fault_key"], t),
                cleanf, fravel(params), avail, t, sel,
                valid)
            local = jax.vmap(funravel)(updf)
            if with_telemetry:
                # corruption magnitude at the seam, where the clean flat
                # panel is still in scope (DESIGN.md §17)
                fault_mag = fault_corruption_norm(updf, cleanf, valid)

        # 4. server update — the aggregator switch step dispatches on
        # the cell's family (Eq. 18 weights: pads carry zero weight;
        # the fedavg branch is bit-identical to the legacy aggregate())
        prev_params = params
        agg_w = sizes_f[sel] * valid
        params, astate = agg_step(
            cell["agg"], astate, jax.random.fold_in(cell["agg_key"], t),
            local, agg_w, s, avail, t, sel, valid)

        # 5. count update v^{t+1}
        counts = counts + s.astype(jnp.float32)

        # dynamic 3DG: refresh participants' embeddings; rebuild every K
        if dynamic:
            e_sel = embed_mean(local)
            emb = emb.at[sel].set(
                jnp.where(valid[:, None], e_sel, emb[sel]))
            h = jax.lax.cond(
                (t + 1) % cfg.graph_refresh_every == 0,
                rebuild_h, lambda e: h, emb)

        # 6. eval (cond-gated to the eval_every cadence)
        def do_eval(_):
            return model.loss(params, xv, yv), model.accuracy(params, xv, yv)

        if cfg.eval_every == 1:
            vl, va = do_eval(None)
        else:
            vl, va = jax.lax.cond(
                (jnp.mod(t, cfg.eval_every) == 0) | (t == cfg.rounds - 1),
                do_eval,
                lambda _: (jnp.float32(jnp.nan), jnp.float32(jnp.nan)),
                None)
        # fairness metrics — the shared device twins (core/fairness.py)
        cvar = count_variance_device(counts)
        gini = gini_device(counts)
        out = {"val_loss": vl, "val_acc": va, "count_var": cvar,
               "gini": gini, "sel": sel.astype(jnp.int32), "valid": valid}
        if with_telemetry:
            # the in-scan health channel (DESIGN.md §17): pure reductions
            # over this step's intermediates — consumers only, nothing
            # feeds back into the carry or the history fields above
            out["telemetry"] = round_telemetry(
                avail=avail, valid=valid, sel=sel, local=local,
                params_prev=prev_params, params_new=params, weights=agg_w,
                h=h, clip_thresh=cfg.telemetry_clip_thresh,
                tau=astate["tau"] if with_memory else None, t=t,
                fault_mag=fault_mag)
        carry1 = {"agg": astate, "counts": counts, "h": h, "emb": emb,
                  "proc": pstate, "sampler": sstate}
        if with_fault:
            carry1["fault"] = fstate
        return carry1, out

    def segment(seg_len: int):
        def run_segment(cell, carry, t0):
            return jax.lax.scan(lambda c, t: step(cell, c, t), carry,
                                t0 + jnp.arange(seg_len))
        return run_segment

    def simulate(cell):
        carry, traj = segment(cfg.rounds)(cell, init(cell), jnp.int32(0))
        return {"params": carry["agg"]["prev"], "counts": carry["counts"],
                **traj}

    return {"init": init, "segment": segment, "simulate": simulate}


# ------------------------------------------------------------------- engine
class ScanEngine:
    """Host-facing wrapper: builds cells, compiles the scanned program once,
    and runs single cells or whole batched sweeps — optionally shard_map'd
    over a ("cells", "silo") mesh and/or segmented for exact-resume
    checkpointing (DESIGN.md §13)."""

    def __init__(self, ds: FedDataset, model: FedModel, cfg: ScanConfig, *,
                 use_masks: bool = False, tracer=None, sink=None):
        self.ds, self.model, self.cfg = ds, model, cfg
        self.n = ds.n_clients
        self.use_masks = use_masks
        self._sims: dict = {}   # ((wm, wf, ws, wt), silo, panel) -> closures
        # program key -> jit'd fn: bounded LRU with hit/miss/compile-ms
        # counters (DESIGN.md §15) — the old unbounded dict leaked one
        # program per (seg_len, variant) across heterogeneous sweeps
        self._programs = ProgramCache(maxsize=cfg.program_cache_size)
        self._cspecs: dict = {}       # (flags, silo, panel) -> carry specs
        self._mesh_obj = None
        # observability spine (DESIGN.md §17): host span tracer + streaming
        # metrics sink — both default to no-ops, both hot-swappable
        self.tracer = tracer if tracer is not None else NULL_TRACER
        self.sink = sink
        self._tel_parts: list = []    # [(t0, k, telemetry_host)] per run
        self._writer_stats: Optional[dict] = None

    def runtime_stats(self) -> dict:
        """The unified telemetry snapshot (DESIGN.md §17): program-cache
        counters FLAT at the top level (hits, misses, evictions, compiles,
        compile_ms, size — the pre-telemetry shape benchmarks read), plus
        the last run's checkpoint-writer backpressure counters and the
        tracer's per-span aggregates."""
        return runtime_snapshot(programs=self._programs,
                                writer=self._writer_stats,
                                tracer=self.tracer)

    def attach_sink(self, sink):
        """Install (or clear, with ``None``) the streaming metrics sink —
        per-segment round metrics flow through ``run_batch_stream``."""
        self.sink = sink

    # ----------------------------------------------------------- programs
    def _mesh(self):
        if self.cfg.mesh is None:
            return None
        if self._mesh_obj is None:
            self._mesh_obj = make_engine_mesh(self.cfg.mesh)
        return self._mesh_obj

    def _flags(self, cells: list[dict]) -> tuple:
        """Static program-variant flags for this batch: ``(wm, wf, ws,
        wt)`` — does any cell need the (N, P) update-memory panel / the
        fault seam / the straggler stale panel, and is the in-scan
        telemetry channel on?  Each flag widens the carry or the traced
        step only for batches that actually use the feature, so the
        benign default program is unchanged."""
        midx = AGGREGATORS.index("memory")
        wm = self.cfg.aggregator == "memory" or any(
            int(np.asarray(c["agg"]["family"])) == midx for c in cells)
        nidx = FAULTS.index("none")
        sidx = FAULTS.index("straggler_stale")
        fams = [int(np.asarray(c["fault"]["family"]))
                for c in cells if "fault" in c]
        wf = self.cfg.fault != "none" or any(f != nidx for f in fams)
        ws = self.cfg.fault == "straggler_stale" or any(
            f == sidx for f in fams)
        return wm, wf, ws, bool(self.cfg.telemetry)

    def _variant(self, batched: bool):
        """(mesh, silo, panel_axis-factory) for this run shape."""
        mesh = self._mesh() if batched else None
        silo = int(mesh.devices.shape[1]) if mesh is not None else 1

        def panel(wm: bool):
            return ENGINE_SILO_AXIS if (
                silo > 1 and self.cfg.silo_reduce == "psum" and wm) else None
        return mesh, silo, panel

    def _closures(self, flags: tuple, silo: int, panel: Optional[str]):
        wm, wf, ws, wt = flags
        key = (flags, silo, panel)
        if key not in self._sims:
            self._sims[key] = _build_simulate(
                self.ds, self.model, self.cfg, self.use_masks,
                with_memory=wm, with_fault=wf, with_stale=ws,
                with_telemetry=wt, silo=silo, panel_axis=panel)
        return self._sims[key]

    def _program(self, cells: list[dict], batched: bool):
        """The compiled full-run program variant for these cells: the (N, P)
        update-memory panel — and likewise the fault seam and its stale
        panel — ride the scan carry ONLY when a cell (or the engine
        default) asks for them — the common fedavg sweep keeps the lean
        carry.  With a mesh, the batched program is shard_map'd over
        ("cells", "silo")."""
        flags = self._flags(cells)
        mesh, silo, panelf = self._variant(batched)
        panel = panelf(flags[0])

        def build():
            fn = self._closures(flags, silo, panel)["simulate"]
            if batched:
                fn = jax.vmap(fn)
            if mesh is not None:
                spec = engine_batch_spec(self.cfg.cell_sharding)
                fn = jax.shard_map(fn, mesh=mesh, in_specs=(spec,),
                                   out_specs=spec, check_vma=False)
            return jax.jit(fn)
        return self._programs.get((flags, batched, silo, panel), build)

    def _carry_specs(self, stacked: dict, flags: tuple, silo: int,
                     panel: Optional[str], init_fn):
        """PartitionSpec tree for the carry (structure from an abstract
        eval — shapes themselves are not consulted beyond rank)."""
        key = (flags, silo, panel)
        if key not in self._cspecs:
            shapes = jax.eval_shape(init_fn, stacked)
            self._cspecs[key] = engine_carry_specs(
                shapes, cell_sharding=self.cfg.cell_sharding,
                panel_sharded=panel is not None)
        return self._cspecs[key]

    def _init_program(self, stacked: dict, flags: tuple):
        mesh, silo, panelf = self._variant(True)
        panel = panelf(flags[0])

        # NOT donated: the stacked cells stay live across every subsequent
        # segment call (donating them here would invalidate the whole run —
        # the donation-safety audit of DESIGN.md §15 rejects it)
        def build():
            fn = jax.vmap(self._closures(flags, silo, panel)["init"])
            if mesh is not None:
                cspecs = self._carry_specs(stacked, flags, silo, panel, fn)
                spec = engine_batch_spec(self.cfg.cell_sharding)
                fn = jax.shard_map(fn, mesh=mesh, in_specs=(spec,),
                                   out_specs=cspecs, check_vma=False)
            return jax.jit(fn)
        return self._programs.get((flags, "init", silo, panel), build)

    def _segment_program(self, stacked: dict, flags: tuple, seg_len: int):
        mesh, silo, panelf = self._variant(True)
        panel = panelf(flags[0])
        donate = bool(self.cfg.donate_carry)

        def build():
            cl = self._closures(flags, silo, panel)
            fn = jax.vmap(cl["segment"](seg_len), in_axes=(0, 0, None))
            if mesh is not None:
                cspecs = self._carry_specs(stacked, flags, silo, panel,
                                           jax.vmap(cl["init"]))
                spec = engine_batch_spec(self.cfg.cell_sharding)
                fn = jax.shard_map(fn, mesh=mesh,
                                   in_specs=(spec, cspecs, P()),
                                   out_specs=(cspecs, spec), check_vma=False)
            # donate the carry (arg 1): the (params, moments, (N, P) memory
            # panel, chain + sampler state) buffers are reused in place
            # across segments instead of fresh HBM allocations per segment;
            # callers interact through CarryHandle, whose consume-once
            # semantics turn use-after-donation into a loud error
            return jax.jit(fn, donate_argnums=(1,) if donate else ())
        return self._programs.get((flags, "seg", seg_len, silo, panel,
                                   donate), build)

    def _pad_cells(self, cells: list[dict]) -> list[dict]:
        """Pad an uneven batch to a multiple of the "cells" axis size by
        repeating the last cell (pad trajectories are dropped on return)."""
        mesh = self._mesh()
        if mesh is None or not self.cfg.cell_sharding:
            return list(cells)
        c = int(mesh.devices.shape[0])
        r = len(cells) % c
        return list(cells) + [cells[-1]] * ((c - r) % c)

    # ------------------------------------------------------------- cells
    def cell(self, *, seed: int = 0, mode: Optional[AvailabilityMode] = None,
             process: Optional[AvailabilityProcess] = None,
             masks: Optional[np.ndarray] = None, alpha: float = 1.0,
             h: Optional[np.ndarray] = None, avail_seed: int = 1234,
             sampler_seed: Optional[int] = None,
             sampler_process: Optional[SamplerProcess] = None,
             aggregator_process: Optional[AggregatorProcess] = None,
             fault_process: Optional[FaultProcess] = None,
             fault_seed: Optional[int] = None) -> dict:
        """One sweep cell = (seed, availability, sampler params) pytree.

        Mask path (``use_masks=True``): pass ``masks`` (rounds, N), e.g. from
        ``precompute_masks`` for bit-exact FLEngine availability.  Device
        path: pass ``process`` (any ``AvailabilityProcess`` scenario family)
        or ``mode`` (a legacy Table-1 mode, wrapped as its ``TableProcess``);
        the cell carries the process params + initial state
        (``init(PRNGKey(avail_seed))``) and per-round draws use the
        ``fold_in(avail_seed, t)`` jax stream.  Cells of different scenario
        families batch together in ``run_batch``.

        The SAMPLER is a per-cell choice too: ``sampler_process`` (any
        ``core.sampler_device.SamplerProcess``; defaults to the engine-level
        ``cfg.sampler`` family with this cell's ``alpha``) compiles to a
        ``lax.switch`` index, so cells of different samplers batch through
        one ``run_batch`` program.  Because every branch traces, EVERY cell
        carries the full (N, N) ``h`` (zeros when no FedGS cell needs it).

        The AGGREGATOR is a per-cell choice the same way:
        ``aggregator_process`` (any ``fed.aggregator_device
        .AggregatorProcess``; defaults to the engine-level
        ``cfg.aggregator`` family) compiles to a ``lax.switch`` index, so
        cells of different server-update rules batch through one
        ``run_batch`` program; the aggregator state is built in-scan from
        the cell's own ``params0``, and its (N, P) update-memory panel is
        carried only by the program variant that actually has a
        memory-family cell (``_program``).

        FAULT INJECTION is per-cell as well: ``fault_process`` (any
        ``fed.faults_device.FaultProcess``; defaults to the engine-level
        ``cfg.fault``/``cfg.fault_frac`` family — ``none`` by default)
        compiles to a ``lax.switch`` index, so benign and adversarial
        cells batch through one ``run_batch`` program; every cell carries
        the (small) fault params + latency state for stacking uniformity,
        but the scan carries fault state only in program variants with an
        actual fault cell (``_flags``).
        """
        c: dict = {"key": jax.random.PRNGKey(seed)}
        if self.use_masks:
            assert masks is not None and masks.shape == (self.cfg.rounds, self.n)
            c["masks"] = jnp.asarray(masks, bool)
        else:
            if process is None:
                assert mode is not None, \
                    "device-side availability needs a process or a mode"
                process = mode.process()
            c["avail_key"] = jax.random.PRNGKey(avail_seed)
            c["proc"] = process.params()
            c["proc_state"] = process.init(c["avail_key"])
        sproc = sampler_process if sampler_process is not None else \
            make_sampler_process(self.cfg.sampler, alpha=alpha,
                                 d_factor=self.cfg.poc_d_factor)
        c["sampler"] = sproc.params(data_sizes=self.ds.sizes)
        c["sampler_key"] = jax.random.PRNGKey(
            seed + 0x5E1EC7 if sampler_seed is None else sampler_seed)
        c["sampler_state"] = sproc.init(c["sampler_key"])
        aproc = aggregator_process if aggregator_process is not None else \
            make_aggregator_process(self.cfg.aggregator)
        c["agg"] = aproc.params()
        c["agg_key"] = jax.random.PRNGKey(seed + 0xA66)
        fproc = fault_process if fault_process is not None else \
            make_fault_process(self.cfg.fault, self.n,
                               frac=self.cfg.fault_frac)
        c["fault"] = fproc.params()
        c["fault_key"] = jax.random.PRNGKey(
            seed + 0xFA17 if fault_seed is None else fault_seed)
        c["fault_state"] = fproc.init(c["fault_key"])
        if self.cfg.graph_refresh_every > 0:
            c["init_key"] = jax.random.PRNGKey(seed + 778)
        elif isinstance(h, jax.ShapeDtypeStruct):
            # abstract H for compile-only dry-runs (lower_batch(abstract=
            # True)): a datacenter-N (N, N) matrix lowers without ever
            # materializing on this host
            c["h"] = h
        elif h is not None:
            c["h"] = jnp.asarray(h, jnp.float32)
        else:
            assert sproc.family != "fedgs", \
                "static FedGS cell needs a normalized H"
            c["h"] = jnp.zeros((self.n, self.n), jnp.float32)
        return c

    # -------------------------------------------------------------- runs
    def _to_history(self, out, i: Optional[int] = None,
                    telemetry: Optional[dict] = None) -> ScanHistory:
        pick = (lambda x: np.asarray(x)) if i is None else \
               (lambda x: np.asarray(x[i]))
        return ScanHistory(val_loss=pick(out["val_loss"]),
                           val_acc=pick(out["val_acc"]),
                           count_var=pick(out["count_var"]),
                           gini=pick(out["gini"]),
                           sel=pick(out["sel"]), valid=pick(out["valid"]),
                           counts=pick(out["counts"]),
                           telemetry=None if telemetry is None else
                           jax.tree_util.tree_map(pick, telemetry))

    # --------------------------------------------------- telemetry plumbing
    def _emit_segment_metrics(self, b: int, t0: int, k: int, traj_h: dict,
                              tel_h: Optional[dict]):
        """Stream one fetched segment's per-round rows to the metrics sink
        (DESIGN.md §17) — called per segment as it lands on host, so a
        service front-end sees metrics while later segments still
        compute.  Pad cells (mesh batch padding) are not emitted."""
        if self.sink is None:
            return
        with self.tracer.span("metrics_emit", t0=t0, rounds=k):
            for j in range(b):
                for r in range(k):
                    row = {"cell": j, "t": t0 + r,
                           "n_valid": int(np.sum(traj_h["valid"][j][r]))}
                    for f in ("val_loss", "val_acc", "count_var", "gini"):
                        row[f] = float(traj_h[f][j][r])
                    if tel_h is not None:
                        row["metrics"] = {
                            kk: np.asarray(v[j][r])
                            for kk, v in tel_h.items()}
                    self.sink.emit("round", row)
            self.sink.emit("segment",
                           {"t0": t0, "rounds": k, "cells": b,
                            "programs": self._programs.stats()})

    def _fetch_segment(self, t0: int, k: int, traj_dev, b: int) -> dict:
        """ONE whole-pytree ``jax.device_get`` of a segment trajectory;
        the telemetry subtree is split off (stashed for the final
        histories + streamed to the sink) so the trajectory that flows
        into checkpoints and stream consumers is bitwise the
        telemetry-off one (assumption log #24)."""
        with self.tracer.span("device_get", t0=t0, rounds=k):
            traj_h = jax.device_get(traj_dev)
        tel_h = traj_h.pop("telemetry", None)
        if tel_h is not None:
            self._tel_parts.append((t0, k, tel_h))
        self._emit_segment_metrics(b, t0, k, traj_h, tel_h)
        return traj_h

    def _assemble_telemetry(self) -> Optional[dict]:
        """Concat the stashed per-segment telemetry into (B, T, ...)
        arrays; a resumed run's pre-resume prefix (telemetry is not
        checkpointed) is NaN-filled so round indices stay aligned."""
        if not self._tel_parts:
            return None
        parts, t_next = [], 0
        for t0, k, tel in self._tel_parts:
            if t0 > t_next:
                gap = t0 - t_next
                parts.append(jax.tree_util.tree_map(
                    lambda x, g=gap: np.full(
                        x.shape[:1] + (g,) + x.shape[2:], np.nan,
                        x.dtype), tel))
            parts.append(tel)
            t_next = t0 + k
        return jax.tree_util.tree_map(
            lambda *xs: np.concatenate(xs, axis=1), *parts)

    def run(self, cell: dict) -> ScanHistory:
        """Execute one cell; the whole trajectory is a single device program
        (always single-device — the mesh applies to ``run_batch``).  The
        output pytree comes back in ONE ``jax.device_get`` transfer (which
        also synchronizes), not one ``np.asarray`` per history field."""
        with self.tracer.span("run_cell"):
            out = jax.device_get(self._program([cell], False)(cell))
        tel = out.pop("telemetry", None)
        self.params = out["params"]
        return self._to_history(out, telemetry=tel)

    # ------------------------------------------------- segmented runtime
    def init_carry(self, cells: list[dict]) -> CarryHandle:
        """Build the full scan carry for these cells and wrap it in a
        donation-safe handle (DESIGN.md §15): ``run_segment`` consumes the
        handle and returns a fresh one; touching a consumed handle raises."""
        cells_p = self._pad_cells(cells)
        flags = self._flags(cells_p)
        stacked = stack_cells(cells_p)
        return CarryHandle(self._init_program(stacked, flags)(stacked))

    def run_segment(self, cells: list[dict], carry: CarryHandle,
                    t0: int, seg_len: int):
        """Dispatch one ``seg_len``-round segment starting at round ``t0``
        (asynchronously — nothing blocks until the outputs are consumed).
        The carry handle is CONSUMED: with ``cfg.donate_carry`` its device
        buffers are donated to the segment program and reused in place.
        Returns ``(new_handle, traj_device)``."""
        cells_p = self._pad_cells(cells)
        flags = self._flags(cells_p)
        return self._run_segment(stack_cells(cells_p), flags, carry, t0,
                                 seg_len)

    def _run_segment(self, stacked: dict, flags: tuple, carry: CarryHandle,
                     t0: int, seg_len: int):
        with self.tracer.span("program_get", seg_len=seg_len):
            fn = self._segment_program(stacked, flags, seg_len)
        # dispatch is async: this span covers trace/lower/compile on a
        # cache miss and ~µs enqueue steady-state (assumption log #25)
        with self.tracer.span("dispatch_segment", t0=t0, rounds=seg_len):
            new_carry, traj = fn(stacked, carry.consume(), jnp.int32(t0))
        return CarryHandle(new_carry), traj

    def run_batch_stream(self, cells: list[dict], *,
                         ckpt_path: Optional[str] = None,
                         ckpt_every: int = 0, resume: bool = False):
        """Generator driving the segmented scan as an async pipeline:
        yields ``(t_start, seg_len, traj_host)`` per segment IN ORDER,
        where ``traj_host`` leaves are (B_padded, seg_len, ...) numpy
        arrays — incremental history streaming for a service front-end
        (``launch/serve.py``) instead of one post-scan gather.

        Pipelining (``cfg.async_pipeline``): segment k+1 is dispatched
        before segment k's trajectory is fetched, so the device→host
        transfer (one ``jax.device_get`` per segment) and the npz
        checkpoint write (a background ``AsyncCheckpointWriter`` thread)
        overlap segment k+1's device compute.  On checkpoint boundaries
        the carry is gathered to host BEFORE the next (donating) dispatch
        — the one mandatory sync of the loop.  With
        ``cfg.async_pipeline=False`` every segment blocks and writes
        inline (the pre-runtime-layer PR 6 behavior); either way the
        dispatched per-round programs are identical, so results are
        bitwise equal (assumption log #19).

        After exhaustion ``self.params`` / ``self.final_counts`` hold the
        final state (host copies, pad cells included)."""
        cfg = self.cfg
        b = len(cells)
        cells_p = self._pad_cells(cells)
        flags = self._flags(cells_p)
        stacked = stack_cells(cells_p)
        rounds = cfg.rounds
        every = int(ckpt_every) if ckpt_every else rounds
        concat = lambda parts: jax.tree_util.tree_map(        # noqa: E731
            lambda *xs: np.concatenate(xs, axis=1), *parts)
        self._tel_parts = []
        self._writer_stats = None
        if self.sink is not None:
            self.sink.emit("run_start",
                           {"cells": b, "rounds": rounds, "mesh": cfg.mesh,
                            "telemetry": bool(cfg.telemetry),
                            "ckpt_every": int(ckpt_every)})
        t0, parts, carry = 0, [], None
        if resume and ckpt_path is not None:
            p = ckpt_path if ckpt_path.endswith(".npz") else ckpt_path + ".npz"
            if os.path.exists(p):
                state = load_checkpoint(ckpt_path)
                t0 = int(np.asarray(state["round"]))
                carry = jax.tree_util.tree_map(jnp.asarray, state["carry"])
                parts.append(state["traj"])
                yield 0, t0, state["traj"]
        if carry is None:
            with self.tracer.span("init_carry", cells=len(cells_p)):
                carry = self._init_program(stacked, flags)(stacked)
        handle = CarryHandle(carry)
        writer = AsyncCheckpointWriter() \
            if (ckpt_path is not None and cfg.async_pipeline) else None
        pending = None                      # (t_start, seg_len, traj_device)

        def meta_of(t_next):
            return {"round": t_next, "rounds": rounds, "b": b,
                    "cells": len(cells_p), "mesh": cfg.mesh}
        try:
            while t0 < rounds:
                k = min(every, rounds - t0)
                handle, traj_dev = self._run_segment(stacked, flags, handle,
                                                     t0, k)
                t1 = t0 + k
                need_ckpt = ckpt_path is not None and t1 < rounds
                if not cfg.async_pipeline:
                    # PR 6 semantics: block, fetch, write inline
                    traj_h = self._fetch_segment(t0, k, traj_dev, b)
                    parts.append(traj_h)
                    if need_ckpt:
                        with self.tracer.span("checkpoint_write", round=t1):
                            save_checkpoint(
                                ckpt_path,
                                {"carry": jax.device_get(handle.tree),
                                 "round": np.int64(t1),
                                 "traj": concat(parts)},
                                metadata=meta_of(t1))
                    yield t0, k, traj_h
                elif need_ckpt:
                    # the checkpoint needs the cumulative trajectory AND
                    # the post-segment carry on host; the carry gather
                    # must land before the next donating dispatch.  The
                    # concat + npz write run on the writer thread,
                    # overlapping the next segment's compute.
                    if pending is not None:
                        ph = self._fetch_segment(pending[0], pending[1],
                                                 pending[2], b)
                        parts.append(ph)
                        yield pending[0], pending[1], ph
                        pending = None
                    traj_h = self._fetch_segment(t0, k, traj_dev, b)
                    parts.append(traj_h)
                    carry_h = jax.device_get(handle.tree)
                    snapshot = list(parts)

                    def _write(ch=carry_h, sn=snapshot, tn=t1):
                        with self.tracer.span("checkpoint_write", round=tn):
                            save_checkpoint(
                                ckpt_path,
                                {"carry": ch, "round": np.int64(tn),
                                 "traj": concat(sn)},
                                metadata=meta_of(tn))
                    writer.submit(_write)
                    yield t0, k, traj_h
                else:
                    # free-running: fetch the PREVIOUS segment while this
                    # one computes
                    if pending is not None:
                        ph = self._fetch_segment(pending[0], pending[1],
                                                 pending[2], b)
                        parts.append(ph)
                        yield pending[0], pending[1], ph
                    pending = (t0, k, traj_dev)
                t0 = t1
            if pending is not None:
                ph = self._fetch_segment(pending[0], pending[1],
                                         pending[2], b)
                parts.append(ph)
                yield pending[0], pending[1], ph
            final = jax.device_get({"params": handle.tree["agg"]["prev"],
                                    "counts": handle.tree["counts"]})
            self.params = jax.tree_util.tree_map(lambda x: x[:b],
                                                 final["params"])
            self.final_counts = final["counts"][:b]
        finally:
            if writer is not None:
                try:
                    writer.close()
                finally:
                    self._writer_stats = writer.stats()
            if self.sink is not None:
                self.sink.emit("run_end", {"runtime": self.runtime_stats()})

    def run_batch(self, cells: list[dict], *,
                  ckpt_path: Optional[str] = None, ckpt_every: int = 0,
                  resume: bool = False) -> list[ScanHistory]:
        """Execute B cells as ONE vmapped-and-scanned XLA program
        (shard_map'd over the mesh when ``cfg.mesh`` is set).

        Checkpointing (DESIGN.md §13): with ``ckpt_path`` the scan runs in
        ``ckpt_every``-round segments and after each non-final segment the
        FULL carry + accumulated trajectory + next round index are saved
        (gathered to host npz — device-layout-free).  ``resume=True`` picks
        up from ``ckpt_path`` if it exists (else starts fresh); at the same
        mesh + cadence the tail recomputes bitwise-identically to the
        uninterrupted run.  Resume on a DIFFERENT device count / mesh
        reshards the loaded carry to the target program's specs and is
        bitwise at ``ckpt_every=1`` (one-round segments compile identically
        on every device count; longer scans pick up ulp-level eval drift
        from SPMD-/length-dependent while-body fusion).

        Runtime layer (DESIGN.md §15): the segmented path runs donated +
        pipelined through ``run_batch_stream`` (bitwise-identical results —
        the compiled per-round programs are unchanged); ``ckpt_every``
        WITHOUT a ``ckpt_path`` now streams the scan in segments too
        (previously it silently ran fused).
        """
        b = len(cells)
        cells_p = self._pad_cells(cells)
        if ckpt_path is None and not resume and not ckpt_every:
            fn = self._program(cells_p, True)
            # ONE device_get of the whole output pytree (one transfer +
            # sync), not one np.asarray round-trip per history field
            with self.tracer.span("device_get", t0=0,
                                  rounds=self.cfg.rounds):
                out = jax.device_get(fn(stack_cells(cells_p)))
            tel = out.pop("telemetry", None)
            self._emit_segment_metrics(b, 0, self.cfg.rounds, out, tel)
            self.params = jax.tree_util.tree_map(lambda x: x[:b],
                                                 out["params"])
            return [self._to_history(out, i, telemetry=tel)
                    for i in range(b)]

        parts = [traj for _, _, traj in self.run_batch_stream(
            cells, ckpt_path=ckpt_path, ckpt_every=ckpt_every,
            resume=resume)]
        traj = jax.tree_util.tree_map(lambda *xs: np.concatenate(xs, axis=1),
                                      *parts)
        tel = self._assemble_telemetry()
        # the stream already set self.params / self.final_counts (B-sliced)
        out = {**traj, "counts": self.final_counts}
        return [self._to_history(out, i, telemetry=tel) for i in range(b)]

    def lower_batch(self, cells: list[dict], *, abstract: bool = False):
        """Lower (without running) — for compile-time measurement.

        ``abstract=True`` lowers against ``ShapeDtypeStruct``s instead of
        device arrays (the stacked-cell structure comes from
        ``jax.eval_shape`` over ``stack_cells``), so datacenter-N cells —
        whose (N, N) ``h`` could never materialize on this host — still
        produce HLO (the compile-only silo-axis dry-run,
        ``launch/fedsim.py::datacenter_cell_dryrun``)."""
        cells_p = self._pad_cells(cells)
        stacked = jax.eval_shape(stack_cells, cells_p) if abstract \
            else stack_cells(cells_p)
        return self._program(cells_p, True).lower(stacked)

    def carry_shapes(self, cells: list[dict]):
        """Abstract (per-device local) carry pytree for these cells —
        what one device holds per scan step.  Used by the compile-only
        dry-run to pin the carry footprint (a silo-sharded memory panel
        must show its (N/silo, P) rows here)."""
        cells_p = self._pad_cells(cells)
        flags = self._flags(cells_p)
        _, silo, panelf = self._variant(True)
        stacked = jax.eval_shape(stack_cells, cells_p)
        return jax.eval_shape(
            jax.vmap(self._closures(flags, silo, panelf(flags[0]))["init"]),
            stacked)
