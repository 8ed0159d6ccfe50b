"""The federated round engine (Algorithm 1).

Per round t:
  1. availability mode draws A_t            (independent seed stream)
  2. sampler picks S_t ⊆ A_t, |S_t| ≤ M     (FedGS solves Eq. 16)
  3. broadcast θ^t; vmap'd local training (E steps SGD, optional prox)
  4. server update: any ``AggregatorProcess`` family via the shared device
     apply (``fed/server.py::ServerAggregator``; default = Eq. 18 FedAvg,
     bit-parity with the legacy ``aggregate``)
  5. update counts v^{t+1}
Evaluation on the shared validation split; history records loss/acc/fairness.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Callable, Optional

import numpy as np

import jax
import jax.numpy as jnp

from repro.core.availability import AvailabilityMode, host_draw
from repro.core.sampler import Sampler, FedGSSampler
from repro.core import graph as graph_mod
from repro.data.fed_dataset import FedDataset
from repro.fed.client import make_local_trainer, make_loss_prober
from repro.fed.faults_device import HostFaultInjector, make_fault_process
from repro.fed.models import FedModel
from repro.fed.runtime import (
    AsyncCheckpointWriter, ProgramCache,
)
from repro.fed.server import ServerAggregator
from repro.fed.telemetry import NULL_TRACER, runtime_snapshot


@dataclass
class FLConfig:
    rounds: int = 200
    sample_frac: float = 0.1          # M = frac * N (paper: 0.1 / 0.2)
    local_steps: int = 10             # E
    batch_size: int = 10
    lr: float = 0.1
    lr_decay: float = 0.998
    prox_mu: float = 0.0
    eval_every: int = 5
    seed: int = 0
    avail_seed: int = 1234            # independent availability stream
    # dynamic 3DG: rebuild the graph from participants' uploaded models every
    # K rounds (0 = static graph; paper §3.2 "dynamically built and polished
    # round by round")
    graph_refresh_every: int = 0


@dataclass
class History:
    rounds: list = field(default_factory=list)
    val_loss: list = field(default_factory=list)
    val_acc: list = field(default_factory=list)
    count_var: list = field(default_factory=list)
    sampled: list = field(default_factory=list)

    @property
    def best_loss(self) -> float:
        return float(np.min(self.val_loss)) if self.val_loss else float("inf")

    @property
    def final_counts_var(self) -> float:
        return self.count_var[-1] if self.count_var else 0.0


class FLEngine:
    def __init__(self, ds: FedDataset, model: FedModel, sampler: Sampler,
                 mode: AvailabilityMode, cfg: FLConfig, *,
                 aggregator=None, agg_backend: str = "ref",
                 fault=None, fault_frac: float = 0.0,
                 fault_seed: Optional[int] = None,
                 tracer=None, sink=None):
        """``aggregator`` is any ``fed.aggregator_device.AggregatorProcess``
        (default FedAvg — bit-parity with the legacy Eq. 18 path);
        ``agg_backend`` routes the memory family's scatter+reduction.
        ``fault`` is a ``fed.faults_device.FaultProcess`` (or a family name
        string, built with ``fault_frac`` adversarial clients) — corruption
        is injected between local training and ``server.apply`` through
        ``HostFaultInjector``, the same branch code and
        ``fold_in(PRNGKey(fault_seed), t)`` stream the scan engine traces,
        so a matching scan cell replays the host run bit-exactly.
        ``fault_seed`` defaults to ``cfg.seed + 0xFA17`` (the scan cell
        convention)."""
        self.ds, self.model, self.sampler, self.mode, self.cfg = ds, model, sampler, mode, cfg
        self.n = ds.n_clients
        self.m = max(1, int(round(cfg.sample_frac * self.n)))
        self._server = ServerAggregator(aggregator, n_clients=self.n,
                                        data_sizes=ds.sizes,
                                        backend=agg_backend, seed=cfg.seed)
        if isinstance(fault, str):
            fault = make_fault_process(fault, self.n, frac=fault_frac)
        if fault is not None and fault.family != "none":
            self._faults = HostFaultInjector(
                fault, fault_seed=cfg.seed + 0xFA17
                if fault_seed is None else fault_seed)
        else:
            self._faults = None
        # observability spine (DESIGN.md §17): the host engine's jitted
        # programs route through the same ProgramCache as the scan engine,
        # so runtime_stats() reports hit/miss/compile counters with one
        # shared snapshot shape (runtime_snapshot); tracer spans + metric
        # sink are optional and default to no-ops
        self.tracer = tracer if tracer is not None else NULL_TRACER
        self.sink = sink
        self._programs = ProgramCache(maxsize=8)
        self._writer_stats: Optional[dict] = None
        self._trainer = self._programs.get(
            "trainer", lambda: make_local_trainer(
                model.loss, local_steps=cfg.local_steps,
                batch_size=cfg.batch_size, prox_mu=cfg.prox_mu))
        self._prober = self._programs.get(
            "prober", lambda: make_loss_prober(model.loss)) \
            if sampler.needs_losses else None
        self._eval = self._programs.get(
            "eval", lambda: jax.jit(lambda p, x, y: (
                model.loss(p, x, y), model.accuracy(p, x, y))))
        self.counts = np.zeros(self.n)

    def runtime_stats(self) -> dict:
        """The shared telemetry snapshot (same shape as
        ``ScanEngine.runtime_stats``): flat ProgramCache counters, the
        last run's checkpoint-writer backpressure counters and the
        tracer's per-span aggregates."""
        return runtime_snapshot(programs=self._programs,
                                writer=self._writer_stats,
                                tracer=self.tracer)

    # ------------------------------------------------------------- 3DG setup
    def install_oracle_graph(self, features: Optional[np.ndarray] = None,
                             eps: float = 0.1, sigma2: float = 0.01,
                             backend: str = "ref"):
        """Build the oracle 3DG (label-distribution features by default,
        Appendix C) and hand H to a FedGS sampler."""
        if not isinstance(self.sampler, FedGSSampler):
            return None
        if features is None:
            features = self.ds.label_dist
        _, r, h = graph_mod.build_3dg(np.asarray(features), eps=eps,
                                      sigma2=sigma2, backend=backend)
        self.sampler.set_graph(h)
        return r

    def install_graph_from_H(self, h: np.ndarray):
        if isinstance(self.sampler, FedGSSampler):
            self.sampler.set_graph(h)

    # ------------------------------------------------------- dynamic 3DG
    def install_dynamic_graph(self, refresh_every: int = 10, eps: float = 0.1,
                              sigma2: float = 0.01, probe_size: int = 64):
        """Functional-similarity 3DG maintained online (paper §3.2): the
        initial graph comes from one all-clients local-training probe round
        (the paper's everyone-available-at-init assumption); afterwards the
        server re-embeds only the clients that participate and rebuilds
        V -> R -> H every ``refresh_every`` rounds."""
        if not isinstance(self.sampler, FedGSSampler):
            return
        self.cfg.graph_refresh_every = refresh_every
        self._graph_eps, self._graph_sigma2 = eps, sigma2
        rng = np.random.default_rng(self.cfg.seed + 777)
        xv = np.asarray(self.ds.x_val, np.float64).reshape(len(self.ds.x_val), -1)
        mu, cov = xv.mean(0), np.cov(xv.T) + 1e-4 * np.eye(xv.shape[1])
        probe = rng.multivariate_normal(mu, cov, probe_size).astype(np.float32)
        self._probe = jnp.asarray(probe.reshape(probe_size, *self.ds.x_val.shape[1:]))

        # init: probe round over ALL clients from a fresh global model
        key = jax.random.PRNGKey(self.cfg.seed + 778)
        params = self.model.init(key)
        stacked = self._trainer(params, jnp.asarray(self.ds.x),
                                jnp.asarray(self.ds.y),
                                jnp.asarray(self.ds.sizes),
                                jnp.float32(self.cfg.lr),
                                jax.random.split(key, self.n))
        self._emb = np.array(graph_mod.probe_embeddings(
            self.model.embed, stacked, self._probe), copy=True)
        self._rebuild_dynamic_graph()

    def _rebuild_dynamic_graph(self):
        from repro.core.graph_device import GraphConfig, build_3dg
        cfg = GraphConfig(eps=self._graph_eps, sigma2=self._graph_sigma2,
                          similarity="functional")
        _, _, h = build_3dg(jnp.asarray(self._emb, jnp.float32), cfg)
        self.sampler.set_graph(np.asarray(h))

    def _update_dynamic_embeddings(self, sel, local_stacked):
        emb = np.asarray(graph_mod.probe_embeddings(
            self.model.embed, local_stacked, self._probe))
        self._emb[sel] = emb

    # ---------------------------------------------------------------- round
    def run(self, progress: Callable | None = None, *,
            ckpt_path: str | None = None, ckpt_every: int = 0,
            resume: bool = False) -> History:
        """Run the federated rounds.  Randomness is derived per round from
        (seed, t) SeedSequences, so the process is Markov in
        (params, counts, t) and a checkpoint resume is exact; the
        availability stream stays independent of training randomness and
        identical across methods (Appendix C)."""
        cfg = self.cfg
        key0 = jax.random.PRNGKey(cfg.seed)
        params = self.model.init(key0)
        hist = History()
        start_round = 0
        # server-update state (momentum / Adam moments / update memory):
        # built from the initial params, then OVERWRITTEN wholesale by the
        # checkpoint on resume — stateful aggregators (fedavgm / fedadam /
        # fedprox_w / memory) resume bitwise-exactly (DESIGN.md §13; the
        # pre-§13 format dropped this state, pinned fixed by
        # tests/test_checkpoint_resume.py)
        self._server.init(params)
        # fault-injector state (AR(1) latency chain + stale panel) follows
        # the same init-then-overwrite-on-resume protocol as server state
        if self._faults is not None:
            self._faults.init(params)
        if resume and ckpt_path:
            import os
            from repro.checkpoint.ckpt import load_checkpoint
            if os.path.exists(ckpt_path if ckpt_path.endswith(".npz")
                              else ckpt_path + ".npz"):
                like = {"params": params, "counts": self.counts,
                        "round": np.zeros((), np.int64),
                        "server": self._server.state}
                if self._faults is not None:
                    like["faults"] = self._faults.state
                try:
                    state = load_checkpoint(ckpt_path, like=like)
                    self._server.state = jax.tree_util.tree_map(
                        jnp.asarray, state["server"])
                except KeyError:      # older checkpoint: missing server or
                    like.pop("server")      # fault state — those restart
                    like.pop("faults", None)
                    state = load_checkpoint(ckpt_path, like=like)
                params = jax.tree_util.tree_map(jnp.asarray, state["params"])
                self.counts = np.asarray(state["counts"], np.float64)
                start_round = int(state["round"]) + 1
                if "server" not in state:
                    self._server.init(params)
                if self._faults is not None:
                    if "faults" in state:
                        self._faults.state = jax.tree_util.tree_map(
                            jnp.asarray, state["faults"])
                    else:
                        self._faults.init(params)

        xs = jnp.asarray(self.ds.x)
        ys = jnp.asarray(self.ds.y)
        sizes = jnp.asarray(self.ds.sizes)
        xv = jnp.asarray(self.ds.x_val)
        yv = jnp.asarray(self.ds.y_val)

        # periodic saves go through the background writer so npz
        # serialization + disk I/O overlap the next round's device compute;
        # close() before returning drains the queue and re-raises any write
        # error (DESIGN.md §15)
        writer = AsyncCheckpointWriter() \
            if (ckpt_path and ckpt_every) else None
        self._writer_stats = None
        if self.sink is not None:
            self.sink.emit("run_start",
                           {"engine": "host", "rounds": cfg.rounds,
                            "start_round": start_round,
                            "sampler": self.sampler.name})
        try:
            self._run_rounds(hist, params, start_round, xs, ys, sizes, xv,
                             yv, progress, ckpt_path, ckpt_every, writer)
        finally:
            if writer is not None:
                try:
                    writer.close()
                finally:
                    self._writer_stats = writer.stats()
            if self.sink is not None:
                self.sink.emit("run_end",
                               {"engine": "host",
                                "runtime": self.runtime_stats()})
        return hist

    def _run_rounds(self, hist, params, start_round, xs, ys, sizes, xv, yv,
                    progress, ckpt_path, ckpt_every, writer):
        cfg = self.cfg
        key0 = jax.random.PRNGKey(cfg.seed)
        for t in range(start_round, cfg.rounds):
            rng = np.random.default_rng(np.random.SeedSequence([cfg.seed, t]))
            key = jax.random.fold_in(key0, t)
            # the ONE shared host availability wrapper — the same call
            # precompute_masks stacks, so scan-engine mask cells replay this
            # engine's availability bit-exactly (works for AvailabilityMode
            # and ProcessMode scenario families alike)
            avail = host_draw(self.mode, t, cfg.avail_seed)
            losses = None
            if self._prober is not None:
                key, sub = jax.random.split(key)
                losses = jax.device_get(self._prober(
                    params, xs, ys, sizes, jax.random.split(sub, self.n)))
            sel = self.sampler.sample(
                avail=avail, m=self.m, rng=rng, counts=self.counts,
                data_sizes=self.ds.sizes, losses=losses, t=t)
            sel = np.asarray(sel, dtype=int)

            lr = cfg.lr * (cfg.lr_decay ** t)
            key, sub = jax.random.split(key)
            with self.tracer.span("local_train", t=t, m=len(sel)):
                local = self._trainer(params, xs[sel], ys[sel], sizes[sel],
                                      jnp.float32(lr),
                                      jax.random.split(sub, len(sel)))
            if self._faults is not None:
                local = self._faults.inject(local, params, sel, avail, t)
            with self.tracer.span("aggregate", t=t):
                params = self._server.apply(
                    local, self.ds.sizes[sel].astype(np.float32), sel,
                    avail, t)
            self.counts[sel] += 1

            if cfg.graph_refresh_every > 0 and hasattr(self, "_emb"):
                self._update_dynamic_embeddings(sel, local)
                if (t + 1) % cfg.graph_refresh_every == 0:
                    self._rebuild_dynamic_graph()

            if t % cfg.eval_every == 0 or t == cfg.rounds - 1:
                with self.tracer.span("eval", t=t):
                    vl, va = self._eval(params, xv, yv)
                from repro.core.fairness import count_variance
                hist.rounds.append(t)
                hist.val_loss.append(float(vl))
                hist.val_acc.append(float(va))
                hist.count_var.append(count_variance(self.counts))
                hist.sampled.append(sel.tolist())
                if self.sink is not None:
                    self.sink.emit("round",
                                   {"engine": "host", "t": t,
                                    "val_loss": float(vl),
                                    "val_acc": float(va),
                                    "count_var": hist.count_var[-1],
                                    "n_selected": int(len(sel)),
                                    "avail_rate":
                                    float(np.mean(avail))})
                if progress:
                    progress(t, float(vl), float(va))
            if writer is not None and (t + 1) % ckpt_every == 0:
                from repro.checkpoint.ckpt import save_checkpoint
                # snapshot on the main thread: params / server.state are
                # rebound functionally each round (the old trees stay
                # valid), but self.counts mutates in place — copy it
                snap = {"params": params, "counts": self.counts.copy(),
                        "round": np.asarray(t, np.int64),
                        "server": self._server.state}
                if self._faults is not None:
                    snap["faults"] = self._faults.state

                def _write(snap=snap, tn=t):
                    with self.tracer.span("checkpoint_write", round=tn):
                        save_checkpoint(
                            ckpt_path, snap,
                            metadata={"round": tn,
                                      "sampler": self.sampler.name,
                                      "aggregator":
                                      self._server.process.name})
                writer.submit(_write)
        self.params = params
