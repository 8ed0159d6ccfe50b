"""Unified telemetry layer (DESIGN.md §17): in-scan stage health metrics,
host-side spans on the profiler's clock, and the shared
``runtime_stats`` snapshot.

The engine runs a whole FedGS round — 3DG rebuild, availability draw,
sampling, (possibly faulted) local training, aggregation (PAPER.md
Alg. 1) — as ONE opaque ``lax.scan`` program, so by default the only
per-round signals that come back are the end-of-round ``ScanHistory``
eval fields.  Diagnosing long-term sampling bias under non-stationary
availability (Rodio et al. 2023; Ribero et al. 2022), a diverging sweep
cell, or a regressed Pallas kernel needs per-stage, per-round health
signals.  Three pieces:

in-scan health channel (``round_telemetry``)
    A pure, scan-traceable metrics pytree computed INSIDE the step body
    from intermediates the step already materializes: per-stage
    update-norm / NaN-fraction / clip-rate on the (M, P) update panel,
    sampler dispersion (the mean pairwise H-distance of the selected set
    — the quantity the paper's Eq. 16 objective maximizes), availability
    rate, aggregator weight entropy, global param-delta norm, and —
    gated exactly like the PR-9 fault carry — the memory panel's
    staleness histogram and the fault seam's corruption magnitude.
    Every metric is a CONSUMER of values the benign program already
    computes (reductions only — nothing feeds back), so a telemetry-off
    program, its outputs and its checkpoints are bitwise untouched
    (assumption log #24).

host-side span tracer (``Tracer``)
    Spans around the host runtime — program get / dispatch /
    device_get / metrics emit / checkpoint write — each entered as a
    ``jax.profiler.TraceAnnotation``: with ``--trace-dir`` they land in
    the one ``jax.profiler`` trace beside the device's ops, on its
    clock, and the tracer keeps bounded per-name aggregates.  Span
    durations are HOST wall-clock around ASYNC dispatch (assumption log
    #25): a "dispatch" span times enqueue, not device compute — device
    time per round stage comes from the trace (each stage of the round
    step is a ``jax.named_scope``, ``scan_engine.ROUND_STAGES``), and
    compile time from the ``ProgramCache`` executable-cache probe
    (DESIGN.md §15).

``runtime_snapshot``
    One merged counters snapshot shared by ``ScanEngine``, ``FLEngine``
    and ``SimService``: the ``ProgramCache`` hit/miss/compile counters
    (flat, for backward compatibility), the ``AsyncCheckpointWriter``
    queue-depth/backpressure counters, and the tracer's per-span
    aggregates.
"""
from __future__ import annotations

import threading
import time
from contextlib import contextmanager
from typing import Optional

import numpy as np

import jax
import jax.numpy as jnp

TELEMETRY_SCHEMA_VERSION = 1

# staleness-age histogram bin edges (rounds since a client's last
# participation): ages land in [0,1), [1,2), [2,4), ... [64, inf) —
# static so the (N_STALE_BINS,) vector is scan-traceable
STALE_BIN_EDGES = (1.0, 2.0, 4.0, 8.0, 16.0, 32.0, 64.0)
N_STALE_BINS = len(STALE_BIN_EDGES) + 1


# --------------------------------------------------- in-scan health metrics
def _sq_norms_vs_base(stacked, base):
    """(M,) per-client squared L2 norm of ``stacked_k - base`` without
    materializing a flat (M, P) panel: per-leaf reductions summed."""
    def leaf(s, b):
        d = s - b[None]
        return jnp.sum(jnp.square(d).reshape(d.shape[0], -1), axis=1)
    parts = jax.tree_util.tree_map(leaf, stacked, base)
    return sum(jax.tree_util.tree_leaves(parts))


def _nonfinite_fracs(stacked):
    """(M,) fraction of non-finite entries per client across all leaves."""
    def bad(s):
        return jnp.sum((~jnp.isfinite(s)).reshape(s.shape[0], -1)
                       .astype(jnp.float32), axis=1)

    def size(s):
        return np.prod(s.shape[1:], dtype=np.float64)
    bads = sum(jax.tree_util.tree_leaves(
        jax.tree_util.tree_map(bad, stacked)))
    total = sum(size(s) for s in jax.tree_util.tree_leaves(stacked))
    return bads / jnp.float32(max(total, 1.0))


def selection_dispersion(h, sel, valid):
    """Mean pairwise H-distance of the selected set — the per-round value
    of the paper's Eq. 16 dispersion objective.  ``sel`` (M,) padded
    indices, ``valid`` (M,) pad mask; invalid slots contribute nothing.
    0 when fewer than two clients were selected."""
    vf = valid.astype(jnp.float32)
    pair = vf[:, None] * vf[None, :]
    pair = pair * (1.0 - jnp.eye(sel.shape[0], dtype=jnp.float32))
    hs = h[sel][:, sel]
    n_pairs = jnp.sum(pair)
    return jnp.where(n_pairs > 0, jnp.sum(hs * pair) / jnp.maximum(
        n_pairs, 1.0), jnp.float32(0.0))


def weight_entropy(weights):
    """Shannon entropy (nats) of the normalized aggregation weights — a
    collapse-to-one-client round shows up as entropy -> 0."""
    w = jnp.maximum(weights.astype(jnp.float32), 0.0)
    z = jnp.sum(w)
    p = w / jnp.maximum(z, 1e-12)
    ent = -jnp.sum(jnp.where(p > 0, p * jnp.log(p), 0.0))
    return jnp.where(z > 0, ent, jnp.float32(0.0))


def staleness_histogram(age):
    """(N_STALE_BINS,) counts of per-client staleness ages (rounds since
    last participation) over the static ``STALE_BIN_EDGES`` buckets."""
    edges = jnp.asarray(STALE_BIN_EDGES, jnp.float32)
    idx = jnp.searchsorted(edges, age.astype(jnp.float32), side="right")
    return jnp.sum(jax.nn.one_hot(idx, N_STALE_BINS, dtype=jnp.float32),
                   axis=0)


def round_telemetry(*, avail, valid, sel, local, params_prev, params_new,
                    weights, h, clip_thresh: float = 10.0,
                    tau=None, t=None, fault_mag=None) -> dict:
    """The per-round in-scan metrics pytree (all jnp, scan-traceable).

    Pure CONSUMER of the step's intermediates: ``avail`` (N,) bool,
    ``sel``/``valid`` (M,) the padded selected set, ``local`` the stacked
    (M, ...) post-training client params, ``params_prev``/``params_new``
    the global params around the server update, ``weights`` (M,) the
    Eq. 18 aggregation weights (pads already zeroed), ``h`` the (N, N)
    normalized 3DG distance panel.  ``tau`` (+ ``t``) adds the memory
    aggregator's staleness histogram; ``fault_mag`` threads the fault
    seam's corruption magnitude through (computed at the seam, where the
    clean panel is still in scope).  Keys are the JSONL sink's metric
    names (schema v1)."""
    vf = valid.astype(jnp.float32)
    n_sel = jnp.sum(vf)
    sq = _sq_norms_vs_base(local, params_prev)
    norms = jnp.sqrt(jnp.maximum(sq, 0.0))
    nmask = jnp.where(valid, norms, 0.0)
    mean_norm = jnp.sum(nmask) / jnp.maximum(n_sel, 1.0)
    max_norm = jnp.max(jnp.where(valid, norms, -jnp.inf))
    max_norm = jnp.where(n_sel > 0, max_norm, jnp.float32(0.0))
    clip = jnp.sum((nmask > clip_thresh).astype(jnp.float32)) \
        / jnp.maximum(n_sel, 1.0)
    nan_frac = jnp.sum(jnp.where(valid, _nonfinite_fracs(local), 0.0)) \
        / jnp.maximum(n_sel, 1.0)
    delta_sq = sum(jax.tree_util.tree_leaves(jax.tree_util.tree_map(
        lambda a, b: jnp.sum(jnp.square(a - b)), params_new, params_prev)))
    tel = {
        "avail_rate": jnp.mean(avail.astype(jnp.float32)),
        "n_selected": n_sel,
        "update_norm_mean": mean_norm,
        "update_norm_max": max_norm,
        "update_clip_rate": clip,
        "update_nan_frac": nan_frac,
        "sampler_dispersion": selection_dispersion(h, sel, valid),
        "weight_entropy": weight_entropy(weights),
        "param_delta_norm": jnp.sqrt(jnp.maximum(delta_sq, 0.0)),
    }
    if tau is not None:
        age = jnp.maximum(jnp.asarray(t, jnp.float32) - tau, 0.0)
        tel["staleness_hist"] = staleness_histogram(age)
    if fault_mag is not None:
        tel["fault_corruption_norm"] = fault_mag
    return tel


def fault_corruption_norm(updf, cleanf, valid):
    """Mean L2 distance between the corrupted and clean flat (M, P)
    update panels over the valid slots — the fault seam's magnitude
    probe (0 for benign cells: the none branch is a bitwise identity)."""
    vf = valid.astype(jnp.float32)
    d = jnp.sqrt(jnp.maximum(
        jnp.sum(jnp.square(updf - cleanf), axis=1), 0.0))
    return jnp.sum(d * vf) / jnp.maximum(jnp.sum(vf), 1.0)


# ------------------------------------------------------- host span tracer
class Tracer:
    """Host spans on the profiler's clock, with per-name aggregates.

    ``span(name, **attrs)`` is a context manager.  On an enabled tracer it
    enters ``jax.profiler.TraceAnnotation(name, **attrs)``, so that while
    a ``jax.profiler`` trace is recording (``--trace-dir``) the span lands
    in the same trace as the device's ops, on the same clock, and it adds
    the span's host wall-clock duration to the per-name aggregates
    (count / total_ms / max_ms) that ``summary()`` returns: memory stays
    bounded by the number of distinct names, however long the process
    runs.  Thread-safe: checkpoint-writer spans record from the writer's
    thread.  A disabled tracer does nothing; the engines default to the
    shared ``NULL_TRACER``.

    Span durations are HOST wall-clock around ASYNC dispatch (assumption
    log #25): a "dispatch" span times enqueue, not device compute.
    Device time per round stage comes from the ``jax.named_scope`` of each
    stage inside the compiled program (``scan_engine.ROUND_STAGES``)."""

    def __init__(self, *, enabled: bool = True):
        self.enabled = enabled
        self._stats: dict[str, dict] = {}
        self._lock = threading.Lock()

    @contextmanager
    def span(self, name: str, **attrs):
        if not self.enabled:
            yield self
            return
        t0 = time.perf_counter()
        try:
            with jax.profiler.TraceAnnotation(name, **attrs):
                yield self
        finally:
            ms = (time.perf_counter() - t0) * 1e3
            with self._lock:
                s = self._stats.setdefault(
                    name, {"count": 0, "total_ms": 0.0, "max_ms": 0.0})
                s["count"] += 1
                s["total_ms"] += ms
                s["max_ms"] = max(s["max_ms"], ms)

    def summary(self) -> dict:
        """Per-span-name aggregates: count / total_ms / max_ms."""
        with self._lock:
            return {k: {"count": s["count"],
                        "total_ms": round(s["total_ms"], 3),
                        "max_ms": round(s["max_ms"], 3)}
                    for k, s in self._stats.items()}


NULL_TRACER = Tracer(enabled=False)


def make_tracer(trace_dir: Optional[str] = None) -> Tracer:
    """CLI-knob constructor: ``--trace-dir`` enables the host spans (the
    ``jax.profiler`` trace they land in is recorded by
    ``launch.obs_cli``); without it, the ``NULL_TRACER``."""
    return Tracer() if trace_dir else NULL_TRACER


# ------------------------------------------------------ unified snapshot
def runtime_snapshot(*, programs=None, writer: Optional[dict] = None,
                     tracer: Optional[Tracer] = None,
                     extra: Optional[dict] = None) -> dict:
    """The ONE ``runtime_stats()`` shape shared by both engines and the
    service: the ``ProgramCache`` counters stay FLAT at the top level
    (``hits`` / ``misses`` / ``compiles`` / ``compile_ms`` / ``size`` —
    the pre-telemetry consumers in the benches read them there), with
    the checkpoint-writer and span sections nested beside them."""
    snap: dict = {"telemetry_schema": TELEMETRY_SCHEMA_VERSION}
    if programs is not None:
        snap.update(programs.stats())
    if writer is not None:
        snap["checkpoint_writer"] = dict(writer)
    if tracer is not None and tracer.enabled:
        snap["spans"] = tracer.summary()
    if extra:
        snap.update(extra)
    return snap
