"""Beyond-paper: host-loop engine vs the jit-compiled scan engine.

The sweep the paper actually runs (Tables 2–4) is (sampler x availability
mode x seed); here the canonical slice — 7 availability modes x 3 seeds at
N=100 clients, synthetic logreg — is executed two ways:

  host  : ``FLEngine.run`` per cell, serially — one Python round loop with a
          host<->device sync per round (the trainer/eval jits are shared
          across cells so the host side pays compilation only once).
  scan  : ``ScanEngine.run_batch`` — all 21 cells as ONE XLA program
          (lax.scan over rounds, vmap over cells, device-side availability
          and sampling).

Reports steady-state speedup (the scan program is compiled once per
(sampler, shape) and cached — ``lax.scan`` makes compile time independent of
the round count) and the speedup including that one-off compile.

``run_shard`` / ``--shard`` adds the sharded-vs-single column: the SAME
cell batch through ``run_batch`` on the ("cells", "silo") engine mesh
(DESIGN.md §13), emitting ``results/BENCH_shard.json``.  Forced CPU host
devices share one physical socket, so the quick number measures shard_map
overhead, not real scale-out — the column exists to track that overhead
and to exercise the meshed program end-to-end in CI.  All repro imports
live inside functions so ``--shard`` can set
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` before jax
initializes (run_shard always runs it in a CPU-only child process).
"""
from __future__ import annotations

import json
import os
import pathlib
import subprocess
import sys
import time

import numpy as np

N_CLIENTS = 100
SEEDS = (0, 1, 2)
SHARD_MESH = (8, 1)
RESULTS = pathlib.Path(__file__).resolve().parent / "results"
_FORCE_FLAG = "--xla_force_host_platform_device_count=8"


def _make_mode(name, ds):
    from repro.core.availability import make_mode
    return make_mode(name, n_clients=ds.n_clients, data_sizes=ds.sizes,
                     label_sets=ds.label_sets(), num_labels=ds.num_classes,
                     seed=99)


def _host_engine(ds, model, sampler_name, mode, cfg, h):
    from repro.core.sampler import FedGSSampler, make_sampler
    from repro.fed.engine import FLEngine
    sampler = (FedGSSampler(alpha=1.0, max_sweeps=32)
               if sampler_name == "fedgs" else make_sampler(sampler_name))
    eng = FLEngine(ds, model, sampler, mode, cfg)
    if sampler_name == "fedgs":
        eng.install_graph_from_H(h)
    return eng


def run(quick: bool = True) -> list[dict]:
    from repro.core.availability import ALL_MODES
    from repro.data.synthetic import make_synthetic
    from repro.fed.engine import FLConfig
    from repro.fed.models import logistic_regression
    from repro.fed.scan_engine import ScanConfig, ScanEngine, oracle_h

    rounds = 30 if quick else 100
    ds = make_synthetic(n_clients=N_CLIENTS, alpha=0.5, beta=0.5, seed=0)
    model = logistic_regression()
    h_raw = None
    rows = []
    for sampler_name in ("uniform", "fedgs"):
        if sampler_name == "fedgs" and h_raw is None:
            from repro.core.graph import build_3dg
            _, _, h_raw = build_3dg(np.asarray(ds.opt_params))
        h_norm = oracle_h(ds.opt_params) if sampler_name == "fedgs" else None

        # ---------------- host loop, serial over cells --------------------
        cells_meta = [(m, s) for m in ALL_MODES for s in SEEDS]
        shared = None
        # warmup engine (compile trainer/eval once, outside the timed region)
        warm_cfg = FLConfig(rounds=2, sample_frac=0.1, local_steps=10,
                            batch_size=10, lr=0.1, eval_every=5, seed=0)
        warm = _host_engine(ds, model, sampler_name, _make_mode("IDL", ds),
                            warm_cfg, h_raw)
        warm.run()
        host_losses = []
        t0 = time.time()
        for mode_name, seed in cells_meta:
            cfg = FLConfig(rounds=rounds, sample_frac=0.1, local_steps=10,
                           batch_size=10, lr=0.1, eval_every=5, seed=seed)
            eng = _host_engine(ds, model, sampler_name,
                               _make_mode(mode_name, ds), cfg, h_raw)
            eng._trainer, eng._eval = warm._trainer, warm._eval  # share jits
            hist = eng.run()
            host_losses.append(hist.best_loss)
        host_s = time.time() - t0

        # ---------------- batched scan engine -----------------------------
        scfg = ScanConfig(rounds=rounds, m=max(1, N_CLIENTS // 10),
                          local_steps=10, batch_size=10, lr=0.1,
                          eval_every=5, sampler=sampler_name, max_sweeps=32)
        seng = ScanEngine(ds, model, scfg)
        cells = [seng.cell(seed=s, mode=_make_mode(m, ds), alpha=1.0,
                           h=h_norm) for m, s in cells_meta]
        t0 = time.time()
        hists = seng.run_batch(cells)          # includes the one-off compile
        scan_total_s = time.time() - t0
        # the ProgramCache's compile-event timer (DESIGN.md §15) splits the
        # one-off XLA compile out of the first-call wall-clock exactly,
        # instead of inferring it as first-call minus second-call
        compile_ms = seng.runtime_stats()["compile_ms"]
        t0 = time.time()
        hists = seng.run_batch(cells)          # steady state
        scan_run_s = time.time() - t0
        scan_losses = [h.best_loss for h in hists]

        rows.append({
            "table": "engine_bench", "sampler": sampler_name,
            "n_clients": N_CLIENTS, "rounds": rounds,
            "cells": len(cells_meta),
            "host_s": round(host_s, 2),
            "scan_total_s": round(scan_total_s, 2),
            "scan_run_s": round(scan_run_s, 2),
            "compile_ms": round(compile_ms, 1),
            "steady_ms": round(scan_run_s * 1e3, 1),
            "speedup": round(host_s / max(scan_run_s, 1e-9), 1),
            "speedup_incl_compile": round(host_s / max(scan_total_s, 1e-9), 1),
            "host_best_loss_mean": round(float(np.mean(host_losses)), 4),
            "scan_best_loss_mean": round(float(np.mean(scan_losses)), 4),
        })
        print(f"[engine_bench] {sampler_name}: host {host_s:.1f}s, "
              f"scan {scan_run_s:.2f}s (+{scan_total_s - scan_run_s:.1f}s "
              f"compile) -> {rows[-1]['speedup']}x", flush=True)

    # whole sweep (all sampler rows together): the headline number
    host_all = sum(r["host_s"] for r in rows)
    run_all = sum(r["scan_run_s"] for r in rows)
    total_all = sum(r["scan_total_s"] for r in rows)
    rows.append({
        "table": "engine_bench", "sampler": "ALL",
        "n_clients": N_CLIENTS, "rounds": rows[0]["rounds"],
        "cells": sum(r["cells"] for r in rows),
        "host_s": round(host_all, 2),
        "scan_total_s": round(total_all, 2),
        "scan_run_s": round(run_all, 2),
        "compile_ms": round(sum(r["compile_ms"] for r in rows), 1),
        "steady_ms": round(run_all * 1e3, 1),
        "speedup": round(host_all / max(run_all, 1e-9), 1),
        "speedup_incl_compile": round(host_all / max(total_all, 1e-9), 1),
        "host_best_loss_mean": float("nan"),
        "scan_best_loss_mean": float("nan"),
    })
    return rows


def summarize(rows) -> list[str]:
    out = ["", "== engine bench: host round loop vs batched scan engine "
           "(7 modes x 3 seeds) =="]
    out.append(f"{'sampler':>8s} {'cells':>6s} {'rounds':>7s} {'host (s)':>9s} "
               f"{'scan (s)':>9s} {'compile (s)':>12s} {'speedup':>8s} "
               f"{'w/ compile':>11s}")
    for r in rows:
        out.append(
            f"{r['sampler']:>8s} {r['cells']:6d} {r['rounds']:7d} "
            f"{r['host_s']:9.2f} {r['scan_run_s']:9.2f} "
            f"{r['scan_total_s'] - r['scan_run_s']:12.2f} "
            f"{r['speedup']:7.1f}x {r['speedup_incl_compile']:10.1f}x")
    out.append("   (best-loss sanity: host vs scan mean "
               + ", ".join(f"{r['sampler']} {r['host_best_loss_mean']:.3f}/"
                           f"{r['scan_best_loss_mean']:.3f}"
                           for r in rows if r["sampler"] != "ALL")
               + ")")
    return out


# ------------------------------------------------- sharded-vs-single column
def _shard_rows(quick: bool = True) -> list[dict]:
    """Time the SAME 21-cell uniform-sampler batch fused on one device vs
    shard_map'd over the (8,) cells-axis mesh.  Requires >= 8 devices in the
    CURRENT process — call ``run_shard`` for the subprocess fallback."""
    import jax

    from repro.core.availability import ALL_MODES
    from repro.data.synthetic import make_synthetic
    from repro.fed.models import logistic_regression
    from repro.fed.scan_engine import ScanConfig, ScanEngine

    need = int(np.prod(SHARD_MESH))
    if jax.device_count() < need:
        raise RuntimeError(
            f"shard bench needs {need} devices, have {jax.device_count()}; "
            f"set XLA_FLAGS={_FORCE_FLAG} before jax initializes or call "
            "run_shard() for the subprocess fallback")

    rounds = 30 if quick else 100
    ds = make_synthetic(n_clients=N_CLIENTS, alpha=0.5, beta=0.5, seed=0)
    model = logistic_regression()
    cells_meta = [(m, s) for m in ALL_MODES for s in SEEDS]

    timings = {}
    for label, mesh in (("single", None), ("shard", SHARD_MESH)):
        cfg = ScanConfig(rounds=rounds, m=max(1, N_CLIENTS // 10),
                         local_steps=10, batch_size=10, lr=0.1, eval_every=5,
                         sampler="uniform", mesh=mesh)
        eng = ScanEngine(ds, model, cfg)
        cells = [eng.cell(seed=s, mode=_make_mode(m, ds))
                 for m, s in cells_meta]
        t0 = time.time()
        hists = eng.run_batch(cells)       # includes the one-off compile
        total_s = time.time() - t0
        compile_ms = eng.runtime_stats()["compile_ms"]
        t0 = time.time()
        hists = eng.run_batch(cells)       # steady state
        run_s = time.time() - t0
        timings[label] = (total_s, run_s, compile_ms,
                          float(np.mean([h.best_loss for h in hists])))
        print(f"[engine_bench --shard] {label}: run {run_s:.2f}s "
              f"({compile_ms / 1e3:.1f}s compile)", flush=True)

    (s_tot, s_run, s_cms, s_loss), (p_tot, p_run, p_cms, p_loss) = \
        timings["single"], timings["shard"]
    rows = [{
        "table": "engine_bench_shard",
        "mesh": "x".join(str(d) for d in SHARD_MESH),
        "devices": jax.device_count(), "backend": jax.default_backend(),
        "n_clients": N_CLIENTS, "rounds": rounds, "cells": len(cells_meta),
        "single_run_s": round(s_run, 3), "single_total_s": round(s_tot, 3),
        "single_compile_ms": round(s_cms, 1),
        "shard_run_s": round(p_run, 3), "shard_total_s": round(p_tot, 3),
        "shard_compile_ms": round(p_cms, 1),
        # >1 means the meshed program is slower — expected on forced CPU
        # host devices, where this tracks pure shard_map/collective overhead
        "shard_overhead_x": round(p_run / max(s_run, 1e-9), 2),
        "single_best_loss_mean": round(s_loss, 4),
        "shard_best_loss_mean": round(p_loss, 4),
    }]
    RESULTS.mkdir(parents=True, exist_ok=True)
    (RESULTS / "BENCH_shard.json").write_text(json.dumps(rows, indent=2))
    return rows


def run_shard(quick: bool = True) -> list[dict]:
    """Sharded-vs-single column on 8 forced CPU host devices, always in a
    child process: this process does not touch jax before spawning it, and
    the child is held to the CPU (``JAX_PLATFORMS=cpu``), so it never needs
    a device this process may hold (XLA_FLAGS only takes effect before jax
    initializes)."""
    repo = pathlib.Path(__file__).resolve().parents[1]
    env = dict(os.environ)
    flags = env.get("XLA_FLAGS", "")
    if "host_platform_device_count" not in flags:
        env["XLA_FLAGS"] = (flags + " " + _FORCE_FLAG).strip()
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = os.pathsep.join(
        p for p in (str(repo / "src"), env.get("PYTHONPATH", "")) if p)
    cmd = [sys.executable, "-m", "benchmarks.engine_bench", "--shard"]
    if not quick:
        cmd.append("--full")
    subprocess.run(cmd, check=True, env=env, cwd=str(repo))
    return json.loads((RESULTS / "BENCH_shard.json").read_text())


def summarize_shard(rows) -> list[str]:
    out = ["", "== engine bench: fused single-device vs shard_map'd "
           "run_batch (results/BENCH_shard.json) =="]
    out.append(f"{'mesh':>6s} {'devices':>8s} {'cells':>6s} {'rounds':>7s} "
               f"{'single (s)':>11s} {'shard (s)':>10s} {'overhead':>9s}")
    for r in rows:
        out.append(f"{r['mesh']:>6s} {r['devices']:8d} {r['cells']:6d} "
                   f"{r['rounds']:7d} {r['single_run_s']:11.2f} "
                   f"{r['shard_run_s']:10.2f} {r['shard_overhead_x']:8.2f}x")
        out.append("   (best-loss sanity: single "
                   f"{r['single_best_loss_mean']:.3f} vs shard "
                   f"{r['shard_best_loss_mean']:.3f}; forced host devices "
                   "share one socket, so overhead_x tracks collective cost, "
                   "not scale-out)")
    return out


if __name__ == "__main__":
    import argparse
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--shard", action="store_true",
                    help="sharded-vs-single column (forces 8 CPU host "
                         "devices; must be set before jax initializes, which "
                         "is why repro imports are function-local)")
    ap.add_argument("--full", action="store_true", help="100 rounds, not 30")
    a = ap.parse_args()
    if a.shard:
        _flags = os.environ.get("XLA_FLAGS", "")
        if "host_platform_device_count" not in _flags:
            os.environ["XLA_FLAGS"] = (_flags + " " + _FORCE_FLAG).strip()
        for line in summarize_shard(_shard_rows(quick=not a.full)):
            print(line)
    else:
        for line in summarize(run(quick=not a.full)):
            print(line)
