"""Telemetry-layer bench (DESIGN.md §17): in-scan health-channel overhead
and bitwise noninterference, JSONL sink throughput.

Three claims, one ``results/BENCH_telemetry.json`` artifact (the CI perf
gate ``benchmarks/perf_assert.py`` enforces the first two):

overhead
    A mixed scenario x sampler x aggregator x fault ``run_batch`` with
    ``ScanConfig.telemetry=True`` vs the identical batch with telemetry
    off, steady state (second call — first call pays the compiles), best
    of 3 to absorb CPU-runner jitter.  Acceptance: <= 5% overhead — the
    metrics are pure in-scan reductions riding the trajectory transfer,
    not a second pass.

bitwise noninterference (assumption log #24)
    The telemetry-on run's ``ScanHistory`` fields AND its checkpoint
    bytes must be IDENTICAL to the telemetry-off run's — the health
    channel is output-only (no carry state, stripped before checkpoint).

sink throughput
    ``JSONLMetricsSink`` events/s and MB/s for round-sized payloads —
    the background-writer pattern must absorb per-round emission at far
    above engine round rates.

Artifacts for eyeballing land in ``results/telemetry/``: the run's
``metrics.jsonl`` and one ``jax.profiler`` trace under ``trace/`` (host
spans and device ops on one clock; its ``perfetto_trace.json.gz`` loads
in ui.perfetto.dev).

  PYTHONPATH=src python -m benchmarks.telemetry_bench
"""
from __future__ import annotations

import argparse
import hashlib
import json
import os
import pathlib
import shutil
import time

import numpy as np

RESULTS = pathlib.Path(__file__).resolve().parent / "results"
N_CLIENTS = 50
B_CELLS = 8


def _mk(rounds, telemetry: bool, **kw):
    """(engine, cells): the runtime_bench mixed-cell shape plus sampler
    variety and a fault cell, so every telemetry source is live —
    memory-panel staleness, fault corruption, FedGS dispersion."""
    from repro.core.availability_device import make_process
    from repro.core.sampler_device import make_sampler_process
    from repro.data.synthetic import make_synthetic
    from repro.fed.aggregator_device import make_aggregator_process
    from repro.fed.faults_device import make_fault_process
    from repro.fed.models import logistic_regression
    from repro.fed.scan_engine import ScanConfig, ScanEngine, oracle_h

    ds = make_synthetic(n_clients=N_CLIENTS, alpha=0.5, beta=0.5, seed=0)
    cfg = ScanConfig(rounds=rounds, m=5, local_steps=5, batch_size=8,
                     eval_every=5, sampler="uniform", aggregator="memory",
                     telemetry=telemetry, **kw)
    eng = ScanEngine(ds, logistic_regression(), cfg)
    h = oracle_h(ds.opt_params)
    scen = ("GE", "CLUSTER", "DRIFT", "DEADLINE")
    aggs = ("memory", "fedavgm", "fedadam", "fedavg")
    samplers = ("fedgs", "uniform", "md", "poc")
    cells = [eng.cell(
        seed=i, avail_seed=40 + i, h=h,
        process=make_process(scen[i % 4], n_clients=ds.n_clients,
                             data_sizes=ds.sizes, label_sets=ds.label_sets(),
                             num_labels=ds.num_classes, rounds=rounds,
                             seed=9 + i),
        sampler_process=make_sampler_process(samplers[i % 4], alpha=1.0),
        aggregator_process=make_aggregator_process(aggs[i % 4]),
        fault_process=make_fault_process("sign_flip", ds.n_clients,
                                         frac=0.2) if i == 3 else None)
        for i in range(B_CELLS)]
    return eng, cells


def _steady(eng, cells, reps: int = 3, **kw):
    """Best-of-``reps`` second-call wall-clock (first call compiles)."""
    hists = eng.run_batch(cells, **kw)
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        hists = eng.run_batch(cells, **kw)
        best = min(best, time.perf_counter() - t0)
    return best, hists


def _md5(path: str) -> str:
    with open(path, "rb") as f:
        return hashlib.md5(f.read()).hexdigest()


def _history_bitwise(ha, hb) -> bool:
    ok = True
    for f in ("val_loss", "val_acc", "count_var", "gini", "sel", "valid",
              "counts"):
        ok &= bool(np.array_equal(np.asarray(getattr(ha, f)),
                                  np.asarray(getattr(hb, f)),
                                  equal_nan=True))
    return ok


def _sink_throughput(n_events: int = 20_000) -> tuple[float, float]:
    """(events/s, MB/s) for round-shaped JSONL payloads."""
    import tempfile

    from repro.obs import JSONLMetricsSink
    payload = {"cell": 3, "t": 17, "val_loss": 0.123, "val_acc": 0.9,
               "metrics": {"update_norm_mean": 0.5, "avail_rate": 0.8,
                           "staleness_hist": list(range(8))}}
    with tempfile.TemporaryDirectory() as td:
        path = os.path.join(td, "m.jsonl")
        t0 = time.perf_counter()
        with JSONLMetricsSink(path, run="bench") as sink:
            for _ in range(n_events):
                sink.emit("round", payload)
            sink.flush()
            wall = time.perf_counter() - t0
            nbytes = sink.stats()["bytes"]
    return n_events / max(wall, 1e-9), nbytes / 1e6 / max(wall, 1e-9)


def run(quick: bool = True) -> list[dict]:
    import tempfile

    import jax

    from benchmarks.common import pallas_backend_mode
    from repro.launch.obs_cli import (
        finish_observability, make_observability,
    )
    from repro.obs import read_metrics_jsonl

    rounds = 40 if quick else 120
    seg = 8

    # ------------- steady state: telemetry off vs on (fused program) ------
    eng_off, cells_off = _mk(rounds, telemetry=False)
    off_s, off_h = _steady(eng_off, cells_off)
    eng_on, cells_on = _mk(rounds, telemetry=True)
    on_s, on_h = _steady(eng_on, cells_on)
    overhead_pct = (on_s / max(off_s, 1e-9) - 1.0) * 100.0
    print(f"[telemetry_bench] steady: off {off_s:.2f}s, on {on_s:.2f}s "
          f"({overhead_pct:+.1f}%)", flush=True)

    # ------------- bitwise noninterference incl. checkpoints --------------
    hist_ok = all(_history_bitwise(a, b) for a, b in zip(off_h, on_h))
    with tempfile.TemporaryDirectory() as td:
        ck_off, ck_on = os.path.join(td, "off"), os.path.join(td, "on")
        eng_off.run_batch(cells_off, ckpt_path=ck_off, ckpt_every=seg)
        eng_on.run_batch(cells_on, ckpt_path=ck_on, ckpt_every=seg)
        ckpt_ok = _md5(ck_off + ".npz") == _md5(ck_on + ".npz")
    bitwise = bool(hist_ok and ckpt_ok)
    print(f"[telemetry_bench] bitwise: history={hist_ok} ckpt={ckpt_ok}",
          flush=True)

    # ------------- sink throughput ----------------------------------------
    ev_s, mb_s = _sink_throughput(5_000 if quick else 50_000)
    print(f"[telemetry_bench] sink: {ev_s:,.0f} events/s, {mb_s:.1f} MB/s",
          flush=True)

    # ------------- artifacts: metrics.jsonl + profiler trace -------------
    # the launch CLIs' --trace-dir / --metrics-jsonl path: one jax.profiler
    # trace holds the engine's host spans and the device's ops
    art = RESULTS / "telemetry"
    art.mkdir(parents=True, exist_ok=True)
    mpath = art / "metrics.jsonl"
    if mpath.exists():
        mpath.unlink()
    shutil.rmtree(art / "trace", ignore_errors=True)
    obs = argparse.Namespace(trace_dir=str(art / "trace"),
                             metrics_jsonl=str(mpath))
    tracer, sink = make_observability(obs, run="telemetry_bench")
    try:
        eng_art, cells_art = _mk(rounds, telemetry=True)
        eng_art.tracer, eng_art.sink = tracer, sink
        eng_art.run_batch(cells_art, ckpt_every=seg)
    finally:
        finish_observability(sink, obs)
    n_round_events = len(read_metrics_jsonl(str(mpath), kind="round"))
    spans = {k: v["count"] for k, v in tracer.summary().items()}

    row = {
        "table": "telemetry_bench", "backend": jax.default_backend(),
        "backend_mode": pallas_backend_mode(),
        "n_clients": N_CLIENTS, "cells": B_CELLS, "rounds": rounds,
        "telemetry_off_s": round(off_s, 3),
        "telemetry_on_s": round(on_s, 3),
        "overhead_pct": round(overhead_pct, 2),
        "bitwise_noninterference": bitwise,
        "jsonl_events_per_s": round(ev_s, 1),
        "jsonl_mb_per_s": round(mb_s, 2),
        "round_events_streamed": n_round_events,
        "spans": spans,
    }
    RESULTS.mkdir(parents=True, exist_ok=True)
    (RESULTS / "BENCH_telemetry.json").write_text(json.dumps([row], indent=2))
    return [row]


def summarize(rows) -> list[str]:
    out = ["", "== telemetry bench: in-scan health channel overhead + "
           "sink throughput (results/BENCH_telemetry.json) =="]
    for r in rows:
        out.append(f"  steady     : off {r['telemetry_off_s']:.2f}s, on "
                   f"{r['telemetry_on_s']:.2f}s "
                   f"({r['overhead_pct']:+.1f}% overhead, gate <= 5%)")
        out.append(f"  bitwise    : history + checkpoints identical "
                   f"on-vs-off: {r['bitwise_noninterference']}")
        out.append(f"  sink       : {r['jsonl_events_per_s']:,.0f} "
                   f"events/s ({r['jsonl_mb_per_s']:.1f} MB/s JSONL)")
        out.append(f"  artifacts  : {r['round_events_streamed']} round "
                   f"events -> results/telemetry/metrics.jsonl, spans "
                   f"{r['spans']} -> results/telemetry/trace/")
    return out


if __name__ == "__main__":
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--full", action="store_true")
    a = ap.parse_args()
    for line in summarize(run(quick=not a.full)):
        print(line)
