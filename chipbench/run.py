"""Run one cell of the chip benchmark once.

  python chipbench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell is an entry of ``workloads`` in ``BENCHMARK.json``; its
configuration (``chipbench/configs/<config>.json``), traffic mix
(``chipbench/traffic/<traffic>.json``), limits
(``chipbench/limits/<workload>.json``), reference
(``chipbench/references/<reference>.py``) and per-layer metrics
(``chipbench/metrics/<metric>.py``) are found by name.

Set-up builds the dataset from the configuration's fixed seed, the engine
and the first sweep's cells from ``--seed``, and runs the cell's compiled
init and segment programs once (the warm-up, whose output the check keeps).
The window then runs sweeps back to back through
``ScanEngine.run_batch_stream`` for ``--seconds``: build the sweep's cells,
``init_carry``, every round in segments, each trajectory fetched to the
host.  With ``--trace 1`` a further stretch of whole segments of a new
sweep runs under the profiler.  Once the window has closed and the
program's state is freed, the plain reference follows the warm-up's
segment and ``correct`` is decided.

Exits non-zero, and prints no result, when JAX finds no TPU or fewer chips
than the cell asks for, or when the benchmark's files are not there.  The
last line of standard output is the result as JSON.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from contextlib import contextmanager  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TRACE_SEGMENTS = 6          # segments delivered under the profiler
# host spans that name an idle gap of the device: the benchmark's own and
# those the engine opens through its tracer hook
SPAN_NAMES = ("cell_build", "fetch_segment", "init_carry", "program_get",
              "dispatch_segment", "device_get", "metrics_emit")


class Refused(Exception):
    """The run cannot be made here; nothing is reported."""


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


# ------------------------------------------------------------ the files
def load_module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_cell(root: str, workload: str) -> dict:
    """Everything one cell names, found by name under ``root``."""
    try:
        with open(os.path.join(root, "BENCHMARK.json")) as f:
            bench = json.load(f)
    except FileNotFoundError as e:
        raise Refused(f"no BENCHMARK.json in {root}") from e
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise Refused(f"no workload {workload!r} in BENCHMARK.json")
    w = cells[workload]
    conf = {c["name"]: c for c in bench["configs"]}[w["config"]]
    with open(os.path.join(root, conf["file"])) as f:
        cfg = json.load(f)
    with open(os.path.join(root, "chipbench", "traffic",
                           f"{w['traffic']}.json")) as f:
        traffic = json.load(f)
    e2e = [m for m in bench["end_to_end"]
           if workload in m.get("workloads", [workload])]
    layer = [m for m in bench["per_layer"]
             if workload in m.get("workloads", [workload])]
    return {"workload": w, "cfg": cfg, "traffic": traffic,
            "end_to_end": e2e, "per_layer": layer}


def metric_reader(root: str, name: str):
    return load_module(os.path.join(root, "chipbench", "metrics",
                                    f"{name}.py"), f"chipbench_metric_{name}")


def reference_module(root: str, name: str):
    return load_module(os.path.join(root, "chipbench", "references",
                                    f"{name}.py"), f"chipbench_ref_{name}")


# ------------------------------------------------------------ host spans
class Spans:
    """Host spans of the benchmark's own calls, and the engine's tracer
    hook: each span is timed on the host clock and, while ``annotate`` is
    set, entered as a ``jax.profiler.TraceAnnotation`` so that it lands on
    the profiler's clock too.  The engine's ``dispatch_segment`` spans give
    each segment's dispatch time."""

    enabled = False          # the engine's runtime_stats reads no summary

    def __init__(self):
        self.annotate = False
        self.dispatch: dict = {}

    @contextmanager
    def span(self, name: str, **attrs):
        if name == "dispatch_segment":
            self.dispatch[int(attrs["t0"])] = time.perf_counter()
        if self.annotate:
            import jax
            with jax.profiler.TraceAnnotation(name):
                yield
        else:
            yield


# ---------------------------------------------------------------- window
def run_sweeps(eng, build, seg: int, seconds: float, spans: Spans,
               n_cells: int, trace_dir=None) -> dict:
    """Sweeps back to back until ``seconds`` have passed; the first
    segment delivered after that closes the window.  With ``trace_dir``,
    ``TRACE_SEGMENTS`` further whole segments then run under the profiler:
    the rest of the open sweep if it has that many left, else a new
    sweep's, after its first (which carries the sweep's init)."""
    import jax
    import numpy as np
    rounds = eng.cfg.rounds
    lat, cell_rounds, segments, sweeps, failed = [], 0, 0, 0, 0
    arrivals = []           # (host time, first round) of each delivery
    t_start = time.perf_counter()
    deadline = t_start + seconds
    t_close = None
    traced = None           # {"left": segments still to trace} once on
    sweep = 0
    n_trace = min(TRACE_SEGMENTS, rounds // seg - 1)
    while traced is None or traced["left"] > 0:
        with spans.span("cell_build"):
            cells = build(sweep)
        spans.dispatch = {}
        stream = eng.run_batch_stream(cells, ckpt_every=seg)
        try:
            for t0, k, traj in stream:
                now = time.perf_counter()
                if t_close is None:
                    arrivals.append((now, t0))
                    lat.append(now - spans.dispatch[t0])
                    cell_rounds += n_cells * k
                    segments += 1
                    vl = np.asarray(traj["val_loss"])[:n_cells]
                    failed += int(not np.all(np.isfinite(
                        vl[~np.isnan(vl)])))
                    sweeps += int(t0 + k == rounds)
                    if now < deadline:
                        continue
                    t_close = now
                    if trace_dir is None:
                        return {"window_s": t_close - t_start,
                                "cell_rounds": cell_rounds,
                                "segments": segments, "sweeps": sweeps,
                                "latencies": lat, "failed": failed,
                                "arrivals": arrivals}
                if traced is None:
                    if rounds - (t0 + k) < n_trace * seg:
                        break               # too few left: a new sweep
                    spans.annotate = True
                    opts = jax.profiler.ProfileOptions()
                    opts.python_tracer_level = 0
                    jax.profiler.start_trace(trace_dir,
                                             profiler_options=opts)
                    traced = {"left": n_trace,
                              "cm": jax.profiler.TraceAnnotation(
                                  "traced_stretch")}
                    traced["cm"].__enter__()
                    continue
                traced["left"] -= 1
                if traced["left"] == 0:
                    break
        finally:
            stream.close()
            if traced is not None and "cm" in traced and (
                    traced["left"] == 0 or sys.exc_info()[0] is not None):
                traced.pop("cm").__exit__(None, None, None)
                jax.profiler.stop_trace()
                spans.annotate = False
        sweep += 1
    return {"window_s": t_close - t_start, "cell_rounds": cell_rounds,
            "segments": segments, "sweeps": sweeps, "latencies": lat,
            "failed": failed, "arrivals": arrivals}


def first_segment(eng, cells, seg: int, dynamic: bool) -> list:
    """Drive the cell's init and segment programs (those the window's
    ``run_batch_stream`` calls) through rounds 0..seg-1 and keep, per cell,
    what the check compares: the parameters before and after, the
    segment's trajectory and, for the dynamic 3DG, H after it."""
    import jax
    handle = eng.init_carry(cells)
    start = jax.device_get(handle.tree["agg"]["prev"])
    handle, traj = eng.run_segment(cells, handle, 0, seg)
    traj = jax.device_get(traj)
    end = jax.device_get({"params": handle.tree["agg"]["prev"],
                          "h": handle.tree["h"] if dynamic else None})
    return [{"params0": {k: v[i] for k, v in start.items()},
             "params": {k: v[i] for k, v in end["params"].items()},
             "h": end["h"][i] if dynamic else None,
             "val_loss": {t: float(traj["val_loss"][i][t])
                          for t in range(seg)},
             "sets": (traj["sel"][i], traj["valid"][i])}
            for i in range(len(cells))]


def p90(values) -> float:
    """90th percentile, linear between order statistics."""
    import numpy as np
    return float(np.percentile(np.asarray(values, np.float64), 90))


# ----------------------------------------------------------------- main
def run(argv=None, *, root: str = ROOT, require_tpu: bool = True) -> dict:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    cell = load_cell(root, args.workload)
    w, cfg, traffic = cell["workload"], cell["cfg"], cell["traffic"]
    for p in (os.path.join(root, "src"), root):
        if p not in sys.path:
            sys.path.insert(0, p)
    if not os.path.isdir(os.path.join(root, "src", "repro")):
        raise Refused(f"the program (src/repro) is not in {root}")

    import jax
    devs = jax.devices()
    dev = devs[0]
    if require_tpu and dev.platform != "tpu":
        raise Refused(f"JAX finds no TPU (platform {dev.platform!r})")
    if len(devs) < w["chips"]:
        raise Refused(f"the cell asks for {w['chips']} chips, JAX finds "
                      f"{len(devs)}")
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(root, ".jax_cache")
    import numpy as np

    from repro.fed.runtime import enable_compile_cache
    from repro.fed.scan_engine import ScanEngine

    from chipbench import check, flops
    from chipbench.data import make_dataset
    from chipbench.generator import (
        cell_specs, engine_cells, engine_config, fed_dataset, fed_model,
        mode_tables,
    )
    from chipbench.peaks import chip_peaks
    cache_dir = enable_compile_cache()
    limits = check.load_limits(root, w["name"])
    refmod = reference_module(root, cfg["reference"])
    peaks = chip_peaks(dev.device_kind) if require_tpu else None
    t_import = time.perf_counter()

    # -------------------------------------------------------- set-up
    ds = make_dataset(cfg)
    tables = mode_tables(traffic, ds)
    g = traffic["graph"]
    dynamic = g["kind"] == "dynamic"
    h_static = None
    if g["kind"] == "oracle_static":
        h_static = np.asarray(refmod.build_h(
            jax.numpy.asarray(ds.label_dist, jax.numpy.float32),
            functional=False, eps=g["eps"], sigma2=g["sigma2"]))
    t_data = time.perf_counter()

    spans = Spans()
    ecfg = engine_config(cfg, traffic)
    eng = ScanEngine(fed_dataset(ds), fed_model(root, cfg, ds), ecfg,
                     use_masks=traffic["availability"]["kind"] == "host_masks",
                     tracer=spans)
    seg = int(traffic["segment_rounds"])

    def specs_of(sweep):
        return cell_specs(cfg, traffic, ds, args.seed, sweep, tables)

    def build(sweep):
        return engine_cells(eng, traffic, specs_of(sweep), h_static)

    specs0 = specs_of(0)
    n_cells = len(specs0)
    first = first_segment(eng, engine_cells(eng, traffic, specs0, h_static),
                          seg, dynamic)
    stats = eng.runtime_stats()
    t_setup = time.perf_counter()
    setup_s = t_setup - T_START
    log(f"set-up {setup_s:.4f} s: import {t_import - T_START:.4f}, data "
        f"{t_data - t_import:.4f}, engine + warm-up "
        f"{t_setup - t_data:.4f} (of which compile or cache load "
        f"{stats['compile_ms'] / 1e3:.4f} s over {stats['compiles']} "
        f"programs); compile cache {cache_dir}")

    # -------------------------------------------------------- window
    compiles0 = stats["compiles"]
    trace_dir = os.path.join(root, ".chipbench_trace")
    shutil.rmtree(trace_dir, ignore_errors=True)
    win = run_sweeps(eng, build, seg, args.seconds, spans, n_cells,
                     trace_dir=trace_dir if args.trace else None)
    compiles_in_window = eng.runtime_stats()["compiles"] - compiles0
    rate = win["cell_rounds"] / win["window_s"]
    log(f"window {win['window_s']:.4f} s: {win['segments']} segments, "
        f"{win['cell_rounds']} cell-rounds, {win['sweeps']} whole sweeps, "
        f"{compiles_in_window} compiles; {rate:.4f} cell-rounds/s, "
        f"segment p90 {1e3 * p90(win['latencies']):.4f} ms, median "
        f"{1e3 * float(np.median(win['latencies'])):.4f} ms")
    gaps = [(b[0] - a[0], b[1]) for a, b in zip(win["arrivals"],
                                                 win["arrivals"][1:])]
    if gaps:
        worst = max(gaps)
        med = float(np.median([g for g, _ in gaps]))
        log(f"deliveries: median gap {1e3 * med:.4f} ms, longest "
            f"{1e3 * worst[0]:.4f} ms (before the segment from round "
            f"{worst[1]}), longest latency "
            f"{1e3 * max(win['latencies']):.4f} ms")
    if compiles_in_window:
        log("warning: the window compiled a program")
    jax.effects_barrier()
    mem_peak = (dev.memory_stats() or {}).get("peak_bytes_in_use", 0) \
        if require_tpu else 0

    # ------------------------------------------------- per-layer metrics
    metrics, breakdown, device_extra = {}, None, {}
    if args.trace:
        from chipbench import trace as trmod
        tr = trmod.load(trace_dir)
        shutil.rmtree(trace_dir, ignore_errors=True)
        st = trmod.stretch(tr, "traced_stretch")
        ctx = {"ops": st["ops"], "busy_s": st["busy_s"],
               "window_s": st["window_s"], "segments": st["segments"],
               "seg": seg, "cells": n_cells, "cfg": cfg, "traffic": traffic,
               "peaks": peaks, "chips": w["chips"], "flops": flops,
               "n_val": len(ds.y_val)}
        for m in cell["per_layer"]:
            v = metric_reader(root, m["name"]).read(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
        log(f"traced stretch {st['window_s']:.4f} s over {st['segments']} "
            f"whole segments, device busy {st['busy_s']:.4f} s")
        device_extra = {"busy_s": st["busy_s"], "window_s": st["window_s"]}
        breakdown = {"device_ops": trmod.op_totals(st["ops"]),
                     "idle_gaps": trmod.idle_gaps(
                         st["ops"], st["a"], st["b"], tr["host"],
                         SPAN_NAMES)}
    else:
        values = {"cell_rounds_per_s": rate,
                  "segment_p90_ms": 1e3 * p90(win["latencies"]),
                  "setup_s": setup_s}
        for m in cell["end_to_end"]:
            metrics[m["name"]] = {"value": float(values[m["name"]]),
                                  "unit": m["unit"]}

    # ------------------------------------------------------------ check
    del eng, build
    gc.collect()
    jax.clear_caches()
    t_ref = time.perf_counter()
    ref = refmod.Reference(cfg, traffic, ds)
    numbers = check.compare(first, [
        ref.replay(sp, seg, sets=p["sets"], h_static=h_static)
        for sp, p in zip(specs0, first)], dynamic)
    del ref
    correct, rows = check.verdict(numbers, limits)
    log(f"reference check {time.perf_counter() - t_ref:.4f} s over "
        f"{n_cells} cells x {seg} rounds")
    for k, v in numbers.items():
        if k not in limits:
            log(f"check (reported, not compared): {k} {v!r}")
    log(f"correct: {correct}")
    for k, v, lim in rows:
        log(f"check: {k} {v!r} limit {lim!r}")

    result = {"correct": bool(correct), "attempted": int(win["segments"]),
              "failed": int(win["failed"]), "metrics": metrics,
              "device": {"platform": dev.platform, "kind": dev.device_kind,
                         "count": len(devs), "memory_peak_bytes": int(
                             mem_peak), **device_extra}}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["check"] = {k: {"value": v, "limit": lim} for k, v, lim in rows}
    return result


def main(argv=None) -> int:
    try:
        result = run(argv)
    except Refused as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return 2
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
