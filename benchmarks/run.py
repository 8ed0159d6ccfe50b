"""Benchmark orchestrator — one section per paper table/figure, plus the
framework-scale extras (solver scaling, kernel micro-bench, roofline report).

  PYTHONPATH=src python -m benchmarks.run             # quick (CPU-budget) pass
  PYTHONPATH=src python -m benchmarks.run --quick     # same, explicit — one
                                                      # pass regenerates EVERY
                                                      # checked-in BENCH_*.json
  PYTHONPATH=src python -m benchmarks.run --full      # paper-scale settings
  PYTHONPATH=src python -m benchmarks.run --only table2,roofline

The quick pass rewrites all BENCH_*.json artifacts (availability, aggregator,
kernels, graph, sampler, shard) — commit them so the perf trajectory and the
CI perf gate (``benchmarks/perf_assert.py``) track the repo, not a laptop.
"""
from __future__ import annotations

import argparse
import csv
import io
import json
import pathlib
import time

RESULTS = pathlib.Path(__file__).resolve().parent / "results"

SECTIONS = ["table2", "fig4", "table3", "table4", "dynamic", "scaling",
            "engine", "shard", "runtime", "telemetry", "availability",
            "aggregator", "robustness", "kernels", "graph", "roofline",
            "variants"]


def _section(name: str, quick: bool):
    if name == "shard":
        # sharded-vs-single run_batch: run_shard runs it in a CPU-only
        # child with 8 forced host devices (XLA_FLAGS only takes effect
        # before jax initializes)
        from benchmarks import engine_bench as m
        rows = m.run_shard(quick=quick)
        return rows, m.summarize_shard(rows)
    if name == "table2":
        from benchmarks import table2_availability as m
    elif name == "fig4":
        from benchmarks import fig4_fairness as m
    elif name == "table3":
        from benchmarks import table3_graph as m
    elif name == "table4":
        from benchmarks import table4_constructed as m
    elif name == "dynamic":
        from benchmarks import ablation_dynamic as m
    elif name == "scaling":
        from benchmarks import sampler_scaling as m
    elif name == "engine":
        from benchmarks import engine_bench as m
    elif name == "runtime":
        from benchmarks import runtime_bench as m
    elif name == "telemetry":
        from benchmarks import telemetry_bench as m
    elif name == "availability":
        from benchmarks import availability_bench as m
    elif name == "aggregator":
        from benchmarks import aggregator_bench as m
    elif name == "robustness":
        from benchmarks import robustness_bench as m
    elif name == "kernels":
        from benchmarks import kernel_bench as m
    elif name == "graph":
        from benchmarks import graph_pipeline_bench as m
    elif name == "roofline":
        from benchmarks import roofline as m
    elif name == "variants":
        from benchmarks import variants_report as m
    else:
        raise ValueError(name)
    rows = m.run(quick=quick)
    return rows, m.summarize(rows)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true",
                    help="paper-scale rounds/clients (hours on CPU)")
    ap.add_argument("--quick", action="store_true",
                    help="explicit quick pass (the default): regenerates "
                         "every BENCH_*.json artifact for commit")
    ap.add_argument("--only", default=None,
                    help="comma-separated subset of sections")
    args = ap.parse_args(argv)
    if args.quick and args.full:
        ap.error("--quick and --full are mutually exclusive")
    quick = not args.full
    sections = args.only.split(",") if args.only else SECTIONS
    # the runtime section spawns compile children that need the device:
    # run it while this process has not touched one yet
    sections = sorted(sections, key=lambda s: s != "runtime")

    RESULTS.mkdir(parents=True, exist_ok=True)
    all_rows = []
    for name in sections:
        t0 = time.time()
        print(f"\n######## {name} ########", flush=True)
        rows, summary = _section(name, quick)
        all_rows.extend(rows)
        for line in summary:
            print(line, flush=True)
        print(f"[{name}: {time.time() - t0:.1f}s]", flush=True)

    # machine-readable dump: one CSV per table
    by_table: dict[str, list] = {}
    for r in all_rows:
        by_table.setdefault(r.get("table", "misc"), []).append(r)
    for table, rows in by_table.items():
        keys = sorted({k for r in rows for k in r if k not in
                       ("counts", "loss_curve", "curve_rounds")})
        buf = io.StringIO()
        w = csv.DictWriter(buf, fieldnames=keys, extrasaction="ignore")
        w.writeheader()
        for r in rows:
            w.writerow(r)
        (RESULTS / f"{table}.csv").write_text(buf.getvalue())
    (RESULTS / "all_rows.json").write_text(json.dumps(all_rows, indent=1, default=str))
    print(f"\nwrote {len(all_rows)} rows across {len(by_table)} tables to {RESULTS}")


if __name__ == "__main__":
    from repro.fed.runtime import enable_compile_cache
    enable_compile_cache()
    main()
