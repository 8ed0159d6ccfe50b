"""Roofline report over the KERNEL bench records (DESIGN.md §14).

Reads ``results/BENCH_kernels.json`` (written by ``kernel_bench.py`` — the
``kernels`` section runs before this one in ``benchmarks/run.py``) and
places every kernel x tier row on the platform roofline: achieved GFLOP/s
and GB/s from the measured wall-clock plus the analytic FLOP/bytes models,
classified compute- vs memory-bound by arithmetic intensity against the
platform ridge point (AI* = peak_flops / peak_bw).

This replaced the dormant LM dry-run table: the repo's hot kernels are the
FedGS graph/solver/aggregator Pallas kernels, so the roofline now tracks
the records that ``perf_assert.py`` gates on.  The boundness
classification comes from the MODEL (AI vs ridge); the achieved fractions
come from the record's wall-clock, so only a compiled chip record gets a
roofline (interpret wall-clock measures the Pallas interpreter).

Peaks come from the one table keyed by ``device_kind``
(``repro/utils/peaks.py``, with its source); a chip kind that is not in
the table raises.  A record taken on the CPU is not a chip measurement and
gets no roofline.
"""
from __future__ import annotations

import json
import pathlib

from repro.utils.peaks import chip_peaks

BENCH = pathlib.Path(__file__).resolve().parent / "results" / "BENCH_kernels.json"


def load() -> dict | None:
    if not BENCH.exists():
        return None
    return json.loads(BENCH.read_text())


def run(quick: bool = True) -> list[dict]:
    rec = load()
    if rec is None or rec.get("backend") == "cpu":
        return []
    peaks = chip_peaks(rec["device_kind"])
    peak_f = peaks["flops_bf16"] / 1e9           # GFLOP/s
    peak_b = peaks["hbm_bytes_s"] / 1e9          # GB/s
    ridge = peak_f / peak_b
    out = []
    for r in rec["rows"]:
        out.append({
            "table": "roofline", "kernel": r["kernel"], "tier": r["tier"],
            "ai": r["ai"],
            "gflops": r["gflops"], "gbps": r["gbps"],
            "frac_peak_flops": round(r["gflops"] / peak_f, 4),
            "frac_peak_bw": round(r["gbps"] / peak_b, 4),
            "bound": "compute" if r["ai"] >= ridge else "memory",
            "backend_mode": r["backend_mode"],
            "ridge_ai": round(ridge, 2),
        })
    return out


def summarize(rows) -> list[str]:
    if not rows:
        return ["", "== Roofline (kernel records) ==",
                "  no chip record in results/BENCH_kernels.json — run the "
                "'kernels' section on a TPU first"]
    mode = rows[0]["backend_mode"]
    ridge = rows[0]["ridge_ai"]
    out = ["", f"== Roofline over BENCH_kernels.json (ridge AI* = {ridge}; "
               f"{mode} mode"
               + ("; achieved fractions are interpreter-bound, model "
                  "classification only)" if mode == "interpret" else ")")
           + " =="]
    out.append(f"{'kernel':18s} {'tier':16s} {'AI':>8s} {'GFLOP/s':>9s} "
               f"{'GB/s':>8s} {'%peakF':>7s} {'%peakB':>7s} {'bound':>8s}")
    for r in rows:
        out.append(f"{r['kernel']:18s} {r['tier']:16s} {r['ai']:8.2f} "
                   f"{r['gflops']:9.2f} {r['gbps']:8.2f} "
                   f"{100 * r['frac_peak_flops']:6.2f}% "
                   f"{100 * r['frac_peak_bw']:6.2f}% {r['bound']:>8s}")
    return out


if __name__ == "__main__":
    for line in summarize(run()):
        print(line)
