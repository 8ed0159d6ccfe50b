"""Kernel micro-bench with roofline instrumentation (DESIGN.md §14).

One row per kernel x shape tier: wall-clock (pallas vs pure-jnp ref), the
analytic FLOP and bytes-moved models next to it, and the derived
arithmetic intensity / achieved GFLOP/s / achieved GB/s that
``benchmarks/roofline.py`` plots against the platform ceilings.  Tiles
resolve through the autotuner table (``tile="auto"``), so the pallas
column times exactly what ships.

Cost models (per-kernel, algorithmic — documented in DESIGN.md §14):

  fused_3dg        flops = 4 N^2 d + 8 N^2        (two matmul phases + epilogue)
                   bytes = 4 (N d + N^2)          (stream U once, write R once)
  floyd_warshall   flops = 2 N^3                  (min-plus inner product)
                   bytes = 8 nb N^2               (read+write every tile per
                                                   pivot round, nb = N/tile)
  fedgs_select     flops ~= 6 S m N + 4 m N       (S sweeps of (m, N) swap
                   bytes ~= 4 (2 S m N + 2 m N)    gains + greedy row math)
  memory_aggregate flops = 2 N P                  (staleness reduction)
                   bytes = 4 (2 N P + m P + N)    (panel round-trip + updates)
  window_attention flops = 4 B H S W D            (qk + av, W-window)
                   bytes = 16 B S H D             (q, k, v, out)

``backend_mode`` is recorded per row (interpret on this CPU container,
compiled on a real accelerator): interpret timings are correctness-grade
only — the interpreter re-writes carried output buffers every grid step —
so the perf-gate (``benchmarks/perf_assert.py``) only enforces the
compiled-mode winners, plus ``fedgs_select`` which wins even under
interpret because the Q-free factorization beats the ref's (N, N) Q
materialization on algorithm, not codegen.

Dumped to ``benchmarks/results/BENCH_kernels.json``.

  PYTHONPATH=src python -m benchmarks.kernel_bench [--quick|--full]
"""
from __future__ import annotations

import json
import pathlib
import time

import numpy as np

import jax
import jax.numpy as jnp

RESULTS = pathlib.Path(__file__).resolve().parent / "results"
BENCH_PATH = RESULTS / "BENCH_kernels.json"

# production tier: the paper-scale client counts start here (ROADMAP.md)
PRODUCTION_N = 1024


def _time_ms(fn, reps=2):
    out = fn()
    jax.block_until_ready(out)
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        out = fn()
        jax.block_until_ready(out)
        best = min(best, time.perf_counter() - t0)
    return best * 1e3, out


def _row(kernel, dims, ref_ms, pallas_ms, max_err, flops, bytes_moved, mode,
         tiles=None):
    n = dims.get("n", 0)
    production = n >= PRODUCTION_N
    # fedgs_select's win is algorithmic (Q-free vs (N, N) Q build), so it is
    # expected to win under the interpreter too; the pure-codegen kernels
    # only beat fused jnp/XLA once Mosaic-compiled
    winner_expected = production and (kernel == "fedgs_select"
                                      or mode == "compiled")
    ai = flops / bytes_moved if bytes_moved else 0.0
    sec = pallas_ms / 1e3
    return {
        "table": "kernels", "kernel": kernel, **dims,
        "tier": ",".join(f"{k}{v}" for k, v in sorted(dims.items())),
        "tiles": tiles or {},
        "ref_ms": round(ref_ms, 3), "pallas_ms": round(pallas_ms, 3),
        "speedup": round(ref_ms / pallas_ms, 3) if pallas_ms else 0.0,
        "max_err": float(max_err),
        "flops": int(flops), "bytes_moved": int(bytes_moved),
        "ai": round(ai, 3),
        "gflops": round(flops / sec / 1e9, 3) if sec else 0.0,
        "gbps": round(bytes_moved / sec / 1e9, 3) if sec else 0.0,
        "backend_mode": mode,
        "production_tier": production,
        "winner_expected": winner_expected,
    }


def _err(a, b):
    a, b = np.asarray(a), np.asarray(b)
    fin = np.isfinite(a) & np.isfinite(b)
    if not bool(np.all(np.isfinite(a) == np.isfinite(b))):
        return float("inf")
    return float(np.max(np.abs(a[fin] - b[fin]))) if fin.any() else 0.0


def _fused_rows(ns, mode, rng):
    from repro.core.graph_device import minmax01, to_adjacency
    from repro.kernels import ops
    from repro.kernels.autotune import resolve
    rows = []
    d = 16
    for n in ns:
        u = jnp.asarray(rng.standard_normal((n, d)).astype(np.float32))
        tiles = resolve("fused_3dg", {"tile": 128}, n=n)

        def _ref(u=u):
            v = u @ u.T
            return to_adjacency(minmax01(v), eps=0.1, sigma2=0.01)

        pal = jax.jit(lambda u: ops.fused_adjacency(u, eps=0.1, sigma2=0.01))
        ref = jax.jit(_ref)
        ms_p, out_p = _time_ms(lambda: pal(u))
        ms_r, out_r = _time_ms(lambda: ref(u))
        rows.append(_row("fused_3dg", {"n": n, "d": d}, ms_r, ms_p,
                         _err(out_p, out_r),
                         flops=4 * n * n * d + 8 * n * n,
                         bytes_moved=4 * (n * d + n * n),
                         mode=mode, tiles=tiles))
    return rows


def _fw_rows(ns, mode, rng):
    from repro.kernels import ops, ref
    from repro.kernels.autotune import resolve
    rows = []
    for n in ns:
        r = (rng.random((n, n)) * 10).astype(np.float32)
        r[rng.random((n, n)) < 0.4] = np.inf
        np.fill_diagonal(r, 0)
        rj = jnp.asarray(r)
        tiles = resolve("floyd_warshall", {"tile": 128}, n=n)
        nb = -(-n // tiles["tile"])
        ms_p, out_p = _time_ms(lambda: ops.floyd_warshall(rj), reps=1)
        ms_r, out_r = _time_ms(lambda: ref.floyd_warshall_ref(rj), reps=1)
        rows.append(_row("floyd_warshall", {"n": n}, ms_r, ms_p,
                         _err(out_p, out_r),
                         flops=2 * n ** 3,
                         bytes_moved=8 * nb * n * n,
                         mode=mode, tiles=tiles))
    return rows


def _select_rows(ns, mode, rng):
    from repro.core.sampler_device import fedgs_select
    rows = []
    sweeps = 2
    for n in ns:
        m = max(16, n // 16)
        h = rng.random((n, n)).astype(np.float32)
        h = (h + h.T) / 2
        np.fill_diagonal(h, 0)
        hj = jnp.asarray(h)
        counts = jnp.zeros((n,), jnp.float32)
        avail = jnp.asarray(rng.random(n) > 0.2)
        al = jnp.float32(1.0)
        sel = {}
        for backend in ("ref", "pallas"):
            fn = jax.jit(lambda h, c, a: fedgs_select(
                h, c, a, al, m=m, max_sweeps=sweeps, backend=backend))
            ms, out = _time_ms(lambda: fn(hj, counts, avail))
            sel[backend] = (ms, np.asarray(out[0]))
        bit_equal = bool(np.array_equal(sel["ref"][1], sel["pallas"][1]))
        row = _row("fedgs_select", {"n": n, "m": m}, sel["ref"][0],
                   sel["pallas"][0], 0.0 if bit_equal else float("inf"),
                   flops=6 * sweeps * m * n + 4 * m * n,
                   bytes_moved=4 * (2 * sweeps * m * n + 2 * m * n),
                   mode=mode)
        row["selected_bit_equal"] = bit_equal
        rows.append(row)
    return rows


def _agg_rows(sizes, mode, rng):
    from repro.fed.aggregator_device import memory_scatter_reduce_ref
    from repro.kernels import ops
    from repro.kernels.autotune import resolve
    rows = []
    for n, p in sizes:
        m = max(8, n // 8)
        mem = jnp.asarray(rng.standard_normal((n, p)).astype(np.float32))
        upd = jnp.asarray(rng.standard_normal((m, p)).astype(np.float32))
        sel = jnp.asarray(rng.permutation(n)[:m].astype(np.int32))
        valid = jnp.ones((m,), bool)
        w = jnp.asarray(rng.random(n).astype(np.float32) / n)
        tiles = resolve("memory_aggregate", {"tile_n": 128, "tile_p": 256},
                        n=n, p=p)
        pal = jax.jit(lambda *a: ops.memory_aggregate(*a))
        ref = jax.jit(memory_scatter_reduce_ref)
        ms_p, out_p = _time_ms(lambda: pal(mem, upd, sel, valid, w))
        ms_r, out_r = _time_ms(lambda: ref(mem, upd, sel, valid, w))
        rows.append(_row("memory_aggregate", {"n": n, "p": p}, ms_r, ms_p,
                         max(_err(out_p[0], out_r[0]),
                             _err(out_p[1], out_r[1])),
                         flops=2 * n * p,
                         bytes_moved=4 * (2 * n * p + m * p + n),
                         mode=mode, tiles=tiles))
    return rows


def _attn_rows(mode, rng):
    from repro.kernels import ops, ref
    b, s, h, d, w = 1, 512, 4, 64, 128
    q = jnp.asarray(rng.standard_normal((b, s, h, d)).astype(np.float32))
    k = jnp.asarray(rng.standard_normal((b, s, h, d)).astype(np.float32))
    v = jnp.asarray(rng.standard_normal((b, s, h, d)).astype(np.float32))
    ms_p, out_p = _time_ms(lambda: ops.window_attention(q, k, v, window=w),
                           reps=1)
    ms_r, out_r = _time_ms(lambda: ref.window_attention_ref(q, k, v, window=w),
                           reps=1)
    return [_row("window_attention", {"b": b, "d": d, "h": h, "s": s, "w": w},
                 ms_r, ms_p, _err(out_p, out_r),
                 flops=4 * b * h * s * w * d,
                 bytes_moved=16 * b * s * h * d, mode=mode)]


def run(quick: bool = True) -> list[dict]:
    from benchmarks.common import pallas_backend_mode
    mode = pallas_backend_mode()
    rng = np.random.default_rng(0)
    ns = [256, 1024] if quick else [256, 1024, 2048]
    rows = []
    rows += _fused_rows(ns + ([] if quick else [4096]), mode, rng)
    rows += _fw_rows(ns, mode, rng)
    rows += _select_rows(ns, mode, rng)
    rows += _agg_rows([(256, 1024), (1024, 2048)] if quick else
                      [(256, 1024), (1024, 2048), (4096, 4096)], mode, rng)
    rows += _attn_rows(mode, rng)

    RESULTS.mkdir(parents=True, exist_ok=True)
    record = {
        "bench": "kernels",
        "backend": jax.default_backend(),
        "device_kind": jax.devices()[0].device_kind,
        "backend_mode": mode,
        "quick": quick,
        "rows": rows,
    }
    BENCH_PATH.write_text(json.dumps(record, indent=1))
    return rows


def summarize(rows) -> list[str]:
    from benchmarks.common import pallas_backend_mode
    out = ["", f"== Pallas kernels vs jnp oracle "
               f"({pallas_backend_mode()} mode; AI = flops/byte) =="]
    out.append(f"{'kernel':18s} {'tier':16s} {'ref_ms':>9s} {'pallas_ms':>10s} "
               f"{'speedup':>8s} {'AI':>7s} {'GFLOP/s':>9s} {'max_err':>9s} "
               f"{'win?':>5s}")
    for r in rows:
        flag = "*" if r["winner_expected"] else ""
        out.append(f"{r['kernel']:18s} {r['tier']:16s} {r['ref_ms']:9.2f} "
                   f"{r['pallas_ms']:10.2f} {r['speedup']:8.2f} {r['ai']:7.2f} "
                   f"{r['gflops']:9.2f} {r['max_err']:9.2e} {flag:>5s}")
    out.append("   (* = production tier where the pallas path is the enforced "
               "winner — see benchmarks/perf_assert.py)")
    return out


if __name__ == "__main__":
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    args = ap.parse_args()
    for line in summarize(run(quick=not args.full)):
        print(line)
