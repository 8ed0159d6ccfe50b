"""Chip smoke test: drive the FedGS scan engine's main path on a TPU.

  python chip_smoke.py              # phases A and B, on one chip
  python chip_smoke.py --chips 4    # phase C only, on four chips

Every phase runs sweeps through ``ScanEngine.run_batch``, the entry point
users call, and checks what comes out:

A  paper    The paper's CIFAR setting at full size (``benchmarks/common.py``
            with ``quick=False``: 100 clients, small CNN, m=10, E=10, B=32,
            lr 0.03): the seven Table-1 availability modes x 3 seeds = 21
            FedGS cells over the static oracle 3DG, 20 rounds in 5-round
            segments (the donated, pipelined runtime path).  Checks: finite
            losses, mean best loss below round 0, every sampled set holds
            min(m, |A_t|) distinct available clients.  The same cells then
            rerun on the host CPU as the plain float32 reference; the share
            of agreeing (cell, round) sampled sets and the largest val_loss
            gap are printed.
B  kernels  The FedGS server path at cross-device N=4096 (m=64, 10 rounds,
            the dynamic 3DG rebuilt in-scan every 5): 4 cells, two with the
            memory aggregator and two with Krum, once with every Pallas
            backend and once with every ``ref`` backend, both on the chip.
            Checks: ``tpu_custom_call`` in the compiled pallas program
            (the kernels ran natively), and each kernel against its
            ``ref`` twin at these widths under the contract its CPU tests
            state (3DG within 1e-5, identical Eq. 16 sets, bitwise memory
            scatter, identical Krum picks).  The two runs' sampled-set
            agreement is printed, not checked: the runs are not bitwise by
            contract (the 3DG matches to 1e-5 and FedGS flips on that).  The
            per-client data is cut to 4 samples per client on average (N
            is not cut): the engine bakes the padded per-client data into
            each program as constants, and at 16 per client (843 MB) one
            program takes ~160 s to compile for a v5e, ~60 s at 4 (129 MB).
C  4 chips  Phase A's batch shard_map'd on mesh (4, 1), then on (2, 2) with
            the memory aggregator and ``silo_reduce="psum"``, each against
            the single-device run: decisions bitwise, evals within 2e-6.

Exits non-zero, and prints no result line, when JAX finds no TPU, when the
repository is not beside this script, or when any phase or check fails.
The last line of standard output is the JSON result.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))

EVAL_TOL = 2e-6          # DESIGN.md assumption #16: evals across programs


class SmokeFailure(Exception):
    """A check on the program's output failed."""


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def log(msg: str) -> None:
    print(msg, flush=True)


# ------------------------------------------------------------------ helpers
def _paper_setting(rounds: int, quick: bool = False):
    """The paper's CIFAR setting as ``benchmarks/common.py`` defines it."""
    from benchmarks.common import fl_config, make_dataset, make_model
    from repro.fed.scan_engine import ScanConfig

    ds = make_dataset("cifar", quick)
    fcfg = fl_config("cifar", quick, 0)
    cfg = ScanConfig(rounds=rounds,
                     m=max(1, int(round(fcfg.sample_frac * ds.n_clients))),
                     local_steps=fcfg.local_steps, batch_size=fcfg.batch_size,
                     lr=fcfg.lr, lr_decay=fcfg.lr_decay, eval_every=1,
                     sampler="fedgs", max_sweeps=32)
    return ds, make_model("cifar"), cfg, fcfg.avail_seed


def _paper_cells(ds, cfg, avail_seed: int, seeds):
    """(availability trace per mode, mode of each cell, cell factory).
    Every cell gets the static oracle H as ``_scan_row_setup`` builds it,
    computed once on the host CPU in float32 and shared by every run, and
    its Table-1 mode's host availability trace (every run sees the
    identical trace)."""
    import jax

    from repro.core.availability import ALL_MODES, make_mode
    from repro.fed.scan_engine import oracle_h, precompute_masks

    with jax.default_device(jax.devices("cpu")[0]):
        h = oracle_h(ds.label_dist)
    masks = {}
    for name in ALL_MODES:
        mode = make_mode(name, n_clients=ds.n_clients, data_sizes=ds.sizes,
                         label_sets=ds.label_sets(),
                         num_labels=ds.num_classes, seed=99)
        masks[name] = precompute_masks(mode, cfg.rounds, avail_seed)

    def cells(eng):
        return [eng.cell(seed=s, masks=masks[name], alpha=1.0, h=h)
                for name in ALL_MODES for s in seeds]
    order = [name for name in ALL_MODES for _ in seeds]
    return masks, order, cells


def _run(eng, cells, **kw):
    """(histories, wall s, compile s) of one ``run_batch``."""
    before = eng.runtime_stats()["compile_ms"]
    t0 = time.perf_counter()
    hists = eng.run_batch(cells, **kw)
    wall = time.perf_counter() - t0
    return hists, wall, (eng.runtime_stats()["compile_ms"] - before) / 1e3


def _check_losses(hists, tag: str) -> None:
    import numpy as np
    for i, h in enumerate(hists):
        check(bool(np.all(np.isfinite(h.val_loss))),
              f"{tag}: cell {i} has a non-finite val_loss")
        check(bool(np.all(np.isfinite(h.val_acc))),
              f"{tag}: cell {i} has a non-finite val_acc")


def _check_sets(hists, m: int, masks_of, tag: str) -> None:
    """Every round's sampled set: min(m, |A_t|) distinct clients (all of
    them available, where the trace is known)."""
    import numpy as np
    for i, h in enumerate(hists):
        avail = masks_of(i)
        for t in range(len(h.sel)):
            s = h.sampled(t)
            check(len(np.unique(s)) == len(s),
                  f"{tag}: cell {i} round {t} repeats a client: {s}")
            if avail is None:
                check(len(s) == m, f"{tag}: cell {i} round {t} sampled "
                      f"{len(s)} clients, not {m}")
                continue
            n_av = int(avail[t].sum())
            check(len(s) == min(m, n_av),
                  f"{tag}: cell {i} round {t} sampled {len(s)} clients "
                  f"with {n_av} available")
            check(bool(np.all(avail[t][s])),
                  f"{tag}: cell {i} round {t} sampled unavailable clients")


def _compare(a_hists, b_hists):
    """(share of agreeing (cell, round) sampled sets, whether every
    decision field is bitwise equal, largest gap per eval field)."""
    import numpy as np
    same = total = 0
    bitwise = True
    gaps = {"val_loss": 0.0, "val_acc": 0.0}
    for a, b in zip(a_hists, b_hists):
        for t in range(len(a.sel)):
            total += 1
            same += bool(np.array_equal(a.sel[t], b.sel[t])
                         and np.array_equal(a.valid[t], b.valid[t]))
        bitwise &= all(np.array_equal(getattr(a, f), getattr(b, f))
                       for f in ("sel", "valid", "counts"))
        for f in gaps:
            gaps[f] = max(gaps[f], float(np.max(np.abs(
                getattr(a, f) - getattr(b, f)))))
    return same / max(total, 1), bitwise, gaps


# ------------------------------------------------------------------- phases
def phase_paper(*, rounds: int = 20, seeds=(0, 1, 2), segment: int = 5,
                quick: bool = False) -> dict:
    """Phase A: the paper's full-size setting, then the CPU reference."""
    import jax
    import numpy as np

    from repro.fed.scan_engine import ScanEngine

    ds, model, cfg, avail_seed = _paper_setting(rounds, quick)
    masks, order, make_cells = _paper_cells(ds, cfg, avail_seed, seeds)
    eng = ScanEngine(ds, model, cfg, use_masks=True)
    hists, wall, comp = _run(eng, make_cells(eng), ckpt_every=segment)
    _, steady, _ = _run(eng, make_cells(eng), ckpt_every=segment)
    log(f"[A paper] {len(hists)} cells x {rounds} rounds, N={ds.n_clients} "
        f"m={cfg.m}: wall {wall:.3f} s, compile {comp:.3f} s, "
        f"steady rerun {steady:.3f} s")
    _check_losses(hists, "A")
    first = float(np.mean([h.val_loss[0] for h in hists]))
    best = float(np.mean([h.best_loss for h in hists]))
    log(f"[A paper] mean val_loss round 0 {first:.6f} -> mean best "
        f"{best:.6f}")
    check(best < first, "A: mean best loss is not below round 0")
    _check_sets(hists, cfg.m, lambda i: masks[order[i]], "A")

    with jax.default_device(jax.devices("cpu")[0]):
        ref_eng = ScanEngine(ds, model, cfg, use_masks=True)
        ref, ref_wall, ref_comp = _run(ref_eng, make_cells(ref_eng),
                                       ckpt_every=segment)
    share, _, gaps = _compare(hists, ref)
    log(f"[A paper] CPU float32 reference (wall {ref_wall:.3f} s, compile "
        f"{ref_comp:.3f} s): sampled sets agree in {share:.4f} of "
        f"(cell, round); largest val_loss gap {gaps['val_loss']:.3e}")
    return {"wall_s": wall, "compile_s": comp, "steady_s": steady,
            "sets_agree": share, "val_loss_gap": gaps["val_loss"]}


def phase_kernels(*, n: int = 4096, m: int = 64, rounds: int = 10,
                  refresh: int = 5, per_client: int = 4) -> dict:
    """Phase B: the FedGS server path at cross-device N, Pallas vs ref."""
    from benchmarks.common import make_model
    from repro.core.availability import make_mode
    from repro.data.vision import make_cifar_like
    from repro.fed.aggregator_device import make_aggregator_process
    from repro.fed.scan_engine import ScanConfig, ScanEngine

    ds = make_cifar_like(n_clients=n, n_total=per_client * n)
    model = make_model("cifar")
    mode = make_mode("LN", n_clients=n, seed=99)
    aggs = ("memory", "memory", "krum", "krum")
    out, hists = {}, {}
    for backend in ("pallas", "ref"):
        cfg = ScanConfig(rounds=rounds, m=m, local_steps=10, batch_size=32,
                         lr=0.03, eval_every=1, sampler="fedgs",
                         max_sweeps=32, graph_refresh_every=refresh,
                         aggregator="memory", graph_backend=backend,
                         solver_backend=backend, agg_backend=backend)
        eng = ScanEngine(ds, model, cfg)

        def cells():
            return [eng.cell(seed=i, mode=mode, alpha=1.0,
                             aggregator_process=make_aggregator_process(
                                 a, krum_f=m // 10))
                    for i, a in enumerate(aggs)]
        hists[backend], wall, comp = _run(eng, cells())
        log(f"[B kernels/{backend}] {len(aggs)} cells x {rounds} rounds, "
            f"N={n} m={m}: wall {wall:.3f} s, compile {comp:.3f} s")
        _check_losses(hists[backend], f"B/{backend}")
        _check_sets(hists[backend], m, lambda i: None, f"B/{backend}")
        out[backend] = {"wall_s": wall, "compile_s": comp}
        if backend == "pallas":
            t0 = time.perf_counter()
            text = eng.lower_batch(cells()).compile().as_text()
            n_calls = text.count("tpu_custom_call")
            log(f"[B kernels/pallas] compiled program holds {n_calls} "
                f"tpu_custom_call sites ({time.perf_counter() - t0:.3f} s "
                f"to recompile for the text)")
            check(n_calls > 0, "B: no tpu_custom_call in the compiled "
                  "pallas program — the kernels did not run natively")
            _kernel_contracts(eng, cells(), hists[backend], m)
    # the two whole runs are not bitwise by contract: build_h's pallas path
    # matches ref to 1e-5, not bitwise, and the memory reduction to
    # roundoff, and FedGS flips on such differences — so their agreement
    # is reported, and each kernel's own contract is what is checked
    share, _, gaps = _compare(hists["pallas"], hists["ref"])
    log(f"[B kernels] pallas vs ref runs: sampled sets agree in "
        f"{share:.4f} of (cell, round); largest val_loss gap "
        f"{gaps['val_loss']:.3e}")
    out["sets_agree"] = share
    return out


def _kernel_contracts(eng, cells, hists, m: int) -> None:
    """Each main-path kernel against its ``ref`` twin, natively at phase
    B's widths, held to the contract its CPU tests hold it to: the in-scan
    3DG build (fused similarity + Floyd–Warshall) on the engine's real
    init embeddings within 1e-5 (``test_kernels.py``); the Eq. 16 solve on
    that H and the runs' counts picks the ref's exact sets
    (``test_sampler_device.py``); the memory scatter is bitwise and its
    reduction within 1e-6 (``test_aggregator_device.py``); Krum picks the
    ref's rows (``test_robust_aggregators.py``)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core.graph_device import GraphConfig, build_h
    from repro.core.sampler_device import fedgs_select
    from repro.fed.aggregator_device import (
        krum_select, memory_scatter_reduce_ref,
    )
    from repro.kernels.ops import memory_aggregate

    cfg = eng.cfg
    t0 = time.perf_counter()
    carry = eng.init_carry(cells).tree
    gcfg = GraphConfig(eps=cfg.graph_eps, sigma2=cfg.graph_sigma2,
                       similarity="functional")
    h = np.asarray(carry["h"])
    h_ref = np.asarray(jax.jit(jax.vmap(
        lambda e: build_h(e, gcfg, backend="ref")))(carry["emb"]))
    err = np.abs(h - h_ref) - 1e-5 * np.abs(h_ref)
    check(bool(np.all(err <= 1e-5)), f"B: pallas 3DG differs from ref by "
          f"{float(np.max(np.abs(h - h_ref)))} (> 1e-5 + 1e-5·|H|)")

    n = h.shape[-1]
    k_av, k_mem, k_upd, k_sel, k_w, k_x = jax.random.split(
        jax.random.PRNGKey(0), 6)
    avail = jax.random.bernoulli(k_av, 0.5, (n,))
    counts = jnp.asarray(np.stack([hh.counts for hh in hists]), jnp.float32)

    def solve(backend):
        return np.asarray(jax.jit(jax.vmap(lambda hh, c: fedgs_select(
            hh, c, avail, jnp.float32(1.0), m=m, max_sweeps=cfg.max_sweeps,
            backend=backend)))(jnp.asarray(h), counts))
    check(np.array_equal(solve("pallas"), solve("ref")),
          "B: pallas and ref Eq. 16 solves pick different sets")

    p = int(carry["agg"]["mem"].shape[-1])
    mem = jax.random.normal(k_mem, (n, p), jnp.float32)
    upd = jax.random.normal(k_upd, (m, p), jnp.float32)
    sel = jax.random.permutation(k_sel, n)[:m]
    valid = jnp.ones((m,), bool)
    w = jax.random.uniform(k_w, (n,), jnp.float32)
    w = w / jnp.sum(w)
    new_p, red_p = jax.jit(memory_aggregate)(mem, upd, sel, valid, w)
    new_r, red_r = jax.jit(memory_scatter_reduce_ref)(mem, upd, sel, valid,
                                                      w)
    check(np.array_equal(np.asarray(new_p), np.asarray(new_r)),
          "B: pallas memory scatter is not bitwise the ref's")
    red_gap = float(np.max(np.abs(np.asarray(red_p) - np.asarray(red_r))))
    check(red_gap <= 1e-6, f"B: memory reduction differs by {red_gap}")

    x = jax.random.normal(k_x, (m, p), jnp.float32)
    picks = [np.asarray(jax.jit(lambda xx, b=b: krum_select(
        xx, valid, m // 10, 1, backend=b)[0])(x)) for b in ("pallas", "ref")]
    check(np.array_equal(*picks), "B: pallas and ref Krum pick different "
          "rows")
    log(f"[B kernels] kernel contracts at N={n} m={m} P={p} hold: 3DG "
        f"within {float(np.max(np.abs(h - h_ref))):.3e} of ref, identical "
        f"Eq. 16 sets, bitwise memory scatter (reduction gap "
        f"{red_gap:.3e}), identical Krum picks "
        f"({time.perf_counter() - t0:.3f} s)")


def phase_mesh(*, rounds: int = 20, seeds=(0, 1, 2), segment: int = 5,
               quick: bool = False, meshes=((4, 1), (2, 2))) -> dict:
    """Phase C: phase A's batch on device meshes vs one device."""
    from repro.fed.scan_engine import ScanEngine

    ds, model, cfg, avail_seed = _paper_setting(rounds, quick)
    _, _, make_cells = _paper_cells(ds, cfg, avail_seed, seeds)
    out = {}
    for mesh in meshes:
        base = cfg if mesh[1] == 1 else dataclasses.replace(
            cfg, aggregator="memory")
        meshed = dataclasses.replace(base, mesh=mesh) if mesh[1] == 1 else \
            dataclasses.replace(base, mesh=mesh, silo_reduce="psum")
        tag = f"C mesh={mesh[0]}x{mesh[1]} {base.aggregator}"
        one = ScanEngine(ds, model, base, use_masks=True)
        single, s_wall, s_comp = _run(one, make_cells(one),
                                      ckpt_every=segment)
        eng = ScanEngine(ds, model, meshed, use_masks=True)
        hists, wall, comp = _run(eng, make_cells(eng), ckpt_every=segment)
        _check_losses(hists, tag)
        share, bitwise, gaps = _compare(hists, single)
        log(f"[{tag}] single device: wall {s_wall:.3f} s, compile "
            f"{s_comp:.3f} s; meshed: wall {wall:.3f} s, compile "
            f"{comp:.3f} s; decisions bitwise: {bitwise} (sets agree in "
            f"{share:.4f}); largest gap val_loss {gaps['val_loss']:.3e}, "
            f"val_acc {gaps['val_acc']:.3e}")
        out[tag] = (bitwise, gaps)
    # every mesh is measured before any check fails, so one four-chip
    # call reports all of them
    for tag, (bitwise, gaps) in out.items():
        check(bitwise, f"{tag}: decisions differ from one device")
        for f, d in gaps.items():
            check(d <= EVAL_TOL, f"{tag}: {f} differs by {d} > {EVAL_TOL}")
    return out


# --------------------------------------------------------------------- main
def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only phase C, the four-chip mesh path")
    args = ap.parse_args(argv)

    if not os.path.isdir(os.path.join(ROOT, "src", "repro")):
        print(f"chip_smoke: the repository's src/repro is not beside "
              f"{__file__}", file=sys.stderr)
        return 2
    sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]

    import jax
    devs = jax.devices()
    dev = devs[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: JAX finds no TPU (platform {dev.platform!r}); "
              f"refusing to run on the CPU", file=sys.stderr)
        return 2
    if len(devs) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} TPU "
              f"devices, JAX finds {len(devs)}", file=sys.stderr)
        return 2

    from repro.fed.runtime import enable_compile_cache
    log(f"device: {dev.platform} {dev.device_kind} x{len(devs)}; compile "
        f"cache {enable_compile_cache()}")
    phases = ([("C mesh", phase_mesh)] if args.chips == 4 else
              [("A paper", phase_paper), ("B kernels", phase_kernels)])
    try:
        for name, fn in phases:
            t0 = time.perf_counter()
            fn()
            log(f"[{name}] passed in {time.perf_counter() - t0:.3f} s")
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
