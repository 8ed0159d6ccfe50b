"""Readings that a hybrid LM cell's limits are set from (not part of a
benchmark run), as ``chipbench/calibrate.py`` makes them, with the
reference's planted model faults besides:

  python chipbench/calibrate_hybrid.py --workload <name> --seeds 1 2 ... \
      [--ssd-highest-seeds ...] [--control-seeds ...] [--out readings.json]

For each seed, in one process on the chip: the program's first segment of
sweep 0 against the float32 reference, as a run checks it.  On the
``--ssd-highest-seeds`` (some of ``--seeds``), the same segment of the
program built again with its SSD contractions at highest precision
(``ssd_highest``), against the same reference replays: what the SSD's
bfloat16 operands add to the gap.  Then, on the
control seeds: the control (the reference in bfloat16, put in the
program's place) and each planted fault put in the program's place:
``half`` (``calibrate.py``'s: half of each round's clients left out of the
aggregation) and the model faults of the reference's ``FAULTS`` (the
attention layer skipped, the gated norm dropped, the residual multiplier
at 1.0), each against the reference on the same sampled sets (the
reference's own decisions, which no planted fault changes).
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--ssd-highest-seeds", type=int, nargs="*", default=[])
    ap.add_argument("--control-seeds", type=int, nargs="*", default=[])
    ap.add_argument("--out", default=None)
    ap.add_argument("--allow-cpu", action="store_true")
    ap.add_argument("--root", default=ROOT)
    args = ap.parse_args(argv)
    root = args.root
    sys.path[:0] = [ROOT, os.path.join(root, "src")]
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(root, ".jax_cache")

    import jax
    import jax.numpy as jnp
    import numpy as np

    from chipbench import check, run
    from chipbench.calibrate import as_program
    from chipbench.data import make_dataset
    from chipbench.generator import (
        cell_specs, engine_cells, engine_config, fed_dataset, fed_model,
        mode_tables,
    )
    from repro.fed.runtime import enable_compile_cache
    from repro.fed.scan_engine import ScanEngine
    if jax.devices()[0].platform != "tpu" and not args.allow_cpu:
        print("calibrate_hybrid: JAX finds no TPU", file=sys.stderr)
        return 2
    enable_compile_cache()
    cell = run.load_cell(root, args.workload)
    cfg, traffic = cell["cfg"], cell["traffic"]
    refmod = run.reference_module(root, cfg["reference"])
    ds = make_dataset(cfg)
    tables = mode_tables(traffic, ds)
    g = traffic["graph"]
    seg = int(traffic["segment_rounds"])
    h_static = np.asarray(refmod.build_h(
        jnp.asarray(ds.label_dist, jnp.float32), functional=False,
        eps=g["eps"], sigma2=g["sigma2"]))

    def specs(seed):
        return cell_specs(cfg, traffic, ds, seed, 0, tables)

    def compact(segs):
        """Each cell's parameters before and after cut to what ``check``
        reads of them: per leaf the change's norm, as a one-element change
        from zero, which ``check.delta_norms`` reads back exactly. A seed
        then holds no copy of a model of gigabytes on the host."""
        return [dict(p, params={k: np.array([v]) for k, v in
                                check.delta_norms(p["params"],
                                                  p["params0"]).items()},
                     params0={k: np.zeros(1) for k in p["params"]})
                for p in segs]

    def first_segments(seeds, label):
        eng = ScanEngine(
            fed_dataset(ds), fed_model(root, cfg, ds),
            engine_config(cfg, traffic),
            use_masks=traffic["availability"]["kind"] == "host_masks")
        t0 = time.perf_counter()
        got = {s: compact(run.first_segment(
            eng, engine_cells(eng, traffic, specs(s), h_static), seg, False))
            for s in seeds}
        print(f"{label}: {len(seeds)} seeds in "
              f"{time.perf_counter() - t0:.1f} s; local_train_seq "
              f"{eng.runtime_stats()['local_train_seq']}", flush=True)
        del eng
        jax.clear_caches()
        return got

    firsts = first_segments(args.seeds, "program")
    witness = {}
    if args.ssd_highest_seeds:
        from repro.models import ssd
        plain = ssd.ssd_chunked

        def ssd_highest(*a, **k):
            with jax.default_matmul_precision("highest"):
                return plain(*a, **k)
        ssd.ssd_chunked = ssd_highest
        try:
            witness = first_segments(
                [s for s in args.seeds if s in args.ssd_highest_seeds],
                "ssd_highest")
        finally:
            ssd.ssd_chunked = plain

    kinds = ("program", "ssd_highest", "control", "half") + tuple(
        refmod.FAULTS)
    out = {"workload": args.workload, **{k: {} for k in kinds}}
    ref = refmod.Reference(cfg, traffic, ds)

    def note(kind, s, t0):
        print(f"{kind} seed {s}: {out[kind][s]} "
              f"({time.perf_counter() - t0:.1f} s)", flush=True)

    def worst_leaves(prog, refs, k=4):
        """The leaves with the largest delta_gap terms: (gap, leaf, the
        program's change norm, the reference's, the median leaf's)."""
        rows = []
        for p, r in zip(prog, refs):
            dp = check.delta_norms(p["params"], p["params0"])
            dr = check.delta_norms(r["params"], r["params0"])
            med = float(np.median(list(dr.values())))
            rows += [(abs(dp[n] - v) / max(v, med), n, dp[n], v, med)
                     for n, v in dr.items() if v >= 1e-3 * med]
        return sorted(rows, reverse=True)[:k]

    for s in args.seeds:
        t0 = time.perf_counter()
        refs = compact([ref.replay(sp, seg, sets=p["sets"],
                                   h_static=h_static)
                        for sp, p in zip(specs(s), firsts[s])])
        out["program"][s] = check.compare(firsts[s], refs, False)
        note("program", s, t0)
        print(f"program seed {s} worst leaves: "
              f"{worst_leaves(firsts[s], refs)}", flush=True)
        if s in witness:
            for p, q in zip(witness[s], firsts[s]):
                for (o1, v1), (o2, v2) in zip(p["sets"], q["sets"]):
                    assert np.array_equal(o1, o2) and np.array_equal(v1, v2)
            t0 = time.perf_counter()
            out["ssd_highest"][s] = check.compare(witness[s], refs, False)
            note("ssd_highest", s, t0)
            print(f"ssd_highest seed {s} worst leaves: "
                  f"{worst_leaves(witness[s], refs)}", flush=True)
    for s in args.control_seeds:
        # the reference on its own decisions; a planted fault that decides
        # the same sets is compared with this one replay
        base = compact([ref.replay(sp, seg, h_static=h_static)
                        for sp in specs(s)])
        broken = [("half", ref, "half")] + [
            (f, refmod.Reference(cfg, traffic, ds, fault=f), None)
            for f in refmod.FAULTS] + [
            ("control", refmod.Reference(cfg, traffic, ds,
                                         dtype=jnp.bfloat16), None)]
        for kind, r, fault in broken:
            t0 = time.perf_counter()
            got = compact([r.replay(sp, seg, h_static=h_static, fault=fault)
                           for sp in specs(s)])
            for a, b in zip(got, base):
                for (o1, v1), (o2, v2) in zip(a["sets"], b["sets"]):
                    assert np.array_equal(o1, o2) and np.array_equal(v1, v2)
            out[kind][s] = check.compare(
                [as_program(a, seg) for a in got], base, False)
            note(kind, s, t0)
    for kind in kinds:
        vals = out[kind]
        if vals:
            keys = next(iter(vals.values())).keys()
            print(kind, {k: (min(v[k] for v in vals.values()),
                             max(v[k] for v in vals.values()))
                         for k in keys}, flush=True)
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)),
                    exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(out, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
