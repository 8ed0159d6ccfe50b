"""Readings that the limits in ``chipbench/limits/<workload>.json`` are
set from (not part of a benchmark run):

  python chipbench/calibrate.py --workload <name> --seeds 1 2 ... \
      [--control-seeds ...] [--out readings.json]

For each seed, in one process on the chip: the program's first segment of
sweep 0 (the warm-up a run makes) against the float32 reference, as a run
checks it.  Then, on the control seeds: the control (the reference
computed in bfloat16, put in the program's place), and the planted faults
(the float32 reference put in the program's place with half of each
round's clients left out of the aggregation, or with one sampled set
altered where it is produced), each against the reference.  A state left
unchanged reads 1 on ``delta_gap`` by construction and is not run.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def as_program(r: dict, seg: int) -> dict:
    """A reference replay put in the program's place."""
    order = [o for o, _ in r["sets"]]
    valid = [v for _, v in r["sets"]]
    return {"params0": r["params0"], "params": r["params"], "h": r["h"],
            "val_loss": {t: r["losses"].get(t, float("nan"))
                         for t in range(seg)},
            "sets": (order, valid)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
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
    from chipbench.data import make_dataset
    from chipbench.generator import (
        cell_specs, engine_cells, engine_config, fed_dataset, fed_model,
        mode_tables,
    )
    from repro.fed.runtime import enable_compile_cache
    from repro.fed.scan_engine import ScanEngine
    if jax.devices()[0].platform != "tpu" and not args.allow_cpu:
        print("calibrate: JAX finds no TPU", file=sys.stderr)
        return 2
    enable_compile_cache()
    cell = run.load_cell(root, args.workload)
    cfg, traffic = cell["cfg"], cell["traffic"]
    refmod = run.reference_module(root, cfg["reference"])
    ds = make_dataset(cfg)
    tables = mode_tables(traffic, ds)
    g = traffic["graph"]
    dynamic = g["kind"] == "dynamic"
    seg = int(traffic["segment_rounds"])
    h_static = None
    if g["kind"] == "oracle_static":
        h_static = np.asarray(refmod.build_h(
            jnp.asarray(ds.label_dist, jnp.float32), functional=False,
            eps=g["eps"], sigma2=g["sigma2"]))
    eng = ScanEngine(fed_dataset(ds), fed_model(root, cfg, ds),
                     engine_config(cfg, traffic),
                     use_masks=traffic["availability"]["kind"] == "host_masks")

    def specs(seed):
        return cell_specs(cfg, traffic, ds, seed, 0, tables)

    t0 = time.perf_counter()
    firsts = {s: run.first_segment(
        eng, engine_cells(eng, traffic, specs(s), h_static), seg, dynamic)
        for s in args.seeds}
    print(f"program: {len(args.seeds)} seeds in "
          f"{time.perf_counter() - t0:.1f} s", flush=True)
    del eng
    jax.clear_caches()

    out = {"workload": args.workload, "program": {}, "control": {},
           "half": {}, "alter": {}}
    ref = refmod.Reference(cfg, traffic, ds)

    def against_ref(seed, prog):
        return check.compare(prog, [
            ref.replay(sp, seg, sets=p["sets"], h_static=h_static)
            for sp, p in zip(specs(seed), prog)], dynamic)

    for s in args.seeds:
        t0 = time.perf_counter()
        out["program"][s] = against_ref(s, firsts[s])
        print(f"program seed {s}: {out['program'][s]} "
              f"({time.perf_counter() - t0:.1f} s)", flush=True)
    for fault in ("half", "alter"):
        for s in args.control_seeds:
            planted = [as_program(ref.replay(
                sp, seg, h_static=h_static, fault=fault), seg)
                for sp in specs(s)]
            out[fault][s] = against_ref(s, planted)
            print(f"{fault} seed {s}: {out[fault][s]}", flush=True)
    ctrl = refmod.Reference(cfg, traffic, ds, dtype=jnp.bfloat16)
    for s in args.control_seeds:
        planted = [as_program(ctrl.replay(sp, seg, h_static=h_static), seg)
                   for sp in specs(s)]
        out["control"][s] = against_ref(s, planted)
        print(f"control seed {s}: {out['control'][s]}", flush=True)
    for kind in ("program", "control", "half", "alter"):
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
