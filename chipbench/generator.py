"""The one traffic generator: a mix's parameters, the configuration and
``--seed`` give each sweep's cells, and those become the program's cells.

A sweep index ``j`` and the run's seed fix every cell of sweep ``j``: its
seed (model init, local-training and sampler keys, the dynamic 3DG's init
key), its availability seed and, for host-drawn availability, its mask
trace.  Every seed gives the same number of cells, rounds and clients, so
the work per sweep does not depend on the seed.
"""
from __future__ import annotations

import os

import numpy as np

from chipbench.data import draw_masks, mode_table

SEED_SPACE = 1 << 30      # cell seeds stay clear of the program's key offsets


def cell_specs(cfg: dict, traffic: dict, ds, seed: int, sweep: int,
               tables: dict) -> list:
    """The cells of one sweep, as plain host data (dicts)."""
    ss = np.random.SeedSequence([abs(int(seed)), int(seed < 0), int(sweep)])
    rng = np.random.default_rng(ss)
    rounds = cfg["rounds"]
    av = traffic["availability"]
    specs = []
    for mode in av["modes"]:
        masks = None
        if av["kind"] == "host_masks":
            masks = draw_masks(tables[mode], rounds, rng)
        elif av["kind"] != "device_table":
            raise ValueError(f"unknown availability kind {av['kind']!r}")
        for _ in range(traffic["seeds_per_mode"]):
            specs.append({"mode": mode, "table": tables[mode],
                          "masks": masks,
                          "seed": int(rng.integers(SEED_SPACE)),
                          "avail_seed": int(rng.integers(SEED_SPACE)),
                          "alpha": float(traffic["sampler"]["alpha"])})
    return specs


def mode_tables(traffic: dict, ds) -> dict:
    av = traffic["availability"]
    return {name: mode_table(name, ds, period=av["period"],
                             seed=av["table_seed"])
            for name in av["modes"]}


# ------------------------------------------------- the program's side
def engine_config(cfg: dict, traffic: dict):
    from repro.fed.scan_engine import ScanConfig
    tr, g, b = cfg, traffic["graph"], traffic["backends"]
    dynamic = g["kind"] == "dynamic"
    kw = {}
    if dynamic:
        kw = {"graph_refresh_every": g["refresh_every"],
              "probe_size": g["probe_size"], "probe_seed": g["probe_seed"]}
    return ScanConfig(
        rounds=tr["rounds"], m=tr["m"], local_steps=tr["local_steps"],
        batch_size=tr["batch_size"], lr=tr["lr"], lr_decay=tr["lr_decay"],
        eval_every=tr["eval_every"], sampler=traffic["sampler"]["family"],
        max_sweeps=traffic["sampler"]["max_sweeps"], graph_eps=g["eps"],
        graph_sigma2=g["sigma2"], graph_backend=b["graph"],
        solver_backend=b["solver"], aggregator=traffic["aggregator"]["family"],
        agg_backend=b["agg"], **kw)


def fed_dataset(ds):
    from repro.data.fed_dataset import FedDataset
    return FedDataset(ds.x, ds.y, ds.sizes, ds.x_val, ds.y_val,
                      ds.num_classes, ds.label_dist)


def fed_model(root: str, cfg: dict, ds):
    """The program's model: ``chipbench/models/<model>.py``'s ``make``."""
    import importlib.util
    path = os.path.join(root, "chipbench", "models", f"{cfg['model']}.py")
    spec = importlib.util.spec_from_file_location(
        f"chipbench_model_{cfg['model']}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.make(cfg, ds.num_classes)


def engine_cells(eng, traffic: dict, specs: list, h_static=None) -> list:
    """The program's cells for these specs."""
    from repro.core.availability_device import TableProcess
    from repro.fed.aggregator_device import (
        MemoryProcess, make_aggregator_process,
    )
    agg = traffic["aggregator"]
    aproc = MemoryProcess(gamma=agg["gamma"]) if agg["family"] == "memory" \
        else make_aggregator_process(agg["family"])
    procs = {}
    cells = []
    for sp in specs:
        kw = {"seed": sp["seed"], "alpha": sp["alpha"],
              "aggregator_process": aproc}
        if sp["masks"] is not None:
            kw["masks"] = sp["masks"]
        else:
            if sp["mode"] not in procs:
                procs[sp["mode"]] = TableProcess(sp["table"], name=sp["mode"])
            kw["process"] = procs[sp["mode"]]
            kw["avail_seed"] = sp["avail_seed"]
        if h_static is not None:
            kw["h"] = h_static
        cells.append(eng.cell(**kw))
    return cells
