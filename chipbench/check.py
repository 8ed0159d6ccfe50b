"""The comparison that decides ``correct``.

Set-up drives the cell's compiled init and segment programs (the ones the
window's ``run_batch_stream`` calls) through the first segment of sweep 0.
Once the window has closed, the plain reference follows every cell of that
sweep over the same rounds, trained on the program's sampled sets, and
these numbers are read; each that has a limit in
``chipbench/limits/<workload>.json`` is compared with it, the others are
reported:

set_gap    the largest amount by which a sampled set's Eq. 16 objective
           lies below the reference's own solve of that round (infinite
           when a set repeats a client, holds an unavailable one, or has
           the wrong size) — availability draw and FedGS solve;
loss_gap   the largest |val_loss| gap at the segment's first eval round
           (round 0: ten local steps, one aggregation, one eval) — local
           training, aggregation, eval (``loss_gap_all``: over every eval
           round of the segment);
delta_gap  over cells and parameter leaves, the worst gap between the
           norms of the program's and the reference's parameter change
           over the segment, against the larger of the reference leaf's
           change and the median leaf's.  A leaf whose reference change
           is under a thousandth of the median leaf's is left out
           (``delta_gap_median``: the median leaf's gap instead).
h_gap      (dynamic 3DG only) the largest |H| entry gap after the
           segment's 3DG rebuild — the similarity and Floyd–Warshall path.
"""
from __future__ import annotations

import json
import os

import numpy as np

def delta_norms(after: dict, before: dict) -> dict:
    return {k: float(np.linalg.norm(np.asarray(after[k], np.float64)
                                    - np.asarray(before[k], np.float64)))
            for k in after}


def worst(values) -> float:
    """The largest value; NaN where any is NaN or there is none (a run
    that diverged, or a reading that is missing, fails)."""
    values = [float(v) for v in values]
    if not values or any(v != v for v in values):
        return float("nan")
    return max(values)


def compare(prog: list, ref: list, dynamic: bool) -> dict:
    """``prog``: per cell {params, params0, val_loss {t: v}, h};
    ``ref``: the reference's replay of the same cell on its sets."""
    cells = []
    for p, r in zip(prog, ref):
        gaps = {t: abs(float(p["val_loss"][t]) - v)
                for t, v in r["losses"].items()}
        dp = delta_norms(p["params"], p["params0"])
        dr = delta_norms(r["params"], r["params0"])
        med = float(np.median(list(dr.values())))
        rel = [abs(dp[k] - v) / max(v, med) for k, v in dr.items()
               if v >= 1e-3 * med]
        c = {"set_gap": worst(r["gaps"]), "loss_gap": gaps[min(gaps)],
             "loss_gap_all": worst(gaps.values()), "delta_gap": worst(rel),
             "delta_gap_median": float(np.median(rel)) if rel
             else float("nan")}
        if dynamic:
            c["h_gap"] = float(np.max(np.abs(
                np.asarray(p["h"], np.float64) - np.asarray(r["h"]))))
        cells.append(c)
    return {k: worst(c[k] for c in cells) for k in cells[0]}


def load_limits(root: str, workload: str) -> dict:
    path = os.path.join(root, "chipbench", "limits", f"{workload}.json")
    with open(path) as f:
        return json.load(f)["limits"]


def verdict(numbers: dict, limits: dict) -> tuple:
    """(correct, [(name, value, limit)]): every number that has a limit is
    within it; a NaN, or a limit whose number is missing, fails."""
    rows = [(k, numbers.get(k, float("nan")), lim)
            for k, lim in limits.items()]
    ok = bool(rows) and all(v == v and v <= lim for _, v, lim in rows)
    return ok, rows
