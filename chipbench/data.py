"""The benchmark's inputs, made on the host from a configuration's fixed
data seed: the federated dataset and the Table-1 availability tables.

This is the benchmark's own copy of the repository's surrogate generator
(``repro/data/vision.py``, ``repro/data/partition.py``) and of the paper's
availability formulas (``repro/core/availability.py``), kept here so that a
change to the program cannot change the inputs it is measured on.
"""
from __future__ import annotations

from dataclasses import dataclass

import numpy as np


@dataclass
class Dataset:
    """Per-client data padded to ``n_max`` rows, plus a shared validation
    split and the true per-client label histograms."""
    x: np.ndarray            # (N, n_max, H, W, C) float32
    y: np.ndarray            # (N, n_max) int32
    sizes: np.ndarray        # (N,) int64
    x_val: np.ndarray        # (V, H, W, C) float32
    y_val: np.ndarray        # (V,) int32
    num_classes: int
    label_dist: np.ndarray   # (N, C) float64 label counts

    @property
    def n_clients(self) -> int:
        return len(self.sizes)

    def label_sets(self) -> list:
        return [set(np.unique(self.y[k][: self.sizes[k]]).tolist())
                for k in range(self.n_clients)]


def _lognormal_sizes(n_total: int, n_clients: int, rng) -> np.ndarray:
    """n_k ~ lognormal(log(n/N) - 0.5, 1), rescaled to sum to n_total."""
    mean = np.log(n_total / n_clients) - 0.5
    sizes = rng.lognormal(mean, 1.0, n_clients)
    return np.maximum((sizes / sizes.sum() * n_total).astype(int), 8)


def _dirichlet_partition(labels, n_clients: int, alpha: float, rng, sizes):
    """Per-client label mix p_k ~ Dir(alpha * C * p*), drawn to the
    allocated sizes and capped by what each class has left."""
    classes = np.unique(labels)
    c = len(classes)
    p_star = np.array([(labels == cl).mean() for cl in classes])
    by_class = {cl: list(rng.permutation(np.flatnonzero(labels == cl)))
                for cl in classes}
    out = [[] for _ in range(n_clients)]
    for k in range(n_clients):
        pk = rng.dirichlet(alpha * p_star * c + 1e-9)
        want = rng.multinomial(sizes[k], pk)
        for ci, cl in enumerate(classes):
            for _ in range(min(want[ci], len(by_class[cl]))):
                out[k].append(by_class[cl].pop())
        while len(out[k]) < sizes[k]:
            nonempty = [cl for cl in classes if by_class[cl]]
            if not nonempty:
                break
            cl = nonempty[int(rng.integers(len(nonempty)))]
            out[k].append(by_class[cl].pop())
    return [np.array(ix, dtype=int) for ix in out]


def make_dataset(spec: dict) -> Dataset:
    """The dataset of a configuration ``spec``: class-Gaussian images (one
    random template per class plus noise), lognormal client sizes and
    Dirichlet label skew, drawn from its ``data_seed``."""
    rng = np.random.default_rng(spec["data_seed"])
    shape = tuple(spec["image_shape"])
    classes = int(spec["classes"])
    n_total = int(spec["n_total"])
    n = int(spec["n_clients"])
    templates = rng.normal(0, 1.0, (classes, *shape)).astype(np.float32)
    y = rng.integers(0, classes, n_total).astype(np.int32)
    x = templates[y] + rng.normal(0, spec["noise"],
                                  (n_total, *shape)).astype(np.float32)
    if spec.get("standardize"):
        # as image inputs are normalised: zero mean, unit variance
        x = ((x - x.mean()) / x.std()).astype(np.float32)
    n_val = int(n_total * spec["val_frac"])
    xv, yv = x[:n_val], y[:n_val]
    x, y = x[n_val:], y[n_val:]
    sizes = _lognormal_sizes(len(y), n, rng)
    parts = _dirichlet_partition(y, n, spec["dir_alpha"], rng, sizes)
    n_max = max(len(p) for p in parts)
    xp = np.zeros((n, n_max, *shape), np.float32)
    yp = np.zeros((n, n_max), np.int32)
    sz = np.zeros(n, np.int64)
    dist = np.zeros((n, classes))
    for k, ix in enumerate(parts):
        xp[k, :len(ix)] = x[ix]
        yp[k, :len(ix)] = y[ix]
        sz[k] = len(ix)
        dist[k] = np.bincount(y[ix], minlength=classes)
    return Dataset(xp, yp, sz, np.asarray(xv), np.asarray(yv), classes, dist)


# ------------------------------------------------ Table-1 availability modes
def mode_table(name: str, ds: Dataset, *, beta=None, period: int = 20,
               seed: int = 0) -> np.ndarray:
    """The (period, N) float64 probability table of one of the paper's
    seven availability modes (Table 1)."""
    n = ds.n_clients
    ns = ds.sizes.astype(float)
    if name == "IDL":
        return np.ones((1, n))
    if name == "MDF":
        b = 0.7 if beta is None else beta
        return (ns ** b / np.max(ns ** b))[None]
    if name == "LDF":
        b = 0.7 if beta is None else beta
        inv = ns ** (-b)
        return (inv / np.max(inv))[None]
    if name == "YMF":
        b = 0.9 if beta is None else beta
        sets = ds.label_sets()
        gmax = max(max(s) for s in sets)
        return np.array([b * min(s) / max(gmax, 1) + (1 - b)
                         for s in sets])[None]
    if name == "YC":
        b = 0.9 if beta is None else beta
        sets = ds.label_sets()
        c = ds.num_classes
        rows = []
        for t in range(period):
            phase = (1 + t) / period
            rows.append([b * float(any(
                y / c <= phase and (phase < (y + 1) / c or y + 1 == c)
                for y in s)) + (1 - b) for s in sets])
        return np.asarray(rows)
    if name in ("LN", "SLN"):
        b = 0.5 if beta is None else beta
        rng = np.random.default_rng(seed)
        c = rng.lognormal(0.0, np.log(1.0 / (1.0 - b)), n)
        p = c / c.max()
        if name == "LN":
            return p[None]
        mod = 0.4 * np.sin(2 * np.pi * (1 + np.arange(period)) / period) + 0.5
        return np.clip(p[None] * mod[:, None], 0.0, 1.0)
    raise ValueError(f"unknown availability mode {name!r}")


def draw_masks(table: np.ndarray, rounds: int, rng) -> np.ndarray:
    """(rounds, N) Bernoulli availability trace, at least one client on."""
    out = rng.random((rounds, table.shape[1])) < table[
        np.arange(rounds) % table.shape[0]]
    for t in np.flatnonzero(~out.any(1)):
        out[t, rng.integers(table.shape[1])] = True
    return out
