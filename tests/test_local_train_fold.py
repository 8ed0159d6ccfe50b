"""Local training folds enclosing vmap axes into its client axis.

Pinned claims:

* under a cell ``vmap`` around a ``lax.scan`` (as the engine runs a
  segment), the trainer returns what a plain vmap of the per-client
  trainer nested in the cell vmap returns, whether the cells share the
  params, the data or the learning rate or not, and at two levels of
  nesting;
* the folded program's convolutions group cells x clients, and each
  conv output splits into one client axis of cells x clients, where the
  nested vmap splits off the cell axis first (the split XLA then copies
  between layouts);
* with no enclosing vmap nothing folds: the trainer, and ``ScanEngine.run``
  on one cell, compile to the same optimized HLO as the nested vmap;
* ``runtime_stats()["local_train_fold"]`` counts the fold.
"""
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.data.fed_dataset import FedDataset
from repro.fed.client import make_local_trainer
from repro.fed.models import small_cnn

CELLS, CLIENTS, N_MAX, STEPS, BATCH = 3, 4, 12, 2, 5


def nested_trainer(model_loss, *, local_steps, batch_size, prox_mu=0.0):
    """The trainer as a plain vmap of one client (no fold): the per-client
    maths of ``make_local_trainer``, written out independently."""

    def one_client(global_params, x, y, n_k, lr, rng):
        def loss_fn(p, xb, yb):
            l = model_loss(p, xb, yb)
            if prox_mu > 0.0:
                l = l + 0.5 * prox_mu * sum(
                    jnp.sum(jnp.square(a - b)) for a, b in zip(
                        jax.tree_util.tree_leaves(p),
                        jax.tree_util.tree_leaves(global_params)))
            return l

        def step(params, key):
            idx = jax.random.randint(key, (batch_size,), 0,
                                     jnp.maximum(n_k, 1))
            g = jax.grad(loss_fn)(params, x[idx], y[idx])
            return jax.tree_util.tree_map(lambda p, gg: p - lr * gg,
                                          params, g), None

        return jax.lax.scan(step, global_params,
                            jax.random.split(rng, local_steps))[0]

    return jax.jit(jax.vmap(one_client, in_axes=(None, 0, 0, 0, None, 0)))


@pytest.fixture(scope="module")
def model():
    return small_cnn()


@pytest.fixture(scope="module")
def batch(model):
    r = np.random.default_rng(0)
    return {
        "params": jax.vmap(model.init)(
            jax.random.split(jax.random.PRNGKey(0), CELLS)),
        "x": jnp.asarray(r.normal(size=(CELLS, CLIENTS, N_MAX, 8, 8, 3)),
                         jnp.float32),
        "y": jnp.asarray(r.integers(0, 10, (CELLS, CLIENTS, N_MAX)),
                         jnp.int32),
        "sizes": jnp.asarray(r.integers(3, N_MAX + 1, (CELLS, CLIENTS)),
                             jnp.int32),
        "lr": jnp.asarray([0.1, 0.05, 0.02], jnp.float32),
        "keys": jax.vmap(lambda k: jax.random.split(k, CLIENTS))(
            jax.random.split(jax.random.PRNGKey(1), CELLS)),
    }


def _first(tree):
    return jax.tree_util.tree_map(lambda a: a[0], tree)


def segment(trainer, in_axes):
    """The trainer called each round of a 2-round ``lax.scan``, vmapped over
    cells with ``in_axes`` for (params, x, y, sizes, lr, keys)."""
    def run(params, x, y, sizes, lr, keys):
        def body(c, t):
            keys_t = jax.vmap(lambda k: jax.random.fold_in(k, t))(keys)
            out = trainer(params, x, y, sizes, lr, keys_t)
            return c + jax.tree_util.tree_leaves(out)[0].sum(), out
        return jax.lax.scan(body, jnp.float32(0), jnp.arange(2))
    return jax.jit(jax.vmap(run, in_axes=in_axes))


def _args(batch, in_axes):
    names = ("params", "x", "y", "sizes", "lr", "keys")
    scalar_lr = jnp.float32(0.1)
    return tuple(
        batch[n] if ax == 0 else (scalar_lr if n == "lr" else _first(batch[n]))
        for n, ax in zip(names, in_axes))


# (params, x, y, sizes, lr, keys) batched over cells (0) or shared (None)
FOLD_CASES = {
    "all_batched": ((0, 0, 0, 0, None, 0), 0.0),
    "lr_batched": ((0, 0, 0, 0, 0, 0), 0.0),
    "shared_params": ((None, 0, 0, 0, None, 0), 0.0),
    "shared_data": ((0, None, None, None, 0, 0), 0.0),
    "prox": ((0, 0, 0, 0, 0, 0), 0.1),
}


@pytest.mark.parametrize("case", list(FOLD_CASES))
def test_fold_matches_nested_vmap(case, model, batch):
    in_axes, prox_mu = FOLD_CASES[case]
    kw = dict(local_steps=STEPS, batch_size=BATCH, prox_mu=prox_mu)
    args = _args(batch, in_axes)
    want = segment(nested_trainer(model.loss, **kw), in_axes)(*args)
    trainer = make_local_trainer(model.loss, **kw)
    got = segment(trainer, in_axes)(*args)
    assert trainer.fold_stats == {"folds": 1, "clients": CELLS * CLIENTS}
    for a, b in zip(jax.tree_util.tree_leaves(want),
                    jax.tree_util.tree_leaves(got)):
        assert a.shape == b.shape
        np.testing.assert_allclose(np.asarray(b), np.asarray(a), rtol=0,
                                   atol=1e-6)


def test_fold_nests(model, batch):
    """Two vmaps around the trainer fold into one axis of 2 x 3 x 4."""
    kw = dict(local_steps=STEPS, batch_size=BATCH)

    def outer(trainer):
        inner = jax.vmap(trainer, in_axes=(0, 0, 0, 0, None, 0))
        return jax.jit(jax.vmap(inner, in_axes=(0, 0, None, None, None, 0)))
    params2 = jax.tree_util.tree_map(lambda a: jnp.stack([a, 0.5 * a]),
                                     batch["params"])
    keys2 = jnp.stack([batch["keys"], jax.vmap(jax.vmap(
        lambda k: jax.random.fold_in(k, 9)))(batch["keys"])])
    args = (params2, jnp.stack([batch["x"], -batch["x"]]), batch["y"],
            batch["sizes"], jnp.float32(0.1), keys2)
    want = outer(nested_trainer(model.loss, **kw))(*args)
    trainer = make_local_trainer(model.loss, **kw)
    got = outer(trainer)(*args)
    assert trainer.fold_stats["clients"] == 2 * CELLS * CLIENTS
    for a, b in zip(jax.tree_util.tree_leaves(want),
                    jax.tree_util.tree_leaves(got)):
        np.testing.assert_allclose(np.asarray(b), np.asarray(a), rtol=0,
                                   atol=1e-6)


def _eqns(jaxpr):
    """Every equation of a jaxpr and of the jaxprs nested in its params."""
    from jax.extend.core import ClosedJaxpr, Jaxpr
    for e in jaxpr.eqns:
        yield e
        for v in e.params.values():
            for sub in (v if isinstance(v, (tuple, list)) else (v,)):
                if isinstance(sub, ClosedJaxpr):
                    yield from _eqns(sub.jaxpr)
                elif isinstance(sub, Jaxpr):
                    yield from _eqns(sub)


def conv_layout(trainer, batch):
    """(feature_group_count of each convolution, the axis sizes each conv
    output is split into) of the cell-vmapped trainer's jaxpr."""
    in_axes = FOLD_CASES["all_batched"][0]
    jaxpr = jax.make_jaxpr(segment(trainer, in_axes))(
        *_args(batch, in_axes)).jaxpr
    groups, splits, conv_out = set(), set(), set()
    for e in _eqns(jaxpr):
        if e.primitive.name == "conv_general_dilated":
            groups.add(e.params["feature_group_count"])
            conv_out.add(e.outvars[0])
        elif e.primitive.name == "reshape" and e.invars[0] in conv_out:
            splits.add(e.outvars[0].aval.shape[-2])
    return groups, splits


def test_folded_convs_group_cells_times_clients(model, batch):
    kw = dict(local_steps=STEPS, batch_size=BATCH)
    groups, splits = conv_layout(make_local_trainer(model.loss, **kw), batch)
    assert groups == {CELLS * CLIENTS}
    # each conv output splits straight into one client axis of cells x
    # clients; the nested vmap splits off the cell axis first and moves it
    # to the front, which is what XLA copies between layouts
    assert splits == {CELLS * CLIENTS}
    assert conv_layout(nested_trainer(model.loss, **kw), batch)[1] == {CELLS}


def canonical_hlo(compiled):
    """Optimized HLO without metadata, every name renamed in order of first
    appearance: what XLA runs, independent of source lines and id counters."""
    lines = compiled.as_text().splitlines()
    start = next(i for i, l in enumerate(lines)
                 if l.startswith(("%", "ENTRY")))
    text = re.sub(r", metadata=\{[^}]*\}", "", "\n".join(lines[start:]))
    names: dict = {}
    return re.sub(r"%?[A-Za-z_][\w\-]*\.\d+|%[\w.\-]+",
                  lambda m: names.setdefault(m.group(0), f"v{len(names)}"),
                  text)


@pytest.fixture(scope="module")
def image_ds():
    r = np.random.default_rng(3)
    xs = [r.normal(size=(n, 8, 8, 3)).astype(np.float32)
          for n in (6, 9, 12, 7, 10, 8)]
    ys = [r.integers(0, 10, len(x)).astype(np.int32) for x in xs]
    return FedDataset.from_lists(
        xs, ys, r.normal(size=(16, 8, 8, 3)).astype(np.float32),
        r.integers(0, 10, 16).astype(np.int32), 10)


def _one_cell_program(ds, model, monkeypatch, trainer_factory):
    """The compiled program of ``ScanEngine.run`` on one cell, its trainer
    made by ``trainer_factory``; returns it and the engine's fold counter."""
    from repro.core.availability_device import make_process
    from repro.fed import scan_engine
    from repro.fed.scan_engine import ScanConfig, ScanEngine

    def factory(*a, **kw):
        trainer = trainer_factory(*a, **kw)
        if not hasattr(trainer, "fold_stats"):
            trainer.fold_stats = {"folds": 0, "clients": 0}
        return trainer
    monkeypatch.setattr(scan_engine, "make_local_trainer", factory)
    eng = ScanEngine(ds, model, ScanConfig(
        rounds=2, m=3, local_steps=STEPS, batch_size=BATCH, lr=0.1,
        eval_every=1, sampler="uniform"))
    cell = eng.cell(seed=0, process=make_process(
        "GE", n_clients=ds.n_clients, data_sizes=ds.sizes,
        label_sets=ds.label_sets(), num_labels=ds.num_classes, rounds=2,
        seed=3))
    compiled = eng._program([cell], False).lower(cell).compile()
    return compiled, eng.runtime_stats()["local_train_fold"]


@pytest.mark.parametrize("where", ["trainer", "scan_engine_run"])
def test_unfolded_program_unchanged(where, model, batch, image_ds,
                                    monkeypatch):
    if where == "trainer":
        args = (*_first(_args(batch, FOLD_CASES["all_batched"][0])[:4]),
                jnp.float32(0.1), _first(batch["keys"]))
        kw = dict(local_steps=STEPS, batch_size=BATCH)
        old = nested_trainer(model.loss, **kw).lower(*args).compile()
        trainer = make_local_trainer(model.loss, **kw)
        new = trainer.lower(*args).compile()
        fold = trainer.fold_stats
    else:
        old, _ = _one_cell_program(image_ds, model, monkeypatch,
                                   nested_trainer)
        new, fold = _one_cell_program(image_ds, model, monkeypatch,
                                      make_local_trainer)
    assert canonical_hlo(new) == canonical_hlo(old)
    assert fold == {"folds": 0, "clients": 0}


def test_runtime_stats_reports_fold(image_ds, model):
    from repro.core.availability_device import make_process
    from repro.fed.scan_engine import ScanConfig, ScanEngine
    ds = image_ds
    eng = ScanEngine(ds, model, ScanConfig(
        rounds=2, m=3, local_steps=STEPS, batch_size=BATCH, lr=0.1,
        eval_every=1, sampler="uniform"))
    cells = [eng.cell(seed=s, process=make_process(
        "GE", n_clients=ds.n_clients, data_sizes=ds.sizes,
        label_sets=ds.label_sets(), num_labels=ds.num_classes, rounds=2,
        seed=3 + s), avail_seed=70 + s) for s in range(2)]
    eng.run_batch(cells)
    # a scan whose carry turns batched is batched again, so the one
    # program may count the fold twice
    fold = eng.runtime_stats()["local_train_fold"]
    assert fold["folds"] >= 1 and fold["clients"] == 2 * 3
