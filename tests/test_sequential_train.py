"""Local training client by client, with the Eq. 18 sum streamed into one
accumulator (``fed/client.make_sequential_trainer``), and the size rule by
which ``ScanEngine`` takes that path.

Pinned claims:

* the sequential trainer returns what the vmap trainer followed by
  ``fedavg_combine`` returns, for the CNN and the reduced hybrid LM, alone
  and under a cell vmap of one cell or several; an all-zero-weight round
  returns the global params;
* the size rule: the paper sweep's stack stays on the vmap path, a
  cross-silo LM's goes client by client; the engine follows the rule, and
  no flag chooses the path;
* on that path only the batch's sampler and aggregator families are built
  (no top-k, so no Power-of-Choice probe, in a FedGS program), only their
  state is carried, and a batch that needs the (M, P) stack raises;
* whole ``ScanEngine`` runs on the two paths agree, and
  ``runtime_stats()["local_train_seq"]`` reports the path.
"""
import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.configs.registry import get_config
from repro.core.sampler_device import make_sampler_step
from repro.data.fed_dataset import FedDataset
from repro.fed import scan_engine
from repro.fed.aggregator_device import (
    MedianProcess, FedAdamProcess, fedavg_combine,
)
from repro.fed.client import make_local_trainer, make_sequential_trainer
from repro.fed.models import language_model, small_cnn
from repro.fed.scan_engine import (
    ScanConfig, ScanEngine, precompute_masks, trains_sequentially,
)

STEPS, BATCH, S = 2, 3, 16


def tiny_lm():
    a = get_config("granite-4.0-h-micro").reduced()
    return dataclasses.replace(
        a, d_model=64, d_ff=128, vocab_size=128, n_heads=4, n_kv_heads=2,
        head_dim=16, ssm=dataclasses.replace(a.ssm, head_dim=16, d_state=8,
                                             chunk=8))


def lm_ds(n=5, seed=0):
    r = np.random.default_rng(seed)
    xs = [r.integers(0, 128, (k, S + 1)).astype(np.int32)
          for k in (5, 7, 6, 4, 8)[:n]]
    return FedDataset.from_lists(
        xs, [np.zeros(len(x), np.int32) for x in xs],
        r.integers(0, 128, (6, S + 1)).astype(np.int32),
        np.zeros(6, np.int32), 2)


def cnn_ds(n=5, seed=1):
    r = np.random.default_rng(seed)
    xs = [r.normal(size=(k, 8, 8, 3)).astype(np.float32)
          for k in (6, 9, 12, 7, 10)[:n]]
    return FedDataset.from_lists(
        xs, [r.integers(0, 10, len(x)).astype(np.int32) for x in xs],
        r.normal(size=(8, 8, 8, 3)).astype(np.float32),
        r.integers(0, 10, 8).astype(np.int32), 10)


MODELS = {"cnn": (small_cnn, cnn_ds), "granite": (
    lambda: language_model(tiny_lm()), lm_ds)}


def round_inputs(model, ds, weights):
    params = model.init(jax.random.PRNGKey(3))
    sel = jnp.asarray([3, 0, 4], jnp.int32)
    keys = jax.random.split(jax.random.PRNGKey(4), 3)
    return (params, jnp.asarray(ds.x), jnp.asarray(ds.y),
            jnp.asarray(ds.sizes, jnp.int32), sel, jnp.float32(0.05), keys,
            jnp.asarray(weights, jnp.float32))


def vmap_then_fedavg(model, args):
    params, x, y, sizes, sel, lr, keys, w = args
    local = make_local_trainer(model.loss, local_steps=STEPS,
                               batch_size=BATCH)(params, x[sel], y[sel],
                                                 sizes[sel], lr, keys)
    return fedavg_combine(local, w, params)


def assert_close(a, b, atol=1e-6):
    for u, v in zip(jax.tree_util.tree_leaves(a),
                    jax.tree_util.tree_leaves(b)):
        np.testing.assert_allclose(np.asarray(u), np.asarray(v), rtol=0,
                                   atol=atol)


@pytest.mark.parametrize("which", list(MODELS))
def test_streamed_sum_matches_vmap_and_fedavg(which):
    make, ds_of = MODELS[which]
    model = make()
    args = round_inputs(model, ds_of(), [6.0, 9.0, 0.0])
    seq = make_sequential_trainer(model.loss, local_steps=STEPS,
                                  batch_size=BATCH)
    assert_close(seq(*args), vmap_then_fedavg(model, args))


@pytest.mark.parametrize("which", list(MODELS))
def test_zero_weight_round_keeps_global(which):
    make, ds_of = MODELS[which]
    model = make()
    args = round_inputs(model, ds_of(), [0.0, 0.0, 0.0])
    avg = make_sequential_trainer(
        model.loss, local_steps=STEPS, batch_size=BATCH)(*args)
    for u, v in zip(jax.tree_util.tree_leaves(avg),
                    jax.tree_util.tree_leaves(args[0])):
        np.testing.assert_array_equal(np.asarray(u), np.asarray(v))


@pytest.mark.parametrize("cells", [1, 2])
def test_cells_train_one_after_another(cells):
    """Under a cell vmap (one cell: the unit axis dropped) each cell gets
    what it gets alone."""
    model = small_cnn()
    ds = cnn_ds()
    base = round_inputs(model, ds, [6.0, 9.0, 7.0])
    params = jax.tree_util.tree_map(
        lambda a: jnp.stack([a * (1 + 0.1 * c) for c in range(cells)]),
        base[0])
    keys = jnp.stack([jax.vmap(lambda k, c=c: jax.random.fold_in(k, c))(
        base[6]) for c in range(cells)])
    seq = make_sequential_trainer(model.loss, local_steps=STEPS,
                                  batch_size=BATCH)
    got = jax.vmap(seq, in_axes=(0, None, None, None, None, None, 0, None))(
        params, *base[1:6], keys, base[7])
    for c in range(cells):
        one = seq(jax.tree_util.tree_map(lambda a: a[c], params),
                  *base[1:6], keys[c], base[7])
        assert_close(jax.tree_util.tree_map(lambda a: a[c], got), one)


def test_size_rule():
    # the paper sweep: 21 cells x 10 clients x the CIFAR CNN's 797,962
    # float32 params (0.67 GB) on a 16 GB chip -> one vmap
    assert not trains_sequentially(21, 10, 4 * 797962, 16 * 2 ** 30)
    # one cell x 2 silos x 797,850,560 float32 params (6.38 GB) -> client
    # by client
    assert trains_sequentially(1, 2, 4 * 797850560, 16 * 2 ** 30)
    share = scan_engine.SEQUENTIAL_SHARE
    assert trains_sequentially(1, 1, 401, 400 / share)
    assert not trains_sequentially(1, 1, 400, 400 / share)


def make_engine(model, ds, monkeypatch, *, sequential, **kw):
    monkeypatch.setattr(scan_engine, "device_memory_bytes",
                        lambda: 1 if sequential else 16 * 2 ** 30)
    cfg = ScanConfig(rounds=3, m=2, local_steps=STEPS, batch_size=BATCH,
                     lr=0.05, eval_every=1, **kw)
    return ScanEngine(ds, model, cfg, use_masks=True)


def cells_of(eng, ds, *, seeds=(0, 1), **kw):
    rng = np.random.default_rng(5)
    return [eng.cell(seed=s, masks=rng.random((3, ds.n_clients)) < 0.8,
                     h=np.zeros((ds.n_clients, ds.n_clients)), **kw)
            for s in seeds]


def test_engine_follows_the_rule(monkeypatch):
    ds = cnn_ds()
    eng = make_engine(small_cnn(), ds, monkeypatch, sequential=False,
                      sampler="fedgs")
    assert eng._flags(cells_of(eng, ds))[4] is None
    eng = make_engine(small_cnn(), ds, monkeypatch, sequential=True,
                      sampler="fedgs")
    assert eng._flags(cells_of(eng, ds)) == (
        False, False, False, False, (("fedgs",), ("fedavg",)))


def test_families_present_build_no_probe():
    """A FedGS-only step never traces the PoC probe; the full switch does."""
    def probe(inputs, idx, keys):
        raise AssertionError("the PoC probe was traced")
    n, m = 6, 2
    args = ({"family": jnp.int32(0), "alpha": jnp.float32(1.0),
             "log_sizes": jnp.zeros(n)}, {}, jax.random.PRNGKey(0),
            {"h": jnp.zeros((n, n)), "counts": jnp.zeros(n)},
            jnp.ones(n, bool), jnp.int32(0))
    step = make_sampler_step(n, m, probe_losses=probe, families=("fedgs",))
    s, _ = step(*args)
    assert int(s.sum()) == m
    with pytest.raises(AssertionError, match="PoC probe"):
        make_sampler_step(n, m, probe_losses=probe)(*args)


def primitives(jaxpr):
    out = set()
    for e in jaxpr.eqns:
        out.add(e.primitive.name)
        for v in e.params.values():
            for sub in (v if isinstance(v, (tuple, list)) else (v,)):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    out |= primitives(inner)
    return out


def segment_jaxpr(eng, cells):
    flags = eng._flags(cells)
    stacked = scan_engine.stack_cells(cells)
    carry = eng._init_program(stacked, flags)(stacked)
    cl = eng._closures(flags, 1, None)
    return jax.make_jaxpr(jax.vmap(cl["segment"](2), in_axes=(0, 0, None)))(
        stacked, carry, jnp.int32(0)).jaxpr, carry


def test_sequential_program_builds_only_present_families(monkeypatch):
    ds = cnn_ds()
    eng = make_engine(small_cnn(), ds, monkeypatch, sequential=True,
                      sampler="fedgs")
    jaxpr, carry = segment_jaxpr(eng, cells_of(eng, ds))
    assert "top_k" not in primitives(jaxpr)
    assert set(carry["agg"]) == {"prev"}        # no moments, no panel
    eng = make_engine(small_cnn(), ds, monkeypatch, sequential=False,
                      sampler="fedgs")
    jaxpr, carry = segment_jaxpr(eng, cells_of(eng, ds))
    assert "top_k" in primitives(jaxpr)           # the full switch
    assert {"m1", "m2", "mem", "tau"} <= set(carry["agg"])


def test_sequential_carries_moments_for_fedadam(monkeypatch):
    ds = cnn_ds()
    eng = make_engine(small_cnn(), ds, monkeypatch, sequential=True,
                      sampler="fedgs")
    cells = cells_of(eng, ds, aggregator_process=FedAdamProcess())
    _, carry = segment_jaxpr(eng, cells)
    assert set(carry["agg"]) == {"prev", "m1", "m2"}


def test_robust_aggregator_on_sequential_path_raises(monkeypatch):
    ds = cnn_ds()
    eng = make_engine(small_cnn(), ds, monkeypatch, sequential=True,
                      sampler="fedgs")
    cells = cells_of(eng, ds, aggregator_process=MedianProcess())
    with pytest.raises(ValueError, match="'median' aggregator"):
        eng.run_batch(cells)


@pytest.mark.parametrize("which", list(MODELS))
@pytest.mark.parametrize("agg", ["fedavg", "fedadam"])
def test_sequential_engine_matches_vmap_engine(which, agg, monkeypatch):
    make, ds_of = MODELS[which]
    ds = ds_of()
    out = {}
    for seq in (False, True):
        eng = make_engine(make(), ds, monkeypatch, sequential=seq,
                          sampler="uniform", aggregator=agg)
        hist = eng.run_batch(cells_of(eng, ds), ckpt_every=2)
        out[seq] = (eng.params, hist, eng.runtime_stats()["local_train_seq"])
    assert out[False][2] == {"programs": 0, "clients": 0, "param_bytes": 0}
    stats = out[True][2]
    assert stats["programs"] >= 1 and stats["clients"] == 2
    assert stats["param_bytes"] == sum(
        a.size * a.dtype.itemsize
        for a in jax.tree_util.tree_leaves(out[True][0])) // 2
    for a, b in zip(out[False][1], out[True][1]):
        np.testing.assert_array_equal(a.sel, b.sel)
        np.testing.assert_allclose(a.val_loss, b.val_loss, rtol=1e-5)
    assert_close(out[False][0], out[True][0], atol=1e-5)


# the canonical optimized HLO (``test_local_train_fold.canonical_hlo``) of
# ``vmap_path_segment``'s program, as the tree before the client-by-client
# path compiled it on the CPU: the vmap path's program is unchanged by that
# path.  A change that alters the vmap path on purpose states its new
# digest here.
VMAP_PATH_DIGEST = \
    "57e4eca4c07f680fe5c638a4f57bb673e3dab0d9b436334f87b9a5ff2df9c717"


def vmap_path_segment():
    """The compiled 2-round segment program of a small paper-shaped sweep:
    3 cells of FedGS over a static H with host masks, FedAvg, the small
    CNN, 6 clients, m=3."""
    r = np.random.default_rng(3)
    xs = [r.normal(size=(n, 8, 8, 3)).astype(np.float32)
          for n in (6, 9, 12, 7, 10, 8)]
    ys = [r.integers(0, 10, len(x)).astype(np.int32) for x in xs]
    ds = FedDataset.from_lists(
        xs, ys, r.normal(size=(16, 8, 8, 3)).astype(np.float32),
        r.integers(0, 10, 16).astype(np.int32), 10)
    eng = ScanEngine(ds, small_cnn(), ScanConfig(
        rounds=4, m=3, local_steps=2, batch_size=5, lr=0.1, eval_every=2,
        sampler="fedgs", aggregator="fedavg"), use_masks=True)
    h = np.abs(r.normal(size=(6, 6)))
    h = (h + h.T) / 2
    np.fill_diagonal(h, 0)
    h /= h.max()
    cells = [eng.cell(seed=s, masks=r.random((4, 6)) < 0.8, h=h)
             for s in range(3)]
    flags = eng._flags(cells)
    assert flags[4] is None
    stacked = scan_engine.stack_cells(cells)
    carry = eng._init_program(stacked, flags)(stacked)
    return eng._segment_program(stacked, flags, 2).lower(
        stacked, carry, jnp.int32(0)).compile()


def test_vmap_path_segment_hlo_unchanged():
    import hashlib

    from test_local_train_fold import canonical_hlo
    text = canonical_hlo(vmap_path_segment())
    assert hashlib.sha256(text.encode()).hexdigest() == VMAP_PATH_DIGEST
