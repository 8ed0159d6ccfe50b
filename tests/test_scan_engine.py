"""The jit-compiled scan engine (scan-over-rounds, vmap-over-cells):

* parity harness — with identical seed streams and bit-identical availability
  masks, the scan engine reproduces FLEngine's sampled sets exactly and its
  val-loss trajectory to float32 round-off (the ISSUE acceptance bar is 1e-4);
* vmap-batch — a batched run equals the per-cell runs stacked;
* device-side Gumbel top-k sampling invariants;
* in-scan dynamic 3DG refresh.
"""
import numpy as np
import pytest

from repro.core.availability import make_mode
from repro.core.sampler import FedGSSampler
from repro.fed.engine import FLConfig, FLEngine
from repro.fed.models import logistic_regression
from repro.fed.scan_engine import (
    ScanConfig, ScanEngine, oracle_h, precompute_masks, stack_cells,
)


def _mode(name, ds, seed=7):
    return make_mode(name, n_clients=ds.n_clients, data_sizes=ds.sizes,
                     label_sets=ds.label_sets(), num_labels=ds.num_classes,
                     seed=seed)


def _host_run(ds, mode, rounds, seed, frac):
    sampler = FedGSSampler(alpha=1.0, max_sweeps=16)
    cfg = FLConfig(rounds=rounds, sample_frac=frac, local_steps=5,
                   batch_size=10, lr=0.1, eval_every=1, seed=seed)
    eng = FLEngine(ds, logistic_regression(), sampler, mode, cfg)
    eng.install_oracle_graph(ds.opt_params)
    return eng, eng.run()


def _scan_cfg(rounds, m, **kw):
    return ScanConfig(rounds=rounds, m=m, local_steps=5, batch_size=10,
                      lr=0.1, eval_every=1, max_sweeps=16, **kw)


@pytest.mark.parametrize("mode_name,frac,rounds", [("IDL", 0.2, 10),
                                                   ("LN", 0.1, 20)])
def test_parity_with_host_engine(synthetic_ds, mode_name, frac, rounds):
    """Same seeds -> same sampled sets, val-loss within 1e-4 (Alg. 1 parity)."""
    ds = synthetic_ds
    mode = _mode(mode_name, ds)
    eng, hist = _host_run(ds, mode, rounds, seed=3, frac=frac)
    masks = precompute_masks(mode, rounds, eng.cfg.avail_seed)
    # parity precondition: the static-shape program always selects M clients
    assert masks.sum(1).min() >= eng.m

    seng = ScanEngine(ds, logistic_regression(),
                      _scan_cfg(rounds, eng.m, sampler="fedgs"),
                      use_masks=True)
    sh = seng.run(seng.cell(seed=3, masks=masks, alpha=1.0,
                            h=eng.sampler._h))
    for i, t in enumerate(hist.rounds):
        assert hist.sampled[i] == sh.sampled(t).tolist(), f"round {t}"
    np.testing.assert_allclose(
        sh.val_loss[np.asarray(hist.rounds)], np.asarray(hist.val_loss),
        atol=1e-4)
    np.testing.assert_array_equal(eng.counts, sh.counts)


def test_vmap_batch_equals_per_cell_runs(synthetic_ds):
    """One vmapped program over B cells == the B single-cell programs."""
    ds = synthetic_ds
    h = oracle_h(ds.opt_params)
    eng = ScanEngine(ds, logistic_regression(),
                     _scan_cfg(8, 6, sampler="fedgs"))
    modes = [_mode(n, ds) for n in ("IDL", "LN", "SLN")]
    cells = [eng.cell(seed=s, mode=m, alpha=a, h=h, avail_seed=40 + s)
             for s, (m, a) in enumerate(zip(modes, (0.5, 1.0, 2.0)))]
    batch = eng.run_batch(cells)
    for cell, b in zip(cells, batch):
        single = eng.run(cell)
        np.testing.assert_array_equal(b.sel, single.sel)
        np.testing.assert_array_equal(b.counts, single.counts)
        np.testing.assert_allclose(b.val_loss, single.val_loss, atol=2e-6)


def test_stack_cells_pads_tables(synthetic_ds):
    """Cells whose availability tables have different periods batch fine."""
    ds = synthetic_ds
    eng = ScanEngine(ds, logistic_regression(),
                     _scan_cfg(6, 4, sampler="uniform"))
    cells = [eng.cell(seed=0, mode=_mode("LN", ds)),       # period 1
             eng.cell(seed=1, mode=_mode("YC", ds))]       # period 20
    stacked = stack_cells(cells)
    assert stacked["proc"]["table"].shape[:2] == (2, 20)
    assert stacked["proc"]["table_b"].shape[:2] == (2, 20)
    hists = eng.run_batch(cells)
    assert all(np.isfinite(h.val_loss).all() for h in hists)


def test_gumbel_selection_invariants(synthetic_ds):
    """S_t subset of A_t and |S_t| = min(M, |A_t|) for uniform and MD."""
    ds = synthetic_ds
    rounds, m = 12, 6
    mode = _mode("LN", ds)
    masks = precompute_masks(mode, rounds, avail_seed=5)
    for sampler in ("uniform", "md"):
        eng = ScanEngine(ds, logistic_regression(),
                         _scan_cfg(rounds, m, sampler=sampler),
                         use_masks=True)
        sh = eng.run(eng.cell(seed=0, masks=masks))
        for t in range(rounds):
            sel = sh.sampled(t)
            avail = np.flatnonzero(masks[t])
            assert set(sel) <= set(avail)
            assert len(sel) == min(m, len(avail))
        # counts track the selections
        assert sh.counts.sum() == sum(min(m, int(masks[t].sum()))
                                      for t in range(rounds))


def test_scan_uniform_learns_device_availability(synthetic_ds):
    """Device-side Bernoulli availability + Gumbel sampling: still learns."""
    ds = synthetic_ds
    eng = ScanEngine(ds, logistic_regression(),
                     _scan_cfg(16, 6, sampler="uniform"))
    sh = eng.run(eng.cell(seed=0, mode=_mode("LN", ds)))
    assert sh.val_loss[-1] < sh.val_loss[0]
    assert np.isfinite(sh.val_loss).all()


def test_dynamic_3dg_refresh_in_scan(synthetic_ds):
    """The carried (emb, H) dynamic-3DG state rebuilds in-scan and learns."""
    ds = synthetic_ds
    eng = ScanEngine(ds, logistic_regression(),
                     _scan_cfg(12, 6, sampler="fedgs",
                               graph_refresh_every=4))
    sh = eng.run(eng.cell(seed=0, mode=_mode("LN", ds)))
    assert np.isfinite(sh.val_loss).all()
    assert sh.val_loss[-1] < sh.val_loss[0]


def test_dynamic_3dg_blocked_init_round(synthetic_ds, monkeypatch):
    """Past INIT_BLOCK clients the all-clients init probe round trains and
    embeds in blocks (lax.map), ragged last block included; it gives the
    single-vmap round's trajectory."""
    import repro.fed.scan_engine as se
    ds = synthetic_ds
    hists = []
    for block in (se.INIT_BLOCK, 8):
        monkeypatch.setattr(se, "INIT_BLOCK", block)
        eng = ScanEngine(ds, logistic_regression(),
                         _scan_cfg(6, 6, sampler="fedgs",
                                   graph_refresh_every=3))
        hists.append(eng.run(eng.cell(seed=0, mode=_mode("LN", ds))))
    np.testing.assert_array_equal(hists[0].sel, hists[1].sel)
    np.testing.assert_allclose(hists[0].val_loss, hists[1].val_loss,
                               atol=1e-6)


def test_poc_selection_invariants(synthetic_ds):
    """In-scan Power-of-Choice: S_t subset of A_t, |S_t| = min(M, |A_t|),
    counts track the selections (the host-loop fallback is gone)."""
    ds = synthetic_ds
    rounds, m = 12, 6
    mode = _mode("LN", ds)
    masks = precompute_masks(mode, rounds, avail_seed=5)
    eng = ScanEngine(ds, logistic_regression(),
                     _scan_cfg(rounds, m, sampler="poc"), use_masks=True)
    sh = eng.run(eng.cell(seed=0, masks=masks))
    for t in range(rounds):
        sel = sh.sampled(t)
        avail = np.flatnonzero(masks[t])
        assert set(sel) <= set(avail)
        assert len(sel) == min(m, len(avail))
    assert sh.counts.sum() == sum(min(m, int(masks[t].sum()))
                                  for t in range(rounds))
    assert np.isfinite(sh.val_loss).all()


def test_poc_learns(synthetic_ds):
    """Sanity: the in-scan PoC trajectory decreases validation loss."""
    ds = synthetic_ds
    eng = ScanEngine(ds, logistic_regression(),
                     _scan_cfg(16, 6, sampler="poc"))
    sh = eng.run(eng.cell(seed=0, mode=_mode("IDL", ds)))
    assert sh.val_loss[-1] < sh.val_loss[0]


def test_poc_keeps_top_m_loss_candidates(synthetic_ds):
    """Round-0 exact replication of the device PoC path: the kept set must
    equal the top-m probed-loss subset of the Gumbel candidate draw (an
    inverted top-k — keeping the LOWEST-loss candidates — would still learn,
    so this pins the selection rule itself)."""
    import jax
    import jax.numpy as jnp
    from repro.core.sampler import gumbel_topk_select

    ds = synthetic_ds
    n, m = ds.n_clients, 6
    cfg = _scan_cfg(1, m, sampler="poc")
    eng = ScanEngine(ds, logistic_regression(), cfg, use_masks=True)
    cell = eng.cell(seed=4, masks=np.ones((1, n), bool))
    sh = eng.run(cell)

    # replicate the in-scan draw + probe with the same key streams
    model = logistic_regression()
    params = model.init(cell["key"])
    d = min(n, max(m, cfg.poc_d_factor * m))
    skey = jax.random.fold_in(cell["sampler_key"], 0)
    logw = jnp.log(jnp.maximum(jnp.asarray(ds.sizes, jnp.float32), 1e-12))
    cand = np.asarray(gumbel_topk_select(skey, logw,
                                         jnp.ones((n,), bool), d))
    cidx = np.argsort(np.where(cand, np.arange(n), n + np.arange(n)))[:d]
    keys = jax.random.split(jax.random.fold_in(skey, 1), d)
    xs, ys = jnp.asarray(ds.x), jnp.asarray(ds.y)
    losses = []
    for i, k in zip(cidx, keys):
        b = jax.random.randint(k, (cfg.poc_probe,), 0,
                               max(int(ds.sizes[i]), 1))
        losses.append(float(model.loss(params, xs[i][b], ys[i][b])))
    want = np.sort(cidx[np.argsort(-np.asarray(losses), kind="stable")[:m]])
    np.testing.assert_array_equal(sh.sampled(0), want)


def test_poc_batches_in_run_batch(synthetic_ds):
    """PoC cells vmap-batch like every other sampler (Table 2 acceptance)."""
    ds = synthetic_ds
    eng = ScanEngine(ds, logistic_regression(),
                     _scan_cfg(8, 6, sampler="poc"))
    cells = [eng.cell(seed=s, mode=_mode("LN", ds), avail_seed=50 + s)
             for s in range(2)]
    batch = eng.run_batch(cells)
    for cell, b in zip(cells, batch):
        single = eng.run(cell)
        np.testing.assert_array_equal(b.sel, single.sel)
        np.testing.assert_allclose(b.val_loss, single.val_loss, atol=2e-6)


def test_mixed_sampler_batch_equals_per_cell(synthetic_ds):
    """THE sampler-subsystem acceptance: one vmapped program running a
    Uniform + MD + PoC + FedGS cell batch (four different sampler families
    behind the one lax.switch step) equals the four per-cell runs."""
    from repro.core.sampler_device import make_sampler_process

    ds = synthetic_ds
    h = oracle_h(ds.opt_params)
    eng = ScanEngine(ds, logistic_regression(),
                     _scan_cfg(8, 6, sampler="fedgs"))
    procs = [make_sampler_process(f, alpha=1.0)
             for f in ("uniform", "md", "poc", "fedgs")]
    cells = [eng.cell(seed=i, mode=_mode("LN", ds), sampler_process=p,
                      h=h, avail_seed=60 + i)
             for i, p in enumerate(procs)]
    batch = eng.run_batch(cells)
    for proc, cell, b in zip(procs, cells, batch):
        single = eng.run(cell)
        np.testing.assert_array_equal(b.sel, single.sel,
                                      err_msg=proc.family)
        np.testing.assert_array_equal(b.counts, single.counts)
        np.testing.assert_allclose(b.val_loss, single.val_loss, atol=2e-6)
        # every family respects the cardinality contract in-scan
        assert np.all(b.valid.sum(1) <= eng.cfg.m)
        assert np.isfinite(b.val_loss).all()


def test_mixed_sampler_cells_match_per_family_engines(synthetic_ds):
    """A cell's sampler_process overrides the engine default and reproduces
    the run a cfg.sampler=<family> engine produces (same streams, same
    program semantics) — the per-cell switch is pure dispatch."""
    ds = synthetic_ds
    h = oracle_h(ds.opt_params)
    mode = _mode("LN", ds)
    from repro.core.sampler_device import make_sampler_process
    eng_mixed = ScanEngine(ds, logistic_regression(),
                           _scan_cfg(6, 6, sampler="fedgs"))
    # md (the weighted Gumbel stream) and poc (the probe-key stream) are the
    # two branches with sampler randomness; uniform is md with equal weights
    for family in ("md", "poc"):
        eng_single = ScanEngine(ds, logistic_regression(),
                                _scan_cfg(6, 6, sampler=family))
        a = eng_mixed.run(eng_mixed.cell(
            seed=2, mode=mode, h=h,
            sampler_process=make_sampler_process(family)))
        b = eng_single.run(eng_single.cell(seed=2, mode=mode, h=h))
        np.testing.assert_array_equal(a.sel, b.sel, err_msg=family)
        np.testing.assert_allclose(a.val_loss, b.val_loss, atol=2e-6)


def test_scan_solver_backend_pallas_matches_ref(synthetic_ds):
    """ScanConfig.solver_backend="pallas" routes the in-scan Eq. 16 solve
    through the tiled solver kernels and reproduces the ref backend's
    sampled sets bit for bit (the solver-parity contract composed into the
    full scanned program)."""
    ds = synthetic_ds
    h = oracle_h(ds.opt_params)
    mode = _mode("LN", ds)
    hists = {}
    for backend in ("ref", "pallas"):
        eng = ScanEngine(ds, logistic_regression(),
                         _scan_cfg(6, 6, sampler="fedgs",
                                   solver_backend=backend))
        hists[backend] = eng.run(eng.cell(seed=0, mode=mode, alpha=1.0, h=h))
    np.testing.assert_array_equal(hists["ref"].sel, hists["pallas"].sel)
    np.testing.assert_array_equal(hists["ref"].counts,
                                  hists["pallas"].counts)
    np.testing.assert_allclose(hists["ref"].val_loss,
                               hists["pallas"].val_loss, atol=1e-6)


def test_dynamic_3dg_pallas_backend(synthetic_ds):
    """ScanConfig.graph_backend="pallas" routes the in-scan rebuild through
    the tiled kernels (interpret mode on CPU) and matches the ref backend."""
    ds = synthetic_ds
    hists = {}
    for backend in ("ref", "pallas"):
        eng = ScanEngine(ds, logistic_regression(),
                         _scan_cfg(6, 6, sampler="fedgs",
                                   graph_refresh_every=3,
                                   graph_backend=backend))
        hists[backend] = eng.run(eng.cell(seed=0, mode=_mode("LN", ds)))
    np.testing.assert_array_equal(hists["ref"].sel, hists["pallas"].sel)
    np.testing.assert_allclose(hists["ref"].val_loss,
                               hists["pallas"].val_loss, atol=1e-5)


def test_eval_every_cadence(synthetic_ds):
    """eval_every > 1 leaves NaN on off rounds, records the last round."""
    ds = synthetic_ds
    cfg = ScanConfig(rounds=7, m=6, local_steps=5, batch_size=10, lr=0.1,
                     eval_every=3, sampler="uniform", max_sweeps=16)
    eng = ScanEngine(ds, logistic_regression(), cfg)
    sh = eng.run(eng.cell(seed=0, mode=_mode("IDL", ds)))
    assert sh.rounds.tolist() == [0, 3, 6]
    assert np.isnan(sh.val_loss[1])
    assert np.isfinite(sh.best_loss)


def test_fairness_host_device_parity():
    """The jnp fairness twins (core/fairness.py) match the numpy faces on
    integer and zero-count inputs (f32 vs f64 round-off only)."""
    from repro.core.fairness import (
        count_range, count_range_device, count_variance,
        count_variance_device, gini, gini_device,
    )
    rng = np.random.default_rng(3)
    cases = [rng.integers(0, 12, 30).astype(float),
             np.zeros(17),                       # zero-sum gini guard
             np.ones(9) * 4,                     # uniform -> gini 0
             rng.random(50) * 100]
    for v in cases:
        assert float(count_variance_device(v)) == pytest.approx(
            count_variance(v), rel=1e-5, abs=1e-5)
        assert float(count_range_device(v)) == pytest.approx(
            count_range(v.astype(int)) if np.all(v == v.astype(int))
            else float(v.max() - v.min()), rel=1e-5, abs=1e-5)
        assert float(gini_device(v)) == pytest.approx(gini(v), abs=1e-5)


def test_scan_history_emits_gini(synthetic_ds):
    """ScanHistory.gini tracks the device gini of the running counts at
    every round (cross-checked against the host gini of the replayed
    selections)."""
    from repro.core.fairness import gini as gini_host
    ds = synthetic_ds
    rounds = 10
    eng = ScanEngine(ds, logistic_regression(),
                     _scan_cfg(rounds, 6, sampler="uniform"))
    sh = eng.run(eng.cell(seed=0, mode=_mode("LN", ds)))
    assert sh.gini.shape == (rounds,)
    counts = np.zeros(ds.n_clients)
    for t in range(rounds):
        counts[sh.sampled(t)] += 1
        assert sh.gini[t] == pytest.approx(gini_host(counts), abs=1e-5), t
    assert sh.gini[-1] == pytest.approx(gini_host(sh.counts), abs=1e-5)


def test_probs_table_matches_numpy_api(synthetic_ds):
    """AvailabilityMode.probs_table is the source of truth the numpy API
    wraps: table[t % period] == probs(t) for every mode."""
    ds = synthetic_ds
    for name in ("IDL", "MDF", "LDF", "YMF", "YC", "LN", "SLN"):
        mode = _mode(name, ds)
        table = mode.probs_table()
        assert table.shape == (mode.period, ds.n_clients)
        for t in (0, 3, 25, 100):
            np.testing.assert_array_equal(table[t % mode.period],
                                          mode.probs(t))
