"""The zero-copy engine runtime (DESIGN.md §15).

Pinned claims:

* donated + pipelined segmented ``run_batch`` is bitwise equal to the
  legacy blocking path (``donate_carry=False, async_pipeline=False``) at
  ``ckpt_every=1``, across every stateful aggregator x availability
  family — and decisions-bitwise vs the fused single program (§13);
* use-after-donation is a LOUD error: a consumed ``CarryHandle`` raises on
  any access, at both the unit level and through ``ScanEngine.run_segment``;
* the ``ProgramCache`` LRU counts hits/misses/evictions/compiles and
  bounds the program set (the old ``_jits`` dict grew unboundedly);
* the ``AsyncCheckpointWriter`` preserves submission order and re-raises
  worker errors instead of dropping them;
* the persistent XLA cache, placed through ``JAX_COMPILATION_CACHE_DIR``
  (default ``<repo>/.jax_cache``), populates and changes no results;
* ``run_batch_stream`` yields segments incrementally, and the
  ``SimService`` front-end streams per-request updates that reassemble to
  the exact ``run_batch`` histories;
* (slow, 8 devices) the N=10^5 datacenter cell LOWERS on a (1, 8) silo
  mesh with the memory panel sharded to N/8 rows — compile-only, the
  (N, N) graph never materializes (the PR 6 ROADMAP leftover).
"""
import os

import numpy as np
import pytest

import jax

from repro.core.availability_device import make_process
from repro.fed.aggregator_device import make_aggregator_process
from repro.fed.models import logistic_regression
from repro.fed.runtime import (
    AsyncCheckpointWriter, CarryHandle, ProgramCache, compile_cache_dir,
    enable_compile_cache,
)
from repro.fed.scan_engine import ScanConfig, ScanEngine

needs8 = pytest.mark.skipif(
    jax.device_count() < 8,
    reason="needs 8 devices: export "
           "XLA_FLAGS=--xla_force_host_platform_device_count=8 before jax "
           "initializes (the CI shard job does)")

HIST_FIELDS = ("sel", "valid", "counts", "gini", "count_var", "val_loss",
               "val_acc")
COMBOS = [("fedavgm", "GE"), ("fedadam", "CLUSTER"),
          ("fedprox_w", "DRIFT"), ("memory", "DEADLINE")]


@pytest.fixture(scope="module")
def ds16():
    from repro.data.synthetic import make_synthetic
    return make_synthetic(n_clients=16, alpha=0.5, beta=0.5, seed=0)


def _proc(name, ds, rounds, seed=7):
    return make_process(name, n_clients=ds.n_clients, data_sizes=ds.sizes,
                        label_sets=ds.label_sets(),
                        num_labels=ds.num_classes, rounds=rounds, seed=seed)


def _cfg(rounds, **kw):
    return ScanConfig(rounds=rounds, m=4, local_steps=2, batch_size=8,
                      lr=0.1, eval_every=1, sampler="uniform", **kw)


def _cells(eng, ds, rounds, agg, scenario, b=2):
    return [eng.cell(seed=s, process=_proc(scenario, ds, rounds, 3 + s),
                     avail_seed=70 + s,
                     aggregator_process=make_aggregator_process(agg))
            for s in range(b)]


# ------------------------------------------------------------ ProgramCache
class TestProgramCache:
    def test_lru_eviction_and_counters(self):
        pc = ProgramCache(maxsize=2)
        built = []

        def mk(tag):
            def build():
                built.append(tag)
                return lambda: tag
            return build

        assert pc.get("a", mk("a"))() == "a"
        assert pc.get("b", mk("b"))() == "b"
        assert pc.get("a", mk("a"))() == "a"       # hit, refreshes a
        assert pc.get("c", mk("c"))() == "c"       # evicts b (LRU)
        assert "b" not in pc and "a" in pc and "c" in pc
        pc.get("b", mk("b"))                        # rebuild b
        st = pc.stats()
        assert built == ["a", "b", "c", "b"]
        assert (st["hits"], st["misses"], st["evictions"]) == (1, 4, 2)
        assert st["size"] == len(pc) == 2

    def test_maxsize_validated(self):
        with pytest.raises(ValueError):
            ProgramCache(maxsize=0)
        with pytest.raises(ValueError):
            ScanConfig(program_cache_size=0)

    def test_compile_counter_on_jitted_fn(self):
        pc = ProgramCache()
        f = pc.get("k", lambda: jax.jit(lambda x: x * 2))
        assert pc.stats()["compiles"] == 0
        f(np.float32(3.0))                          # first call compiles
        assert pc.stats()["compiles"] == 1
        assert pc.stats()["compile_ms"] > 0
        f(np.float32(4.0))                          # steady state
        assert pc.stats()["compiles"] == 1


# ------------------------------------------------------------- CarryHandle
class TestCarryHandle:
    def test_consume_once(self):
        h = CarryHandle({"x": 1})
        assert h.alive and h.tree == {"x": 1}
        assert h.consume() == {"x": 1}
        assert not h.alive
        with pytest.raises(RuntimeError, match="use-after-donation"):
            _ = h.tree
        with pytest.raises(RuntimeError, match="use-after-donation"):
            h.consume()


# --------------------------------------------------- AsyncCheckpointWriter
class TestAsyncCheckpointWriter:
    def test_ordered_writes(self):
        seen = []
        with AsyncCheckpointWriter() as w:
            for i in range(5):
                w.submit(seen.append, i)
            w.flush()
        assert seen == [0, 1, 2, 3, 4]

    def test_error_surfaces_on_close(self):
        w = AsyncCheckpointWriter()
        w.submit(lambda: 1 / 0)
        with pytest.raises(RuntimeError, match="checkpoint write failed"):
            w.close()

    def test_error_is_fail_fast(self):
        seen = []
        w = AsyncCheckpointWriter()
        w.submit(lambda: 1 / 0)
        with pytest.raises(RuntimeError, match="checkpoint write failed"):
            w.flush()
        # after the first error the worker is still alive for close()
        w.submit(seen.append, 1)
        w.close()
        assert seen == [1]


# -------------------------------------------------- engine runtime surface
def test_run_segment_use_after_donation(ds16):
    rounds = 4
    eng = ScanEngine(ds16, logistic_regression(), _cfg(rounds))
    cells = _cells(eng, ds16, rounds, "memory", "GE")
    h0 = eng.init_carry(cells)
    h1, traj = eng.run_segment(cells, h0, 0, 2)
    assert not h0.alive and h1.alive
    with pytest.raises(RuntimeError, match="use-after-donation"):
        eng.run_segment(cells, h0, 2, 2)
    # the returned handle chains on fine
    h2, _ = eng.run_segment(cells, h1, 2, 2)
    assert h2.alive
    # jax-level donation backs the handle: on backends that implement
    # donation the consumed device buffers really are gone
    if eng.cfg.donate_carry:
        assert not h1.alive


def test_runtime_stats_counters(ds16):
    rounds = 4
    eng = ScanEngine(ds16, logistic_regression(), _cfg(rounds))
    cells = _cells(eng, ds16, rounds, "fedavg", "GE")
    eng.run_batch(cells)
    st = eng.runtime_stats()
    assert st["misses"] == st["size"] == 1 and st["compiles"] == 1
    assert st["compile_ms"] > 0
    eng.run_batch(cells)                       # cache hit, no new compile
    st = eng.runtime_stats()
    assert st["hits"] == 1 and st["compiles"] == 1


@pytest.mark.parametrize("agg,scenario", COMBOS)
def test_donated_pipelined_bitwise_vs_legacy(ds16, tmp_path, agg, scenario):
    """The tentpole parity claim: donated + pipelined segmented run_batch
    at ckpt_every=1 is bitwise equal to the legacy blocking non-donated
    path, for every stateful aggregator x availability family — and
    decisions-bitwise (evals to 2e-6) vs the fused single program."""
    ds = ds16
    rounds = 5
    new = ScanEngine(ds, logistic_regression(), _cfg(rounds))
    assert new.cfg.donate_carry and new.cfg.async_pipeline   # the defaults
    legacy = ScanEngine(ds, logistic_regression(),
                        _cfg(rounds, donate_carry=False,
                             async_pipeline=False))
    fused = new.run_batch(_cells(new, ds, rounds, agg, scenario))
    got = new.run_batch(_cells(new, ds, rounds, agg, scenario),
                        ckpt_path=str(tmp_path / "a"), ckpt_every=1)
    ref = legacy.run_batch(_cells(legacy, ds, rounds, agg, scenario),
                           ckpt_path=str(tmp_path / "b"), ckpt_every=1)
    for i in range(2):
        for f in HIST_FIELDS:
            np.testing.assert_array_equal(
                getattr(got[i], f), getattr(ref[i], f),
                err_msg=f"{agg}/{scenario} cell {i}: {f}")
        for f in ("sel", "valid", "counts"):
            np.testing.assert_array_equal(
                getattr(got[i], f), getattr(fused[i], f),
                err_msg=f"{agg}/{scenario} fused cell {i}: {f}")
        np.testing.assert_allclose(got[i].val_loss, fused[i].val_loss,
                                   atol=2e-6)


def test_stream_yields_segments_incrementally(ds16):
    ds = ds16
    rounds = 6
    eng = ScanEngine(ds, logistic_regression(), _cfg(rounds))
    cells = _cells(eng, ds, rounds, "fedavgm", "GE")
    segs = list(eng.run_batch_stream(cells, ckpt_every=4))
    assert [(t0, k) for t0, k, _ in segs] == [(0, 4), (4, 2)]
    for _, k, traj in segs:
        assert traj["sel"].shape[:2] == (len(cells), k)
        assert isinstance(traj["sel"], np.ndarray)
    # stitched stream == plain segmented run, and final state is exposed
    assert eng.params is not None and eng.final_counts.shape == (
        len(cells), ds.n_clients)
    whole = eng.run_batch(cells, ckpt_every=4)
    sel = np.concatenate([t["sel"] for _, _, t in segs], axis=1)
    np.testing.assert_array_equal(sel[0], whole[0].sel)


def test_ckpt_every_without_path_segments(ds16):
    """ckpt_every with NO ckpt_path streams in segments (it used to run
    fused silently) — decisions stay bitwise vs fused."""
    ds = ds16
    rounds = 6
    eng = ScanEngine(ds, logistic_regression(), _cfg(rounds))
    fused = eng.run_batch(_cells(eng, ds, rounds, "fedadam", "CLUSTER"))
    seg = eng.run_batch(_cells(eng, ds, rounds, "fedadam", "CLUSTER"),
                        ckpt_every=2)
    for f in ("sel", "valid", "counts"):
        np.testing.assert_array_equal(getattr(seg[0], f),
                                      getattr(fused[0], f), err_msg=f)


def test_compile_cache_dir_populates_and_preserves_results(ds16, tmp_path,
                                                           monkeypatch):
    from jax.experimental.compilation_cache import compilation_cache as cc
    ds = ds16
    rounds = 4
    cache = str(tmp_path / "xla-cache")
    plain = ScanEngine(ds, logistic_regression(), _cfg(rounds))
    a = plain.run_batch(_cells(plain, ds, rounds, "fedavg", "GE"))
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", cache)
    saved = {k: getattr(jax.config, k) for k in (
        "jax_compilation_cache_dir",
        "jax_persistent_cache_min_compile_time_secs",
        "jax_persistent_cache_min_entry_size_bytes")}
    try:
        assert enable_compile_cache() == cache
        cached = ScanEngine(ds, logistic_regression(), _cfg(rounds))
        b = cached.run_batch(_cells(cached, ds, rounds, "fedavg", "GE"))
    finally:
        # later tests in this process must not write into tmp_path
        cc.reset_cache()
        for k, v in saved.items():
            jax.config.update(k, v)
    for f in HIST_FIELDS:
        np.testing.assert_array_equal(getattr(a[0], f), getattr(b[0], f),
                                      err_msg=f)
    assert os.path.isdir(cache) and os.listdir(cache), \
        "persistent compile cache left empty"


def test_compile_cache_dir_defaults_to_repo(monkeypatch):
    """With JAX_COMPILATION_CACHE_DIR unset the cache resolves to the fixed
    ``<repo>/.jax_cache`` (resolving creates nothing)."""
    import pathlib
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    repo = pathlib.Path(__file__).resolve().parents[1]
    assert compile_cache_dir() == str(repo / ".jax_cache")


# --------------------------------------------------------------- SimService
def test_sim_service_streams_and_matches_run_batch(ds16):
    from repro.launch.serve import SimService
    ds = ds16
    rounds = 6
    svc = SimService(ScanEngine(ds, logistic_regression(), _cfg(rounds)))
    ref_eng = ScanEngine(ds, logistic_regression(), _cfg(rounds))
    kw = lambda i: dict(                                      # noqa: E731
        seed=i, avail_seed=70 + i,
        process=_proc(("GE", "DEADLINE")[i % 2], ds, rounds, 3 + i),
        aggregator_process=make_aggregator_process(
            ("memory", "fedavgm")[i % 2]))
    tickets = [svc.submit(**kw(i)) for i in range(2)]
    updates = list(svc.drain(segment=3))
    # one update per (request, segment), tagged with the right windows
    assert [(u.request, u.t0, u.rounds) for u in updates] == \
        [(0, 0, 3), (1, 0, 3), (0, 3, 3), (1, 3, 3)]
    ref = ref_eng.run_batch([ref_eng.cell(**kw(i)) for i in range(2)],
                            ckpt_every=3)
    for i, t in enumerate(tickets):
        hist = svc.histories[t]
        for f in HIST_FIELDS:
            np.testing.assert_array_equal(getattr(hist, f),
                                          getattr(ref[i], f), err_msg=f)
        # streamed slices reassemble to the final history
        vl = np.concatenate([u.val_loss for u in updates
                             if u.request == t])
        np.testing.assert_array_equal(vl, hist.val_loss)


def test_serve_fedsim_entry_runs(capsys):
    from repro.launch import serve
    hists = serve.main(["--fedsim", "--cells", "2", "--rounds", "4",
                        "--segment", "2", "--n-clients", "12"])
    assert len(hists) == 2 and hists[0].val_loss.shape == (4,)
    out = capsys.readouterr().out
    assert "fedsim: 2 cells x 4 rounds" in out


# -------------------------------------------- datacenter compile-only dry-run
@pytest.mark.slow
@needs8
def test_datacenter_cell_dryrun_lowering():
    """The N=10^5 silo-axis proof (compile-only): the cell lowers fully
    abstract — the (N, N) graph H (40 GB) never materializes — and the
    scan carry stays silo-sharded: memory panel (N/8, P) rows per device,
    total per-cell carry (excl H) under 4 MB.  A regression that grows the
    carry (e.g. the panel going global again) fails these pins."""
    import math

    from repro.launch.fedsim import datacenter_cell_dryrun

    n = 100_000
    lowered, carry = datacenter_cell_dryrun(n_clients=n, mesh=(1, 8))
    assert carry["agg"]["mem"].shape == (1, n // 8, 36)     # silo-sharded
    assert carry["agg"]["tau"].shape == (1, n)              # tau stays global
    assert carry["h"].shape == (1, n, n)                    # abstract only
    leaves = jax.tree_util.tree_leaves(carry)
    bytes_excl_h = sum(math.prod(x.shape) * x.dtype.itemsize
                       for x in leaves) - n * n * 4
    assert bytes_excl_h < 4_000_000, f"carry grew: {bytes_excl_h} bytes"
    hlo = lowered.as_text()
    assert len(hlo) > 0 and "100000" in hlo
