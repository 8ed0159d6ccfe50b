"""The comparison that decides ``correct`` fails a broken run: the
control (the reference in bfloat16 put in the program's place), and the
program with its timed path broken underneath — a segment that returns
its state unchanged, half of each round's clients left out of the
aggregation with the mean taken over the rest, and a sampled set altered
where it is produced.  (The cells run on one chip: there is no exchange
between chips to leave out.)  An unbroken run is correct."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import check
from chipbench.data import make_dataset
from chipbench.generator import cell_specs, mode_tables
from chipbench.run import load_cell, reference_module
from chipbench.tests.conftest import DYN, run_cell

CELLS = ["paper-fedgs-static", DYN]


@pytest.mark.parametrize("workload", CELLS)
def test_unbroken_run_is_correct(tiny_root, workload):
    res = run_cell(tiny_root, workload)
    assert res["correct"] is True, res["check"]


@pytest.mark.parametrize("workload", CELLS)
def test_control_is_not_correct(tiny_root, workload):
    cell = load_cell(tiny_root, workload)
    cfg, traffic = cell["cfg"], cell["traffic"]
    refmod = reference_module(tiny_root, cfg["reference"])
    ds = make_dataset(cfg)
    specs = cell_specs(cfg, traffic, ds, 3000000123, 0,
                       mode_tables(traffic, ds))
    g = traffic["graph"]
    dynamic = g["kind"] == "dynamic"
    h = None if dynamic else np.asarray(refmod.build_h(
        jnp.asarray(ds.label_dist, jnp.float32), functional=False,
        eps=g["eps"], sigma2=g["sigma2"]))
    seg = traffic["segment_rounds"]
    ctrl = refmod.Reference(cfg, traffic, ds, dtype=jnp.bfloat16)
    from chipbench.calibrate import as_program
    prog = [as_program(ctrl.replay(sp, seg, h_static=h), seg)
            for sp in specs]
    ref = refmod.Reference(cfg, traffic, ds)
    numbers = check.compare(prog, [ref.replay(sp, seg, sets=p["sets"],
                                              h_static=h)
                                   for sp, p in zip(specs, prog)], dynamic)
    ok, rows = check.verdict(numbers,
                             check.load_limits(tiny_root, workload))
    assert not ok, rows


def _unchanged_state(monkeypatch):
    from repro.fed import scan_engine
    from repro.fed.runtime import CarryHandle
    orig = scan_engine.ScanEngine._run_segment

    def stuck(self, stacked, flags, carry, t0, seg_len):
        kept = jax.tree_util.tree_map(jnp.copy, carry.tree)
        _, traj = orig(self, stacked, flags, carry, t0, seg_len)
        return CarryHandle(kept), traj
    monkeypatch.setattr(scan_engine.ScanEngine, "_run_segment", stuck)


def _half_batch(monkeypatch):
    from repro.fed import aggregator_device as ad
    orig = ad.fedavg_combine

    def half(stacked, weights, prev=None):
        m = weights.shape[0]
        return orig(stacked, weights * (jnp.arange(m) < m // 2), prev)
    monkeypatch.setattr(ad, "fedavg_combine", half)
    orig_mem = ad.memory_scatter_reduce_ref

    def half_mem(mem, upd, sel, valid, w):
        m = valid.shape[0]
        return orig_mem(mem, upd, sel, valid & (jnp.arange(m) < m // 2), w)
    monkeypatch.setattr(ad, "memory_scatter_reduce_ref", half_mem)
    from repro.kernels import ops
    orig_pallas = ops.memory_aggregate

    def half_pallas(mem, upd, sel, valid, w, **kw):
        m = valid.shape[0]
        return orig_pallas(mem, upd, sel, valid & (jnp.arange(m) < m // 2),
                           w, **kw)
    monkeypatch.setattr(ops, "memory_aggregate", half_pallas)


def _altered_set(monkeypatch):
    from repro.core import sampler_device as sd
    orig = sd.fedgs_select

    def altered(h, counts, avail, alpha, **kw):
        s = orig(h, counts, avail, alpha, **kw)
        spare = ~s & avail
        most = jnp.argmax(jnp.where(spare, counts, -1.0))
        return jnp.where(spare.any(), s.at[jnp.argmax(s)].set(False)
                         .at[most].set(True), s)
    monkeypatch.setattr(sd, "fedgs_select", altered)


@pytest.mark.parametrize("fault", [_unchanged_state, _half_batch,
                                   _altered_set],
                         ids=["unchanged_state", "half_batch", "altered_set"])
@pytest.mark.parametrize("workload", CELLS)
def test_broken_program_is_not_correct(tiny_root, monkeypatch, fault,
                                       workload):
    fault(monkeypatch)
    res = run_cell(tiny_root, workload)
    assert res["correct"] is False, res["check"]
