"""The trace reduction on a small recorded trace: the traced stretch of
a run on a TPU v5e of an earlier cell (N=4,096, the dynamic 3DG), kept
to its module events, the benchmark's host spans and the device ops of
2 ms or more
(``data/trace_xdev_small.json``).  Every number is checked against a
plain recount of the same events."""
import os

import numpy as np
import pytest

from chipbench import trace
from chipbench.run import SPAN_NAMES

DATA = os.path.join(os.path.dirname(__file__), "data",
                    "trace_xdev_small.json")


@pytest.fixture(scope="module")
def tr():
    return trace.read(DATA)


def brute_leaves(ops):
    return [e for e in ops
            if not any(f is not e and e[1] <= f[1]
                       and f[1] + f[2] <= e[1] + e[2] for f in ops)]


def test_stretch_is_whole_segments_inside_the_span(tr):
    a, b = trace.span(tr, "traced_stretch")
    mods = sorted(m for m in tr["device"][0]["modules"]
                  if "run_segment" in m[0] and a <= m[1]
                  and m[1] + m[2] <= b)
    st = trace.stretch(tr, "traced_stretch")
    assert st["segments"] == len(mods) - 2 == 3
    assert st["a"] == mods[1][1]
    assert st["b"] == mods[-2][1] + mods[-2][2]
    assert st["window_s"] == pytest.approx((st["b"] - st["a"]) * 1e-9)


def test_busy_time_is_the_union_of_leaf_ops(tr):
    st = trace.stretch(tr, "traced_stretch")
    a, b = st["a"], st["b"]
    leaves = brute_leaves(tr["device"][0]["ops"])
    assert sorted(map(tuple, trace.leaves(tr["device"][0]["ops"]))) == \
        sorted(map(tuple, leaves))
    # a 10-microsecond timeline of the stretch
    res = 1e4
    on = np.zeros(int((b - a) / res) + 1, bool)
    for _, s, d in leaves:
        lo, hi = max(s, a), min(s + d, b)
        if hi > lo:
            on[int((lo - a) / res):int(np.ceil((hi - a) / res))] = True
    assert st["busy_s"] == pytest.approx(on.sum() * res * 1e-9, rel=2e-3)
    gaps = trace.idle_gaps(st["ops"], a, b, tr["host"], SPAN_NAMES,
                           top=10 ** 6)
    assert sum(g for _, g in gaps) == pytest.approx(
        st["window_s"] - st["busy_s"], rel=1e-9)
    assert all(lbl in SPAN_NAMES + ("(none)",) for lbl, _ in gaps)


def test_op_totals_sum_numbered_instances(tr):
    st = trace.stretch(tr, "traced_stretch")
    top = trace.op_totals(st["ops"])
    name = top[0][0]
    want = sum(min(s + d, st["b"]) - max(s, st["a"])
               for n, s, d in brute_leaves(tr["device"][0]["ops"])
               if n.startswith(name)
               and min(s + d, st["b"]) > max(s, st["a"]))
    assert name == "floyd_warshall_pallas"
    assert top[0][1] == pytest.approx(want * 1e-9)
    assert [t for _, t in top] == sorted((t for _, t in top), reverse=True)


def test_idle_share_reader_on_the_recorded_stretch(tr):
    from chipbench.tests.test_readers import ctx, reader
    st = trace.stretch(tr, "traced_stretch")
    c = dict(ctx(st["ops"], busy_s=st["busy_s"], window_s=st["window_s"]),
             segments=st["segments"])
    assert reader("device_idle_share").read(c) == pytest.approx(
        100 * (1 - st["busy_s"] / st["window_s"]))
