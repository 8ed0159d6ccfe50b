"""Device time per stage of the round step: the stage map read from the
HLO of the live segment executable of a tiny CPU cell, and the five
readers on a synthetic stretch."""
import gc
import re

import numpy as np
import pytest

from chipbench import stages
from chipbench.tests.conftest import make_root
from chipbench.tests.test_readers import ctx, reader

READERS = {"local_train_ms_per_round": "local_train",
           "sampler_ms_per_round": "sampler",
           "aggregate_ms_per_round": "aggregate",
           "eval_ms_per_round": "eval",
           "unstaged_ms_per_round": None}
SMAP = {"fusion.1": "local_train", "copy.2": "local_train",
        "sort.3": "sampler", "fusion.4": "aggregate", "reduce.5": "eval",
        "fusion.6": "availability"}
OPS = [["%fusion.1 = f32[8] fusion(...)", 0.0, 4e6],
       ["%copy.2 = f32[8] copy(...)", 4e6, 1e6],
       ["%sort.3 = f32[8] sort(...)", 5e6, 2e6],
       ["%fusion.4 = f32[8] fusion(...)", 7e6, 3e6],
       ["%reduce.5 = f32[8] reduce(...)", 10e6, 5e5],
       ["%fusion.6 = f32[8] fusion(...)", 10.5e6, 5e5],
       ["%copy.7 = f32[8] copy(...)", 11e6, 1.5e6]]


def staged_ctx(ops=OPS, smap=SMAP, **kw):
    return dict(ctx(ops, **kw), **{stages.KEY: smap})


@pytest.mark.parametrize("name", sorted(READERS))
def test_stage_reader_sums_its_stage_per_round(name):
    c = staged_ctx(segments=2)            # 2 segments of 5 rounds
    want = {"local_train": 5.0, "sampler": 2.0, "aggregate": 3.0,
            "eval": 0.5, None: 1.5}[READERS[name]]
    assert reader(name).read(c) == pytest.approx(want / 10)


@pytest.mark.parametrize("name", sorted(READERS))
def test_stage_reader_is_silent_without_a_stretch_or_stages(name):
    r = reader(name)
    assert r.read(staged_ctx(segments=0)) is None
    assert r.read(staged_ctx(ops=[])) is None
    # a program that names no stage (or no live segment executable)
    assert r.read(staged_ctx(smap={})) is None


def test_stage_readers_split_every_op():
    c = staged_ctx(segments=2)
    total = sum(d for _, _, d in OPS) * 1e-6 / 10
    named = sum(reader(n).read(c) for n in READERS)
    avail = stages.ms_per_round(c, "availability")
    assert named + avail == pytest.approx(total)


def test_stage_names_are_the_programs():
    from repro.fed.scan_engine import ROUND_STAGES
    assert stages.STAGES == ROUND_STAGES


def test_module_stages_inherits_the_callers_stage():
    text = """HloModule jit_run_segment

%body (p: f32[]) -> f32[] {
  %p = f32[] parameter(0)
  ROOT %copy.1 = f32[] copy(%p)
}

%cond (q: f32[]) -> pred[] {
  %q = f32[] parameter(0)
  ROOT %lt.1 = pred[] compare(%q, %q), direction=LT
}

ENTRY %main (x: f32[]) -> f32[] {
  %x = f32[] parameter(0)
  %w = f32[] while(%x), condition=%cond, body=%body, metadata={op_name="jit(run_segment)/while/body/closed_call/local_train/while"}
  ROOT %copy.2 = f32[] copy(%w)
}
"""
    got = stages.module_stages(text)
    assert got["copy.1"] == got["lt.1"] == got["w"] == "local_train"
    assert got["copy.2"] is None and got["x"] is None
    assert stages.build([text, text.replace("local_train", "eval")]) == {}


# ------------------------------------------ a live segment executable
@pytest.fixture(scope="module")
def live_hlo(tmp_path_factory):
    """The HLO of the tiny paper cell's segment program, compiled and run
    once on the CPU as the harness's set-up does; the engine stays alive
    while the tests read it."""
    import jax

    from chipbench import run
    from chipbench.data import make_dataset
    from chipbench.generator import (
        cell_specs, engine_cells, engine_config, fed_dataset, fed_model,
        mode_tables,
    )
    from repro.fed.scan_engine import ScanEngine
    root = make_root(str(tmp_path_factory.mktemp("stages") / "bench"))
    cell = run.load_cell(root, "paper-fedgs-static")
    cfg, traffic = cell["cfg"], cell["traffic"]
    ds = make_dataset(cfg)
    g = traffic["graph"]
    h = np.asarray(run.reference_module(root, cfg["reference"]).build_h(
        jax.numpy.asarray(ds.label_dist, jax.numpy.float32),
        functional=False, eps=g["eps"], sigma2=g["sigma2"]))
    gc.collect()
    jax.clear_caches()
    eng = ScanEngine(fed_dataset(ds), fed_model(root, cfg, ds),
                     engine_config(cfg, traffic), use_masks=True)
    specs = cell_specs(cfg, traffic, ds, 3000000123, 0,
                       mode_tables(traffic, ds))
    run.first_segment(eng, engine_cells(eng, traffic, specs, h),
                      int(traffic["segment_rounds"]), False)
    texts = stages.segment_hlo()
    yield texts
    del eng


def test_stage_map_of_a_live_segment_executable(live_hlo):
    assert live_hlo
    c = {}
    smap = stages.stage_map(c)
    assert c[stages.KEY] is smap
    assert {"local_train", "sampler", "aggregate", "eval"} <= \
        set(smap.values())


def _executed(text):
    """{computation: [(name, line)]} of the computations the device runs
    as ops: the entry, and the bodies, conditions and branches of its
    control flow (fused and reducer computations are no ops of their
    own)."""
    comps, cur, entry = {}, None, None
    for line in text.splitlines():
        h = stages._HEADER.match(line)
        if h:
            cur = h.group(1)
            comps[cur] = []
            entry = cur if line.startswith("ENTRY") else entry
        elif cur is not None and stages._INSTR.match(line):
            comps[cur].append((stages._INSTR.match(line).group(1), line))
    return comps, entry


def test_no_op_of_the_scan_body_is_unstaged_but_loop_control(live_hlo):
    """Every op that the step's own code emits in the scan's body lies in
    a stage; what is left is the scan's loop control (its counter, the
    stacking of the trajectory, the body's constants) and what the
    compiler adds without metadata (the copies of the loop carry)."""
    free = {"parameter", "constant", "get-tuple-element", "tuple",
            "bitcast"}
    checked = 0
    for text in live_hlo:
        st = stages.module_stages(text)
        comps, entry = _executed(text)
        outer = [re.search(r"body=%?([^\s,}]+)", line).group(1)
                 for _, line in comps[entry] if " while(" in line]
        assert len(outer) == 1            # the scan over the segment
        for name, line in comps[outer[0]]:
            op = re.match(r"^\s+(?:ROOT\s+)?%?\S+ = .*? ([a-z][\w-]*)\(",
                          line).group(1)
            if op in free:
                continue
            checked += 1
            path = stages._PATH.search(line)
            if st[name] is None and path is not None:
                rest = path.group(1).split("while/body/", 1)[1]
                assert "/" not in rest, (name, path.group(1))
    assert checked > 20


def test_building_the_map_compiles_nothing(live_hlo):
    import jax
    seen = []

    def listen(event, *_a, **_k):
        seen.append(event)
    jax.monitoring.register_event_duration_secs_listener(listen)
    try:
        assert stages.build(stages.segment_hlo())
        jax.jit(lambda x: x + 1)(np.float32(1.0))     # the listener hears
    finally:
        jax.monitoring.unregister_event_duration_listener(listen)
    compiles = [e for e in seen if "backend_compile" in e]
    assert len(compiles) == 1


def test_readers_find_the_live_program_by_themselves(live_hlo):
    smap = stages.stage_map({})
    name = next(k for k, v in smap.items() if v == "local_train")
    c = ctx([[f"%{name} = f32[] fusion()", 0.0, 3e6]], segments=1)
    assert reader("local_train_ms_per_round").read(c) == pytest.approx(0.6)
    assert reader("unstaged_ms_per_round").read(c) == 0.0
