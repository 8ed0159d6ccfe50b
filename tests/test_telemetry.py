"""The unified telemetry layer (DESIGN.md §17).

Pinned claims:

* BITWISE NONINTERFERENCE (assumption log #24): a ``telemetry=True`` run's
  ``ScanHistory`` fields AND its checkpoint bytes are identical to the
  ``telemetry=False`` run's, across a stateful aggregator x availability x
  fault cell mix — the health channel is output-only (no carry state,
  stripped before checkpoint);
* the per-round metrics themselves are sane: ``(T,)``/``(T, bins)``
  float32 leaves, avail_rate in [0, 1], n_selected <= m, staleness
  histogram rows sum to the panel mass, the fault cell's corruption norm
  is positive while clean cells read 0;
* a resumed run's pre-resume telemetry prefix is NaN (telemetry is
  observability, not state — it is NOT checkpointed);
* the host-side ``Tracer`` nests spans and summarizes per-name in bounded
  memory, and its spans land in the ``jax.profiler`` trace as host events
  with their attributes; the NULL_TRACER records nothing;
* ``JSONLMetricsSink`` round-trips schema-versioned events in order and
  ``read_metrics_jsonl`` rejects unknown schema versions;
* ``render_prometheus`` emits valid exposition text (TYPE/HELP + labeled
  samples);
* both engines share one ``runtime_stats()`` snapshot shape: flat
  program-cache counters plus nested checkpoint-writer and span blocks;
* ``SimService`` stamps submit -> first-segment and submit -> complete
  latency per request and serves them through ``metrics_text()``.
"""
import glob
import json
import math
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core.availability_device import make_process
from repro.fed.aggregator_device import make_aggregator_process
from repro.fed.faults_device import make_fault_process
from repro.fed.models import logistic_regression
from repro.fed.scan_engine import ScanConfig, ScanEngine, oracle_h
from repro.fed.telemetry import (
    N_STALE_BINS, NULL_TRACER, TELEMETRY_SCHEMA_VERSION, Tracer,
    fault_corruption_norm, make_tracer, round_telemetry, runtime_snapshot,
    selection_dispersion, staleness_histogram, weight_entropy,
)
from repro.obs import (
    JSONLMetricsSink, prom_families, read_metrics_jsonl, render_prometheus,
)

HIST_FIELDS = ("sel", "valid", "counts", "gini", "count_var", "val_loss",
               "val_acc")
COMBOS = [("memory", "GE"), ("fedavgm", "CLUSTER"), ("fedadam", "DRIFT")]


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


def _cells(eng, ds, rounds, agg, scenario, b=2, fault_cell=None):
    return [eng.cell(
        seed=s, process=_proc(scenario, ds, rounds, 3 + s),
        avail_seed=70 + s, h=oracle_h(ds.opt_params),
        aggregator_process=make_aggregator_process(agg),
        fault_process=(make_fault_process("sign_flip", ds.n_clients,
                                          frac=0.25)
                       if s == fault_cell else None))
        for s in range(b)]


# ------------------------------------------------------- metric reductions
class TestMetricReductions:
    def test_selection_dispersion_matches_hand_mean(self):
        h = jnp.asarray(np.random.default_rng(0).uniform(size=(6, 6)),
                        jnp.float32)
        sel = jnp.asarray([0, 2, 5, 0])
        valid = jnp.asarray([1.0, 1.0, 1.0, 0.0])
        got = float(selection_dispersion(h, sel, valid))
        idx = [0, 2, 5]
        hs = np.asarray(h)[np.ix_(idx, idx)]
        want = (hs.sum() - np.trace(hs)) / (3 * 2)
        assert got == pytest.approx(want, rel=1e-6)

    def test_dispersion_degenerate_selection_is_zero(self):
        h = jnp.ones((4, 4), jnp.float32)
        sel = jnp.asarray([1, 0, 0, 0])
        valid = jnp.asarray([1.0, 0.0, 0.0, 0.0])   # < 2 valid -> no pairs
        assert float(selection_dispersion(h, sel, valid)) == 0.0

    def test_weight_entropy_bounds(self):
        u = jnp.ones(5, jnp.float32)
        assert float(weight_entropy(u)) == pytest.approx(math.log(5),
                                                         rel=1e-5)
        spike = jnp.asarray([1.0, 0.0, 0.0], jnp.float32)
        assert float(weight_entropy(spike)) == pytest.approx(0.0, abs=1e-6)

    def test_staleness_histogram_bins_and_mass(self):
        age = jnp.asarray([0.0, 1.0, 3.0, 100.0], jnp.float32)
        hist = np.asarray(staleness_histogram(age))
        assert hist.shape == (N_STALE_BINS,) and hist.dtype == np.float32
        assert hist.sum() == pytest.approx(4.0)
        assert hist[0] == 1.0 and hist[-1] == 1.0   # 0 -> first, 100 -> last

    def test_fault_corruption_norm_zero_when_clean(self):
        f = jnp.ones((3, 7), jnp.float32)
        valid = jnp.ones(3, jnp.float32)
        assert float(fault_corruption_norm(f, f, valid)) == 0.0
        assert float(fault_corruption_norm(-f, f, valid)) > 0.0

    def test_round_telemetry_leaves_all_float32(self):
        """Every leaf float32 so resumed runs can NaN-pad the prefix."""
        n, m, p = 8, 3, 5
        params = {"w": jnp.zeros(p, jnp.float32)}
        local = {"w": jnp.ones((m, p), jnp.float32)}
        tel = round_telemetry(
            avail=jnp.ones(n, jnp.float32),
            valid=jnp.ones(m, jnp.float32),
            sel=jnp.asarray([0, 1, 2]),
            local=local, params_prev=params,
            params_new={"w": jnp.full(p, 0.1, jnp.float32)},
            weights=jnp.ones(m, jnp.float32),
            h=jnp.ones((n, n), jnp.float32),
            tau=jnp.zeros(n, jnp.float32), t=jnp.asarray(4, jnp.int32),
            fault_mag=jnp.asarray(0.5, jnp.float32))
        assert {"avail_rate", "n_selected", "update_norm_mean",
                "sampler_dispersion", "weight_entropy", "staleness_hist",
                "fault_corruption_norm"} <= set(tel)
        for k, v in tel.items():
            assert v.dtype == jnp.float32, k


# ------------------------------------------------ bitwise noninterference
@pytest.mark.parametrize("agg,scenario", COMBOS)
def test_telemetry_bitwise_noninterference(ds16, tmp_path, agg, scenario):
    """Assumption log #24: history fields and checkpoint bytes identical
    on-vs-off, with a sign-flip fault cell in the mix."""
    ds = ds16
    rounds = 6
    off = ScanEngine(ds, logistic_regression(), _cfg(rounds))
    on = ScanEngine(ds, logistic_regression(),
                    _cfg(rounds, telemetry=True))
    kw = dict(fault_cell=1)
    h_off = off.run_batch(_cells(off, ds, rounds, agg, scenario, **kw),
                          ckpt_path=str(tmp_path / "off"), ckpt_every=3)
    h_on = on.run_batch(_cells(on, ds, rounds, agg, scenario, **kw),
                        ckpt_path=str(tmp_path / "on"), ckpt_every=3)
    for i in range(2):
        for f in HIST_FIELDS:
            np.testing.assert_array_equal(
                getattr(h_on[i], f), getattr(h_off[i], f),
                err_msg=f"{agg}/{scenario} cell {i}: {f}")
    assert (tmp_path / "off.npz").read_bytes() == \
        (tmp_path / "on.npz").read_bytes(), "checkpoint bytes differ"
    assert h_off[0].telemetry is None
    assert h_on[0].telemetry is not None


def test_telemetry_content_sane(ds16):
    ds = ds16
    rounds = 5
    eng = ScanEngine(ds, logistic_regression(),
                     _cfg(rounds, telemetry=True))
    hists = eng.run_batch(_cells(eng, ds, rounds, "memory", "GE",
                                 fault_cell=1))
    clean, faulty = hists[0].telemetry, hists[1].telemetry
    assert clean["avail_rate"].shape == (rounds,)
    assert clean["staleness_hist"].shape == (rounds, N_STALE_BINS)
    assert np.all((clean["avail_rate"] >= 0) & (clean["avail_rate"] <= 1))
    assert np.all(clean["n_selected"] <= eng.cfg.m)
    assert np.all(clean["sampler_dispersion"] >= 0)
    assert np.all(clean["update_nan_frac"] == 0.0)
    # memory panel: every round's histogram carries the full N-client mass
    assert np.allclose(clean["staleness_hist"].sum(axis=1), ds.n_clients)
    # the sign-flip cell shows corruption; the clean cell reads zero
    assert np.all(clean["fault_corruption_norm"] == 0.0)
    assert faulty["fault_corruption_norm"].max() > 0.0


def test_telemetry_resume_prefix_nan(ds16, tmp_path):
    """Telemetry is NOT checkpointed: resuming from a mid-run save leaves
    the pre-resume rounds NaN while the tail is real — and the history
    fields still match the uninterrupted run bitwise."""
    ds = ds16
    rounds = 6
    ck = str(tmp_path / "ck")
    eng = ScanEngine(ds, logistic_regression(),
                     _cfg(rounds, telemetry=True))
    full = eng.run_batch(_cells(eng, ds, rounds, "memory", "GE"),
                         ckpt_path=ck, ckpt_every=3)
    # rewind the on-disk state to the mid-run save: stream and stop after
    # the first segment's checkpoint lands
    eng2 = ScanEngine(ds, logistic_regression(),
                      _cfg(rounds, telemetry=True))
    for _t0, _k, _traj in eng2.run_batch_stream(
            _cells(eng2, ds, rounds, "memory", "GE"),
            ckpt_path=ck, ckpt_every=3):
        break
    eng3 = ScanEngine(ds, logistic_regression(),
                      _cfg(rounds, telemetry=True))
    res = eng3.run_batch(_cells(eng3, ds, rounds, "memory", "GE"),
                         ckpt_path=ck, resume=True, ckpt_every=3)
    for i in range(2):
        for f in HIST_FIELDS:
            np.testing.assert_array_equal(getattr(res[i], f),
                                          getattr(full[i], f), err_msg=f)
        tel = res[i].telemetry
        assert np.all(np.isnan(tel["avail_rate"][:3]))
        assert np.all(np.isfinite(tel["avail_rate"][3:]))


def test_telemetry_streams_round_events(ds16, tmp_path):
    """The engine's sink feed: run_start / per-round round events with the
    metrics dict / segment / run_end, all loadable via read_metrics_jsonl."""
    ds = ds16
    rounds = 4
    path = str(tmp_path / "m.jsonl")
    eng = ScanEngine(ds, logistic_regression(),
                     _cfg(rounds, telemetry=True))
    eng.tracer = Tracer()
    with JSONLMetricsSink(path, run="test") as sink:
        eng.sink = sink
        eng.run_batch(_cells(eng, ds, rounds, "memory", "GE"),
                      ckpt_every=2)
    evs = read_metrics_jsonl(path)
    kinds = [e["kind"] for e in evs]
    assert kinds[0] == "run_start" and kinds[-1] == "run_end"
    rounds_evs = read_metrics_jsonl(path, kind="round")
    assert len(rounds_evs) == 2 * rounds          # per cell per round
    ev = rounds_evs[0]
    assert {"cell", "t", "metrics", "run", "seq", "wall_time"} <= set(ev)
    assert "avail_rate" in ev["metrics"]
    # spans covered the streamed run
    names = set(eng.tracer.summary())
    assert {"program_get", "dispatch_segment", "device_get",
            "metrics_emit"} <= names


# ------------------------------------------------------------------ Tracer
class TestTracer:
    def test_nested_spans_and_summary(self):
        tr = Tracer()
        with tr.span("outer", tag="x"):
            with tr.span("inner"):
                pass
            with tr.span("inner"):
                pass
        s = tr.summary()
        assert set(s) == {"inner", "outer"}
        assert s["inner"]["count"] == 2 and s["outer"]["count"] == 1
        assert s["outer"]["total_ms"] >= s["inner"]["total_ms"]
        assert s["inner"]["max_ms"] <= s["inner"]["total_ms"]

    def test_spans_land_in_the_profiler_trace(self, tmp_path):
        """An enabled tracer's spans are host events of the jax.profiler
        trace, with their attributes; the NULL_TRACER's are not."""
        jax.profiler.start_trace(str(tmp_path))
        try:
            tr = Tracer()
            with tr.span("dispatch_segment", t0=5, rounds=2):
                jnp.ones(4).block_until_ready()
            with NULL_TRACER.span("null_span"):
                pass
        finally:
            jax.profiler.stop_trace()
        evs = _host_events(tmp_path)
        (ev,) = [e for e in evs if e.name == "dispatch_segment"]
        assert dict(ev.stats) == {"t0": 5, "rounds": 2}
        assert ev.duration_ns > 0
        assert not [e for e in evs if e.name == "null_span"]

    def test_null_tracer_records_nothing(self):
        with NULL_TRACER.span("x"):
            pass
        assert NULL_TRACER.summary() == {}
        assert "spans" not in runtime_snapshot(tracer=NULL_TRACER)

    def test_summary_stays_bounded(self):
        tr = Tracer()
        for i in range(10_000):
            with tr.span(("a", "b", "c")[i % 3], i=i):
                pass
        s = tr.summary()
        assert sorted(s) == ["a", "b", "c"]
        assert sum(v["count"] for v in s.values()) == 10_000
        assert len(tr._stats) == 3

    def test_span_exception_still_recorded(self):
        tr = Tracer()
        with pytest.raises(ValueError):
            with tr.span("boom"):
                raise ValueError("x")
        assert tr.summary()["boom"]["count"] == 1

    def test_make_tracer_gating(self, tmp_path):
        assert make_tracer(None) is NULL_TRACER
        tr = make_tracer(str(tmp_path))
        assert tr.enabled and tr is not NULL_TRACER


def _host_events(trace_dir):
    """The host-thread events of the jax.profiler trace under
    ``trace_dir``."""
    from jax.profiler import ProfileData
    (path,) = glob.glob(os.path.join(str(trace_dir), "**", "*.xplane.pb"),
                        recursive=True)
    return [e for plane in ProfileData.from_file(path).planes
            if plane.name.startswith("/host:")
            for line in plane.lines for e in line.events]


# ------------------------------------------------------------------- sinks
class TestJSONLSink:
    def test_round_trip_ordered_and_schema_stamped(self, tmp_path):
        path = str(tmp_path / "m.jsonl")
        with JSONLMetricsSink(path, run="r1") as sink:
            for i in range(20):
                sink.emit("round", {"t": i})
            sink.flush()
            st = sink.stats()
        assert st["events"] == 20 and st["bytes"] > 0
        evs = read_metrics_jsonl(path)
        assert [e["payload"]["t"] if "payload" in e else e["t"]
                for e in evs] == list(range(20))
        assert all(e["schema"] == TELEMETRY_SCHEMA_VERSION for e in evs)
        assert [e["seq"] for e in evs] == list(range(20))
        assert all(e["run"] == "r1" for e in evs)

    def test_unknown_schema_rejected(self, tmp_path):
        path = tmp_path / "bad.jsonl"
        path.write_text(json.dumps({"schema": 999, "kind": "round",
                                    "seq": 0}) + "\n")
        with pytest.raises(ValueError, match="schema"):
            read_metrics_jsonl(str(path))
        assert read_metrics_jsonl(str(path), strict=False) == []

    def test_numpy_payloads_jsonable(self, tmp_path):
        path = str(tmp_path / "m.jsonl")
        with JSONLMetricsSink(path) as sink:
            sink.emit("round", {"x": np.float32(1.5),
                                "hist": np.arange(3, dtype=np.int32),
                                "nan": float("nan")})
        (ev,) = read_metrics_jsonl(path)
        assert ev["x"] == 1.5 and ev["hist"] == [0, 1, 2]
        assert ev["nan"] is None    # JSONL stays standard-parseable


class TestPrometheus:
    def test_render_exposition_format(self):
        fams = {
            "requests_total": {"type": "counter", "help": "reqs",
                               "samples": [({}, 3)]},
            "queue_seconds": {"type": "gauge", "help": "q",
                              "samples": [({"request": "0"}, 0.25),
                                          ({"request": "1"}, 0.5)]},
        }
        text = render_prometheus(fams)
        assert "# TYPE fedgs_requests_total counter" in text
        assert "fedgs_requests_total 3" in text
        assert 'fedgs_queue_seconds{request="0"} 0.25' in text
        assert text.endswith("\n")

    def test_prom_families_helper(self):
        fams = prom_families({"hits": 4, "misses": 1}, type_="counter")
        text = render_prometheus(fams, prefix="x_")
        assert "x_hits 4" in text and "# TYPE x_misses counter" in text


# ----------------------------------------------- shared runtime snapshot
def test_runtime_snapshot_shape():
    snap = runtime_snapshot(
        programs=None, writer={"submitted": 2},
        tracer=Tracer(), extra={"foo": 1})
    assert snap["telemetry_schema"] == TELEMETRY_SCHEMA_VERSION
    assert snap["checkpoint_writer"] == {"submitted": 2}
    assert snap["foo"] == 1 and "spans" in snap


def test_scan_engine_runtime_stats_nested_blocks(ds16, tmp_path):
    ds = ds16
    rounds = 4
    eng = ScanEngine(ds, logistic_regression(), _cfg(rounds))
    eng.run_batch(_cells(eng, ds, rounds, "fedavg", "GE"),
                  ckpt_path=str(tmp_path / "ck"), ckpt_every=2)
    st = eng.runtime_stats()
    # flat program-cache counters (pre-§17 shape) preserved
    assert st["misses"] >= 1 and st["compiles"] >= 1 and "size" in st
    w = st["checkpoint_writer"]
    assert w["submitted"] == w["completed"] >= 1
    assert w["queue_high_watermark"] >= 1
    assert w["blocked_ms"] >= 0 and w["write_ms"] > 0


def test_flengine_runtime_stats(ds16):
    from repro.core.availability import make_mode
    from repro.core.sampler import UniformSampler
    from repro.fed.engine import FLConfig, FLEngine
    ds = ds16
    mode = make_mode("IDL", n_clients=ds.n_clients, data_sizes=ds.sizes,
                     label_sets=ds.label_sets(),
                     num_labels=ds.num_classes, seed=7)
    cfg = FLConfig(rounds=3, sample_frac=0.25, local_steps=2,
                   batch_size=8, lr=0.1, eval_every=1, seed=0)
    eng = FLEngine(ds, logistic_regression(), UniformSampler(), mode, cfg)
    eng.run()
    st = eng.runtime_stats()
    assert st["telemetry_schema"] == TELEMETRY_SCHEMA_VERSION
    assert st["misses"] >= 2          # trainer + eval programs
    assert st["compiles"] >= 2 and st["compile_ms"] > 0
    # no checkpoint writer ran and the tracer is the NULL_TRACER, so the
    # nested blocks are absent — the flat shape stays minimal
    assert "spans" not in st and "checkpoint_writer" not in st
    eng.tracer = Tracer()
    with eng.tracer.span("probe"):
        pass
    assert eng.runtime_stats()["spans"]["probe"]["count"] == 1


# --------------------------------------------------------------- SimService
def test_sim_service_request_latency_and_metrics_text(ds16):
    from repro.launch.serve import SimService
    ds = ds16
    rounds = 4
    svc = SimService(ScanEngine(ds, logistic_regression(),
                                _cfg(rounds, telemetry=True)))
    kw = lambda i: dict(                                      # noqa: E731
        seed=i, avail_seed=70 + i, process=_proc("GE", ds, rounds, 3 + i),
        aggregator_process=make_aggregator_process("memory"))
    tickets = [svc.submit(**kw(i)) for i in range(2)]
    updates = list(svc.drain(segment=2))
    assert len(updates) == 4
    for t in tickets:
        tm = svc.histories[t].request_timing
        assert 0 <= tm["first_segment_s"] <= tm["complete_s"]
        assert svc.histories[t].telemetry is not None
    st = svc.stats()
    assert st["service"]["requests_total"] == 2
    assert st["service"]["segments_streamed_total"] == 2
    assert st["service"]["rounds_streamed_total"] == rounds * 2
    text = svc.metrics_text()
    assert "# TYPE fedgs_requests_total counter" in text
    assert "fedgs_requests_total 2" in text
    assert 'fedgs_request_queue_seconds{request="0"}' in text
    assert "fedgs_rounds_per_second" in text
    assert "fedgs_program_cache_hit_rate" in text


def test_fedsim_cli_with_observability(tmp_path, capsys):
    """serve --fedsim end-to-end with every observability knob on: JSONL
    metrics and one jax.profiler trace (the engine's host spans among its
    events) land on disk, prometheus text prints."""
    from repro.launch import serve
    mpath = tmp_path / "m.jsonl"
    tdir = tmp_path / "traces"
    hists = serve.main(["--fedsim", "--cells", "2", "--rounds", "4",
                       "--segment", "2", "--n-clients", "12",
                        "--telemetry", "--metrics-jsonl", str(mpath),
                        "--trace-dir", str(tdir)])
    assert len(hists) == 2 and hists[0].telemetry is not None
    evs = read_metrics_jsonl(str(mpath))
    assert {"run_start", "round", "request", "run_end"} <= \
        {e["kind"] for e in evs}
    evs = _host_events(tdir)
    dispatch = [e for e in evs if e.name == "dispatch_segment"]
    assert dispatch and all(e.duration_ns > 0 for e in dispatch)
    assert glob.glob(str(tdir / "**" / "perfetto_trace.json.gz"),
                     recursive=True)
    out = capsys.readouterr().out
    assert "fedgs_requests_total" in out
