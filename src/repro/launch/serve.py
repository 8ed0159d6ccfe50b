"""Serving entry points: the LM decode path and the federated-simulation
service (ROADMAP "simulation-as-a-service").

LM path — prefill a prompt batch, then decode tokens:

  PYTHONPATH=src python -m repro.launch.serve --arch smollm-135m --reduced \
      --batch 8 --prompt-len 64 --gen 32

Federated-simulation path — a long-lived ``SimService`` over ONE hot
``ScanEngine``: heterogeneous sweep-cell requests (mixed samplers /
availability scenarios / aggregators) batch into a single ``run_batch``
program, and per-round metrics stream back segment by segment through the
engine's donated/pipelined ``run_batch_stream`` (DESIGN.md §15) instead of
arriving post-scan.  Run from the command line, a restarted service
re-loads its XLA programs from the persistent compile cache, which lives in
``$JAX_COMPILATION_CACHE_DIR`` (default ``<repo>/.jax_cache``):

  PYTHONPATH=src python -m repro.launch.serve --fedsim --cells 4 \
      --rounds 24 --segment 8
"""
from __future__ import annotations

import argparse
import time
from dataclasses import dataclass

import numpy as np

import jax
import jax.numpy as jnp

from repro.configs.registry import get_config
from repro.launch.obs_cli import (
    add_observability_args, finish_observability, make_observability,
)
from repro.models import lm


# ------------------------------------------------- simulation-as-a-service
@dataclass
class SegmentUpdate:
    """One streamed per-request slice of a scan segment."""
    request: int               # submit() ticket
    t0: int                    # first round of the segment
    rounds: int                # segment length
    val_loss: np.ndarray       # (rounds,) — NaN off the eval cadence
    val_acc: np.ndarray        # (rounds,)
    sel: np.ndarray            # (rounds, M) sampled sets (padded)
    valid: np.ndarray          # (rounds, M)
    metrics: dict | None = None   # per-round in-scan telemetry slice
                                  # (ScanConfig.telemetry cells only)


class SimService:
    """Queue sweep-cell requests, execute them as ONE batched scan program,
    stream per-segment metrics back incrementally.

    The service owns a single ``ScanEngine``: its ``ProgramCache`` keeps the
    compiled programs hot across ``drain()`` calls (same static shapes =
    zero recompiles), and the persistent compile cache the command-line
    entry point turns on (``fed.runtime.enable_compile_cache``) keeps them
    across service restarts.  ``submit`` accepts everything
    ``ScanEngine.cell`` does — the ``lax.switch`` subsystems mean arbitrary
    sampler/availability/aggregator mixes still compile to one program.

    Observability (DESIGN.md §17): per-request queue latencies land in
    ``self.timings`` — ``first_segment_s`` (submit -> first streamed
    segment) and ``complete_s`` (submit -> reassembled history) — and on
    the returned ``ScanHistory`` as ``.request_timing``;
    ``metrics_text()`` renders service counters + the engine's runtime
    snapshot as a Prometheus text exposition."""

    def __init__(self, engine):
        self.engine = engine
        self._pending: list[tuple[int, dict]] = []
        self._next = 0
        self.histories: dict[int, object] = {}   # request -> ScanHistory
        self.timings: dict[int, dict] = {}       # request -> latency dict
        self._counters = {"requests_total": 0, "drains_total": 0,
                          "segments_streamed_total": 0,
                          "updates_streamed_total": 0,
                          "rounds_streamed_total": 0,
                          "drain_busy_seconds_total": 0.0}

    def submit(self, **cell_kwargs) -> int:
        """Queue one sweep-cell request; returns its ticket."""
        rid = self._next
        self._next += 1
        self._pending.append((rid, self.engine.cell(**cell_kwargs)))
        self.timings[rid] = {"submit_time": time.time()}
        self._counters["requests_total"] += 1
        return rid

    def _segment_metrics(self, t0: int, j: int) -> dict | None:
        """This segment's per-request telemetry slice, if the engine just
        stashed one (telemetry-off runs stream ``None``)."""
        parts = getattr(self.engine, "_tel_parts", None)
        if parts and parts[-1][0] == t0:
            return {k: v[j] for k, v in parts[-1][2].items()}
        return None

    def drain(self, *, segment: int = 0, ckpt_path=None, resume=False):
        """Run every pending request as one batched program, yielding a
        ``SegmentUpdate`` per (request, segment) as soon as that segment's
        trajectory lands on host — segment k+1 computes while k streams.
        ``segment=0`` runs the whole horizon as a single segment.  Final
        ``ScanHistory`` objects land in ``self.histories``."""
        if not self._pending:
            return
        ids = [rid for rid, _ in self._pending]
        cells = [c for _, c in self._pending]
        self._pending = []
        t_start = time.time()
        self._counters["drains_total"] += 1
        parts = []
        for t0, k, traj in self.engine.run_batch_stream(
                cells, ckpt_every=segment, ckpt_path=ckpt_path,
                resume=resume):
            parts.append(traj)
            self._counters["segments_streamed_total"] += 1
            self._counters["rounds_streamed_total"] += k * len(ids)
            now = time.time()
            for j, rid in enumerate(ids):
                self.timings[rid].setdefault(
                    "first_segment_s",
                    now - self.timings[rid]["submit_time"])
                self._counters["updates_streamed_total"] += 1
                yield SegmentUpdate(
                    request=rid, t0=t0, rounds=k,
                    val_loss=traj["val_loss"][j], val_acc=traj["val_acc"][j],
                    sel=traj["sel"][j], valid=traj["valid"][j],
                    metrics=self._segment_metrics(t0, j))
        full = jax.tree_util.tree_map(
            lambda *xs: np.concatenate(xs, axis=1), *parts)
        out = {**full, "counts": self.engine.final_counts}
        tel = self.engine._assemble_telemetry()
        done = time.time()
        self._counters["drain_busy_seconds_total"] += done - t_start
        for j, rid in enumerate(ids):
            self.timings[rid]["complete_s"] = \
                done - self.timings[rid]["submit_time"]
            hist = self.engine._to_history(out, j, telemetry=tel)
            hist.request_timing = dict(self.timings[rid])
            self.histories[rid] = hist
            if self.engine.sink is not None:
                self.engine.sink.emit(
                    "request", {"request": rid, **self.timings[rid]})

    def stats(self) -> dict:
        """Service counters merged over the engine's runtime snapshot
        (program-cache / checkpoint-writer / span counters)."""
        return {**self.engine.runtime_stats(), "service": dict(self._counters)}

    def metrics_text(self) -> str:
        """Prometheus text exposition (format 0.0.4) of the service
        counters, per-request queue latencies and the engine's runtime
        counters — scrape or dump, zero dependencies."""
        from repro.obs import render_prometheus
        eng = self.engine.runtime_stats()
        wall = max(self._counters["drain_busy_seconds_total"], 1e-9)
        fams = {
            "requests_total": {
                "type": "counter", "help": "Sweep-cell requests submitted.",
                "samples": [({}, self._counters["requests_total"])]},
            "segments_streamed_total": {
                "type": "counter", "help": "Scan segments streamed.",
                "samples": [({},
                             self._counters["segments_streamed_total"])]},
            "rounds_streamed_total": {
                "type": "counter",
                "help": "Cell-rounds streamed to clients.",
                "samples": [({}, self._counters["rounds_streamed_total"])]},
            "rounds_per_second": {
                "type": "gauge",
                "help": "Cell-rounds per busy drain second.",
                "samples": [({}, self._counters["rounds_streamed_total"]
                             / wall)]},
            "program_cache_hit_rate": {
                "type": "gauge",
                "help": "ProgramCache hits / (hits + misses).",
                "samples": [({}, eng["hits"] / max(
                    eng["hits"] + eng["misses"], 1))]},
            "compile_ms_total": {
                "type": "counter",
                "help": "Total XLA compile wall-clock (ms).",
                "samples": [({}, eng["compile_ms"])]},
            "request_queue_seconds": {
                "type": "gauge",
                "help": "submit -> first streamed segment latency.",
                "samples": [({"request": str(r)}, tm["first_segment_s"])
                            for r, tm in sorted(self.timings.items())
                            if "first_segment_s" in tm]},
            "request_complete_seconds": {
                "type": "gauge",
                "help": "submit -> reassembled history latency.",
                "samples": [({"request": str(r)}, tm["complete_s"])
                            for r, tm in sorted(self.timings.items())
                            if "complete_s" in tm]},
        }
        return render_prometheus(fams)


def _fedsim_main(args):
    from repro.core.availability_device import make_process
    from repro.data.synthetic import make_synthetic
    from repro.fed.models import logistic_regression
    from repro.fed.scan_engine import ScanConfig, ScanEngine

    ds = make_synthetic(n_clients=args.n_clients, alpha=0.5, beta=0.5,
                        seed=args.seed)
    cfg = ScanConfig(rounds=args.rounds, m=4, local_steps=2, batch_size=8,
                     eval_every=1, sampler="uniform",
                     telemetry=bool(getattr(args, "telemetry", False)))
    tracer, sink = make_observability(args)
    svc = SimService(ScanEngine(ds, logistic_regression(), cfg,
                                tracer=tracer, sink=sink))
    scenarios = ("GE", "CLUSTER", "DRIFT", "DEADLINE")
    tickets = [svc.submit(
        seed=i, avail_seed=100 + i,
        process=make_process(scenarios[i % 4], n_clients=ds.n_clients,
                             data_sizes=ds.sizes,
                             label_sets=ds.label_sets(),
                             num_labels=ds.num_classes,
                             rounds=args.rounds, seed=7 + i))
        for i in range(args.cells)]
    t0 = time.time()
    n_updates = 0
    try:
        for upd in svc.drain(segment=args.segment):
            n_updates += 1
            loss = upd.val_loss[np.isfinite(upd.val_loss)]
            print(f"req {upd.request} rounds "
                  f"[{upd.t0}, {upd.t0 + upd.rounds}) "
                  f"loss {loss[-1]:.4f}" if loss.size else
                  f"req {upd.request} rounds "
                  f"[{upd.t0}, {upd.t0 + upd.rounds})")
        wall = time.time() - t0
        st = svc.stats()
        print(f"fedsim: {len(tickets)} cells x {args.rounds} rounds, "
              f"{n_updates} streamed updates in {wall:.2f}s "
              f"({len(tickets) * args.rounds / max(wall, 1e-9):.1f} "
              f"cell-rounds/s)")
        print(f"programs: {st['misses']} built ({st['compiles']} compiles, "
              f"{st['compile_ms']:.0f} ms), {st['hits']} cache hits")
        print(svc.metrics_text(), end="")
    finally:
        trace = finish_observability(tracer, sink, args)
        if trace:
            print(f"trace: {trace}")
    return [svc.histories[t] for t in tickets]


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="smollm-135m")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--prompt-len", type=int, default=64)
    ap.add_argument("--gen", type=int, default=32)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--seed", type=int, default=0)
    # federated-simulation service mode (SimService over one hot ScanEngine)
    ap.add_argument("--fedsim", action="store_true",
                    help="serve federated sweep cells instead of LM decode")
    ap.add_argument("--cells", type=int, default=4)
    ap.add_argument("--rounds", type=int, default=24)
    ap.add_argument("--segment", type=int, default=8,
                    help="streaming segment length (0 = one segment)")
    ap.add_argument("--n-clients", type=int, default=16)
    ap.add_argument("--telemetry", action="store_true",
                    help="enable the in-scan per-round health channel "
                         "(ScanConfig.telemetry)")
    add_observability_args(ap)
    args = ap.parse_args(argv)

    if args.fedsim:
        return _fedsim_main(args)

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    key = jax.random.PRNGKey(args.seed)
    params = lm.init_params(key, cfg)

    rng = np.random.default_rng(args.seed)
    batch = {"tokens": jnp.asarray(
        rng.integers(0, cfg.vocab_size, (args.batch, args.prompt_len)), jnp.int32)}
    if cfg.family == "vlm" and cfg.n_image_tokens:
        batch["image_emb"] = jnp.asarray(
            rng.normal(0, 0.02, (args.batch, cfg.n_image_tokens, cfg.d_model)),
            jnp.dtype(cfg.dtype))
    if cfg.enc_dec:
        batch["audio_frames"] = jnp.asarray(
            rng.normal(0, 0.02, (args.batch, cfg.n_audio_frames, cfg.d_model)),
            jnp.dtype(cfg.dtype))

    # prefill builds a cache sized for prompt+gen
    total = args.prompt_len + (cfg.n_image_tokens if cfg.family == "vlm" else 0)
    max_len = total + args.gen

    prefill_j = jax.jit(lambda p, b: lm.prefill(p, cfg, b))
    decode_j = jax.jit(lambda p, t, c: lm.decode_step(p, cfg, t, c))

    t0 = time.time()
    logits, cache = prefill_j(params, batch)
    logits.block_until_ready()
    t_prefill = time.time() - t0

    # grow the cache to max_len (prefill sized it to the prompt)
    def grow(x):
        if x.ndim == 5 and x.shape[2] == total:          # (L,B,S,H,D)
            pad = [(0, 0)] * 5
            pad[2] = (0, args.gen)
            return jnp.pad(x, pad)
        return x
    cache = {k: (grow(v) if k in ("k", "v") else v) for k, v in cache.items()}

    toks = jnp.argmax(logits, -1).astype(jnp.int32)
    out = [np.asarray(toks)]
    t1 = time.time()
    for i in range(args.gen - 1):
        key, sub = jax.random.split(key)
        logits, cache = decode_j(params, toks, cache)
        if args.temperature > 0:
            toks = jax.random.categorical(sub, logits / args.temperature, -1).astype(jnp.int32)
        else:
            toks = jnp.argmax(logits, -1).astype(jnp.int32)
        out.append(np.asarray(toks))
    jax.block_until_ready(toks)
    t_decode = time.time() - t1

    gen = np.stack(out, 1)
    n_tok = gen.size
    print(f"arch={cfg.name} batch={args.batch} prompt={args.prompt_len} gen={args.gen}")
    print(f"prefill: {t_prefill:.3f}s  decode: {t_decode:.3f}s "
          f"({n_tok / max(t_decode, 1e-9):.1f} tok/s)")
    print("first sequence:", gen[0][:16].tolist())
    return gen


if __name__ == "__main__":
    from repro.fed.runtime import enable_compile_cache
    enable_compile_cache()
    main()
