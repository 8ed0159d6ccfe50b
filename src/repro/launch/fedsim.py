"""Dry-run of the FEDERATED ROUND ITSELF on the production mesh.

The arch × shape dry-runs prove the model zoo lowers; this proves the
*paper's own program* — one FedGS communication round over thousands of
clients — lowers and compiles multi-pod:

  round_step(global_params, client_data, sel_weights, lr)
    -> vmap'd E-step local SGD over M sampled clients (clients sharded over
       the dp axes = the federated-silo axis, DESIGN.md §3)
    -> Eq. 18 weighted aggregation (an all-reduce over the client shards)

plus the server-side 3DG pipeline at datacenter client counts (similarity +
Floyd–Warshall + the QUBO solve for N clients), lowered as one jit program.

  PYTHONPATH=src python -m repro.launch.fedsim [--clients 4096] [--multi-pod]

Results: benchmarks/results/dryrun/fedsim__*.json (same record schema).
"""
import os
if "xla_force_host_platform_device_count" not in os.environ.get("XLA_FLAGS",
                                                                ""):
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + " --xla_force_host_platform_device_count=512").strip()

import argparse
import json
import time

import numpy as np

import jax
import jax.numpy as jnp

from repro.launch.dryrun import RESULTS_DIR, PEAK_FLOPS, HBM_BW, ICI_BW, _mem_dict
from repro.launch.mesh import make_production_mesh
from repro.utils.hlo import analyze as hlo_analyze

DIM, CLASSES = 60, 10          # the paper's Synthetic(0.5, 0.5) model


def round_step_factory(local_steps: int, batch: int):
    """One federated round: vmap'd local logreg training + Eq. 18 aggregate."""

    def local(global_params, x, y, n_k, lr, key):
        def loss(p, xb, yb):
            logits = xb @ p["w"] + p["b"]
            logp = jax.nn.log_softmax(logits)
            return -jnp.mean(jnp.take_along_axis(logp, yb[:, None], 1))

        def step(p, k):
            idx = jax.random.randint(k, (batch,), 0, jnp.maximum(n_k, 1))
            g = jax.grad(loss)(p, x[idx], y[idx])
            return jax.tree_util.tree_map(lambda a, b: a - lr * b, p, g), None

        p, _ = jax.lax.scan(step, global_params,
                            jax.random.split(key, local_steps))
        return p

    def round_step(global_params, xs, ys, sizes, lr, keys):
        from repro.fed.aggregator_device import fedavg_combine
        locals_ = jax.vmap(local, in_axes=(None, 0, 0, 0, None, 0))(
            global_params, xs, ys, sizes, lr, keys)
        # the shared Eq. 18 combine (zero-weight guard = params kept)
        return fedavg_combine(locals_, sizes.astype(jnp.float32),
                              global_params)

    return round_step


def graph_pipeline(feats, counts, avail, alpha, m_sel, max_sweeps: int = 32,
                   *, eps: float = 0.1, sigma2: float = 0.01,
                   backend: str = "ref", solver_backend: str = "ref"):
    """Server-side FedGS pipeline as ONE jit program: V -> R -> H -> solve.

    Pure composition of the shared device-native 3DG stages
    (``core.graph_device``) with the shared Q-construction + solver
    (``core.sampler_device.fedgs_select``) — NaN-safe by construction.
    ``backend`` routes the graph build, ``solver_backend`` the Eq. 16
    solve (fused Q build + tiled greedy/swap kernels at datacenter N).
    """
    from repro.core.graph_device import GraphConfig, build_h
    from repro.core.sampler_device import fedgs_select
    h = build_h(feats, GraphConfig(eps=eps, sigma2=sigma2), backend=backend)
    return fedgs_select(h, counts, avail, jnp.float32(alpha),
                        m=m_sel, max_sweeps=max_sweeps,
                        backend=solver_backend)


def aggregator_program(aggregator: str, n_clients: int, m_sel: int, *,
                       backend: str = "ref"):
    """The server-update apply as ONE jit-lowerable program at datacenter
    client counts: any ``fed.aggregator_device`` family over the logreg
    params (for ``memory``, the (N, P) panel scatter + rectified reduction
    with ``backend`` routing the fused Pallas kernel).  Returns the jitted
    fn and its abstract (state, upd, w, s, avail, t) argument specs."""
    from repro.fed.aggregator_device import (
        init_agg_state, make_aggregator_process, make_aggregator_step,
    )
    f32, b8 = jnp.float32, jnp.bool_
    gp = {"w": jax.ShapeDtypeStruct((DIM, CLASSES), f32),
          "b": jax.ShapeDtypeStruct((CLASSES,), f32)}
    proc = make_aggregator_process(aggregator)
    # lower the named family's branch with the state it actually reads —
    # non-memory families carry a 0-row panel spec, so the recorded
    # argument/memory stats are the family's own, not the union's
    step = make_aggregator_step(n_clients, m_sel, gp, backend=backend,
                                family=proc.family)
    aparams = proc.params()
    key = jax.random.PRNGKey(0)

    def apply(state, upd, wts, s, avail, t):
        return step(aparams, state, key, upd, wts, s, avail, t)

    rows = n_clients if proc.family == "memory" else 0
    state = jax.eval_shape(
        lambda p: init_agg_state(p, n_clients, memory_rows=rows), gp)
    args = (state,
            {"w": jax.ShapeDtypeStruct((m_sel, DIM, CLASSES), f32),
             "b": jax.ShapeDtypeStruct((m_sel, CLASSES), f32)},
            jax.ShapeDtypeStruct((m_sel,), f32),
            jax.ShapeDtypeStruct((n_clients,), b8),
            jax.ShapeDtypeStruct((n_clients,), b8),
            jax.ShapeDtypeStruct((), jnp.int32))
    return jax.jit(apply), args


def sweep_program(mesh_shape: tuple, *, n_clients: int = 32, rounds: int = 8,
                  aggregator: str = "memory"):
    """Lower the shard_map'd sweep engine (``fed.scan_engine.run_batch``
    under ``ScanConfig.mesh``, DESIGN.md §13) at dry-run scale: one cell
    per "cells"-axis shard, the silo axis row-sharding local training (and
    the memory panel via ``silo_reduce="psum"`` when it divides N).
    Returns the lowered-and-compiled program plus its HLO stats."""
    from repro.core.availability_device import make_process
    from repro.data.synthetic import make_synthetic
    from repro.fed.models import logistic_regression
    from repro.fed.scan_engine import ScanConfig, ScanEngine

    ds = make_synthetic(n_clients=n_clients, alpha=0.5, beta=0.5, seed=0)
    silo = mesh_shape[1] if len(mesh_shape) > 1 else 1
    cfg = ScanConfig(rounds=rounds, m=4, local_steps=2, batch_size=8,
                     sampler="uniform", aggregator=aggregator,
                     mesh=tuple(mesh_shape),
                     silo_reduce="psum" if silo > 1 and n_clients % silo == 0
                     else "gather")
    eng = ScanEngine(ds, logistic_regression(dim=ds.x.shape[-1]), cfg)
    cells = [eng.cell(seed=s, process=make_process(
        "GE", n_clients=n_clients, data_sizes=ds.sizes, rounds=rounds))
        for s in range(mesh_shape[0])]
    compiled = eng.lower_batch(cells).compile()
    return compiled, hlo_analyze(compiled.as_text())


def datacenter_cell_dryrun(n_clients: int = 100_000, mesh: tuple = (1, 8), *,
                           rounds: int = 2, m: int = 32,
                           aggregator: str = "memory",
                           samples_per_client: int = 4, dim: int = 8,
                           classes: int = 4):
    """Compile-only proof of the silo axis at datacenter N (the ROADMAP
    leftover from PR 6): lower ONE N=10^5 sweep cell on a (cells, silo)
    mesh with the psum-sharded memory panel — HLO only, never executed.
    The (N, N) graph H alone would be 40 GB at N=10^5, so the cell's H is
    a ``jax.ShapeDtypeStruct`` and the lowering runs fully abstract
    (``ScanEngine.lower_batch(abstract=True)``).

    Returns ``(lowered, carry_shapes)``: the jax ``Lowered`` program (call
    ``.as_text()`` for the HLO the CI dry-run step pins) and the abstract
    scan-carry pytree, whose memory-panel leaf must show (N / silo, P)
    rows — a carry-size regression (e.g. the panel silently going global
    again) surfaces as a shape change here."""
    from repro.core.availability_device import make_process
    from repro.data.fed_dataset import FedDataset
    from repro.fed.models import logistic_regression
    from repro.fed.scan_engine import ScanConfig, ScanEngine

    silo = mesh[1] if len(mesh) > 1 else 1
    if n_clients % max(silo, 1):
        raise ValueError(f"N={n_clients} must divide by silo={silo}")
    # tiny per-client payload — client COUNT is the thing under test
    s = samples_per_client
    ds = FedDataset(
        x=np.zeros((n_clients, s, dim), np.float32),
        y=np.zeros((n_clients, s), np.int32),
        sizes=np.full((n_clients,), s, np.int64),
        x_val=np.zeros((8, dim), np.float32),
        y_val=np.zeros((8,), np.int32),
        num_classes=classes,
        label_dist=np.zeros((n_clients, classes)))
    cfg = ScanConfig(rounds=rounds, m=m, local_steps=1, batch_size=2,
                     sampler="uniform", aggregator=aggregator,
                     mesh=tuple(mesh), silo_reduce="psum")
    eng = ScanEngine(ds, logistic_regression(dim=dim, classes=classes), cfg)
    cells = [eng.cell(
        seed=0,
        process=make_process("GE", n_clients=n_clients, data_sizes=ds.sizes,
                             rounds=rounds),
        h=jax.ShapeDtypeStruct((n_clients, n_clients), jnp.float32))
        for _ in range(mesh[0])]
    lowered = eng.lower_batch(cells, abstract=True)
    return lowered, eng.carry_shapes(cells)


def run(n_clients: int, *, multi_pod: bool, sample_frac: float = 0.1,
        n_max: int = 512, local_steps: int = 10, batch: int = 10,
        force: bool = False, solver_backend: str = "ref",
        aggregator: str = "fedavg", agg_backend: str = "ref",
        sweep_mesh: tuple | None = None, tracer=None,
        sink=None) -> dict:
    from repro.fed.telemetry import NULL_TRACER
    tracer = tracer if tracer is not None else NULL_TRACER
    mesh_tag = "pod2" if multi_pod else "pod1"
    key = f"fedsim__c{n_clients}__{mesh_tag}"
    if sweep_mesh:
        key += f"__sweep{'x'.join(str(s) for s in sweep_mesh)}"
    if solver_backend != "ref":
        key += f"__{solver_backend}"
    if aggregator != "fedavg":
        key += f"__{aggregator}"
        if agg_backend != "ref":
            key += f"__{agg_backend}"
    out_path = RESULTS_DIR / f"{key}.json"
    if out_path.exists() and not force:
        return json.loads(out_path.read_text())
    rec = {"arch": f"fedsim-c{n_clients}", "shape": "fl_round",
           "mesh": mesh_tag, "variant": "baseline", "kind": "fl_round",
           "ok": False}
    t0 = time.time()
    try:
        mesh = make_production_mesh(multi_pod=multi_pod)
        from jax.sharding import NamedSharding, PartitionSpec as P
        dp = ("pod", "data") if multi_pod else ("data",)
        client_sh = NamedSharding(mesh, P(dp))
        repl = NamedSharding(mesh, P())
        sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
        dp_total = int(np.prod([sizes[a] for a in dp]))
        # pad the sampled-client count to the dp width (production pads the
        # cohort with zero-weight clients)
        m_sel = max(dp_total, int(round(sample_frac * n_clients)))
        m_sel = ((m_sel + dp_total - 1) // dp_total) * dp_total

        # ---- the round program: M sampled clients sharded over dp --------
        step = round_step_factory(local_steps, batch)
        gp = {"w": jax.ShapeDtypeStruct((DIM, CLASSES), jnp.float32),
              "b": jax.ShapeDtypeStruct((CLASSES,), jnp.float32)}
        args = (gp,
                jax.ShapeDtypeStruct((m_sel, n_max, DIM), jnp.float32),
                jax.ShapeDtypeStruct((m_sel, n_max), jnp.int32),
                jax.ShapeDtypeStruct((m_sel,), jnp.int32),
                jax.ShapeDtypeStruct((), jnp.float32),
                jax.ShapeDtypeStruct((m_sel, 2), jnp.uint32))
        jitted = jax.jit(step, in_shardings=(
            jax.tree_util.tree_map(lambda _: repl, gp),
            client_sh, client_sh, client_sh, None, client_sh),
            out_shardings=jax.tree_util.tree_map(lambda _: repl, gp))
        with tracer.span("lower", stage="round"):
            lowered = jitted.lower(*args)
        with tracer.span("compile", stage="round"):
            compiled = lowered.compile()
        hc = hlo_analyze(compiled.as_text())
        rec["round"] = {
            "m_sampled": m_sel,
            "flops_per_device": hc.flops, "bytes_per_device": hc.bytes,
            "collective_bytes_per_device": hc.collective_bytes,
            "mem": _mem_dict(compiled),
        }

        # ---- the server-side FedGS pipeline (N x N graph + solve) --------
        gargs = (jax.ShapeDtypeStruct((n_clients, CLASSES), jnp.float32),
                 jax.ShapeDtypeStruct((n_clients,), jnp.float32),
                 jax.ShapeDtypeStruct((n_clients,), jnp.bool_))
        gj = jax.jit(lambda f, c, a: graph_pipeline(
            f, c, a, 1.0, m_sel, solver_backend=solver_backend),
            in_shardings=(None, None, None))
        with mesh:
            with tracer.span("lower", stage="server_pipeline"):
                glow = gj.lower(*gargs)
            with tracer.span("compile", stage="server_pipeline"):
                gcomp = glow.compile()
        ghc = hlo_analyze(gcomp.as_text())
        rec["server_pipeline"] = {
            "n_clients": n_clients,
            "flops": ghc.flops, "bytes": ghc.bytes,
            "mem": _mem_dict(gcomp),
        }

        # ---- the server-update (aggregator) program ----------------------
        aj, aargs = aggregator_program(aggregator, n_clients, m_sel,
                                       backend=agg_backend)
        with tracer.span("compile", stage="aggregator"):
            acomp = aj.lower(*aargs).compile()
        ahc = hlo_analyze(acomp.as_text())
        rec["aggregator"] = {
            "family": aggregator, "backend": agg_backend,
            "n_clients": n_clients, "m_sampled": m_sel,
            "flops": ahc.flops, "bytes": ahc.bytes,
            "mem": _mem_dict(acomp),
        }
        # ---- the shard_map'd sweep engine on the ("cells","silo") mesh ---
        if sweep_mesh:
            scomp, shc = sweep_program(sweep_mesh)
            rec["sweep_engine"] = {
                "mesh": list(sweep_mesh),
                "flops_per_device": shc.flops, "bytes_per_device": shc.bytes,
                "collective_bytes_per_device": shc.collective_bytes,
                "mem": _mem_dict(scomp),
            }
        # roofline terms for the round program
        rec["compute_term_s"] = hc.flops / PEAK_FLOPS
        rec["memory_term_s"] = hc.bytes / HBM_BW
        rec["collective_term_s"] = hc.collective_bytes / ICI_BW
        terms = {"compute": rec["compute_term_s"],
                 "memory": rec["memory_term_s"],
                 "collective": rec["collective_term_s"]}
        rec["dominant"] = max(terms, key=terms.get)
        rec["ok"] = True
    except Exception as e:
        import traceback
        rec["error"] = f"{type(e).__name__}: {e}"
        rec["traceback"] = traceback.format_exc()[-4000:]
    rec["total_s"] = round(time.time() - t0, 2)
    if sink is not None:
        sink.emit("dryrun", {"key": key, "ok": rec["ok"],
                             "total_s": rec["total_s"],
                             "spans": tracer.summary()})
    RESULTS_DIR.mkdir(parents=True, exist_ok=True)
    out_path.write_text(json.dumps(rec, indent=1))
    print(f"[fedsim] {key}: {'ok' if rec['ok'] else 'FAIL ' + rec.get('error', '')[:120]} "
          f"({rec['total_s']}s)", flush=True)
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--clients", type=int, default=4096)
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--solver-backend", default="ref",
                    choices=("ref", "pallas"),
                    help="route the server-side Eq. 16 solve through the "
                         "tiled Pallas solver kernels")
    from repro.fed.aggregator_device import FAMILIES as _AGGS
    ap.add_argument("--aggregator", default="fedavg", choices=_AGGS,
                    help="server-update family to lower as the aggregator "
                         "program (fed/aggregator_device.py)")
    ap.add_argument("--agg-backend", default="ref", choices=("ref", "pallas"),
                    help="route the memory family's (N, P) panel "
                         "scatter+reduce through the fused Pallas kernel")
    ap.add_argument("--sweep-mesh", default=None, metavar="CxS",
                    help="also lower the shard_map'd sweep engine on a "
                         "(cells[, silo]) engine mesh, e.g. 8 or 4x2 "
                         "(fed/scan_engine.py, DESIGN.md §13)")
    from repro.launch.obs_cli import (
        add_observability_args, finish_observability, make_observability,
    )
    add_observability_args(ap)
    args = ap.parse_args()
    sweep = tuple(int(s) for s in args.sweep_mesh.split("x")) \
        if args.sweep_mesh else None
    tracer, sink = make_observability(args, run=f"fedsim-c{args.clients}")
    try:
        rec = run(args.clients, multi_pod=args.multi_pod, force=args.force,
                  solver_backend=args.solver_backend,
                  aggregator=args.aggregator, agg_backend=args.agg_backend,
                  sweep_mesh=sweep, tracer=tracer, sink=sink)
    finally:
        trace = finish_observability(sink, args)
        if trace:
            print(f"trace: {trace}")
    raise SystemExit(0 if rec["ok"] else 1)


if __name__ == "__main__":
    main()
