"""Federated LM training entry point — the paper's Algorithm 1 driving the
assigned-architecture model zoo.

Each federated client owns a distinct Markov-chain token stream (the LM
analogue of label skew); FedGS builds the 3DG from client unigram statistics
(oracle) or functional similarity, samples clients under an availability
mode, clients run E local AdamW steps, and the server applies any
aggregator family (``--aggregator``: Eq. 18 FedAvg, server momentum,
FedAdam, proximal-weighted, or the memory-rectified reduction, with
``--agg-backend pallas`` routing the (N, P) panel through the fused
kernel).

  PYTHONPATH=src python -m repro.launch.train --arch smollm-135m --reduced \
      --rounds 20 --clients 16 --mode LN --sampler fedgs

``--reduced`` uses the 2-layer smoke variant (CPU-friendly); without it the
full config is built (requires a real accelerator mesh for the big archs).
"""
from __future__ import annotations

import argparse
import time

import numpy as np

import jax
import jax.numpy as jnp

from repro.configs.base import INPUT_SHAPES
from repro.configs.registry import get_config
from repro.core.availability import ProcessMode, make_mode
from repro.core.availability_device import ALL_SCENARIOS, make_process
from repro.core.sampler import make_sampler, FedGSSampler
from repro.core import graph as graph_mod
from repro.core.fairness import count_variance
from repro.data.lm_stream import token_batches
from repro.fed.aggregator_device import FAMILIES as AGGREGATORS
from repro.fed.aggregator_device import make_aggregator_process
from repro.fed.faults_device import FAMILIES as FAULTS
from repro.fed.faults_device import HostFaultInjector, make_fault_process
from repro.fed.server import ServerAggregator
from repro.launch.obs_cli import (
    add_observability_args, finish_observability, make_observability,
)
from repro.models import lm
from repro.optim.optimizers import adamw


def client_unigrams(tokens: np.ndarray, vocab: int) -> np.ndarray:
    """(N, n_seq, S+1) -> (N, vocab) normalized unigram histograms: the
    label-distribution analogue used as oracle 3DG features."""
    n = tokens.shape[0]
    out = np.zeros((n, vocab), np.float64)
    for k in range(n):
        out[k] = np.bincount(tokens[k].reshape(-1), minlength=vocab)
    return out / np.maximum(out.sum(1, keepdims=True), 1)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="smollm-135m")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--rounds", type=int, default=20)
    ap.add_argument("--clients", type=int, default=16)
    ap.add_argument("--sample-frac", type=float, default=0.25)
    ap.add_argument("--local-steps", type=int, default=4)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--mode", default="LN",
                    help="Table-1 availability mode (IDL/MDF/LDF/YMF/YC/LN/"
                         "SLN) or a stateful scenario family "
                         "(GE/CLUSTER/DRIFT/DEADLINE)")
    ap.add_argument("--sampler", default="fedgs")
    ap.add_argument("--alpha", type=float, default=1.0)
    ap.add_argument("--solver-backend", default="ref", choices=("ref", "pallas"),
                    help="FedGS Eq. 16 solve: pure-jnp ref or the tiled "
                         "Pallas kernels (large client counts)")
    ap.add_argument("--aggregator", default="fedavg", choices=AGGREGATORS,
                    help="server-update family (fed/aggregator_device.py): "
                         "Eq. 18 fedavg, server momentum, FedAdam, "
                         "proximal-weighted averaging, or the FedAR/MIFA-"
                         "style memory-rectified reduction")
    ap.add_argument("--agg-backend", default="ref", choices=("ref", "pallas"),
                    help="memory-family scatter+reduce: pure-jnp ref or "
                         "the fused Pallas panel kernel")
    ap.add_argument("--fault", default="none", choices=FAULTS,
                    help="Byzantine/straggler fault family injected between "
                         "local training and aggregation "
                         "(fed/faults_device.py); pair with a robust "
                         "--aggregator (median/trimmed_mean/krum)")
    ap.add_argument("--byzantine-frac", type=float, default=0.0,
                    help="fraction of clients made adversarial (ceil(frac*N) "
                         "by a seeded permutation; identity fixed per seed)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--ckpt", default=None,
                    help="checkpoint path: saves params+counts every 10 "
                         "rounds and resumes if present")
    add_observability_args(ap)
    args = ap.parse_args(argv)
    tracer, sink = make_observability(args, run=f"train-{args.arch}")

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    n, m = args.clients, max(1, int(round(args.sample_frac * args.clients)))
    vocab = min(cfg.vocab_size, 512)

    # ---- per-client token pools + oracle 3DG ------------------------------
    pools = token_batches(vocab, n, tokens_per_client=args.batch * (args.seq + 1) * 8,
                          seq_len=args.seq, seed=args.seed)
    sizes = np.full(n, pools.shape[1], np.float64)
    feats = client_unigrams(pools, vocab)

    sampler = make_sampler(args.sampler, alpha=args.alpha,
                           solver_backend=args.solver_backend) \
        if args.sampler == "fedgs" else make_sampler(args.sampler)
    if isinstance(sampler, FedGSSampler):
        _, _, h = graph_mod.build_3dg(feats, eps=0.1, sigma2=0.01)
        sampler.set_graph(h)
    if args.mode.upper() in ALL_SCENARIOS:
        # stateful scenario families (GE / CLUSTER / DRIFT / DEADLINE) get
        # the same host face as the Table-1 modes via ProcessMode
        mode = ProcessMode(make_process(args.mode, n_clients=n,
                                        data_sizes=sizes, rounds=args.rounds,
                                        seed=args.seed),
                           avail_seed=args.seed + 1234)
    else:
        mode = make_mode(args.mode, n_clients=n, data_sizes=sizes,
                         label_sets=[set(np.argsort(-feats[k])[:3].tolist()) for k in range(n)],
                         num_labels=vocab)

    # ---- model + local trainer -------------------------------------------
    key = jax.random.PRNGKey(args.seed)
    params = lm.init_params(key, cfg)
    opt = adamw()

    @jax.jit
    def local_train(p, seqs, lr, key):
        """E local AdamW steps on one client's pool."""
        state = opt.init(p)

        def step(carry, k):
            p, s = carry
            idx = jax.random.randint(k, (args.batch,), 0, seqs.shape[0])
            b = {"tokens": seqs[idx][:, :-1], "labels": seqs[idx][:, 1:]}
            loss, g = jax.value_and_grad(
                lambda q: lm.train_loss(q, cfg, b, remat=False))(p)
            p, s = opt.update(g, s, p, lr)
            return (p, s), loss

        (p, _), losses = jax.lax.scan(step, (p, state),
                                      jax.random.split(key, args.local_steps))
        return p, losses.mean()

    @jax.jit
    def eval_loss(p, seqs):
        b = {"tokens": seqs[:, :-1], "labels": seqs[:, 1:]}
        return lm.train_loss(p, cfg, b, remat=False)

    val = jnp.asarray(pools[:, -1])        # one held-out sequence per client
    pools_j = jnp.asarray(pools[:, :-1])

    rng = np.random.default_rng(args.seed)
    avail_rng = np.random.default_rng(args.seed + 1234)
    counts = np.zeros(n)
    server = ServerAggregator(make_aggregator_process(args.aggregator),
                              n_clients=n, data_sizes=sizes,
                              backend=args.agg_backend, seed=args.seed)
    start = 0
    if args.ckpt:
        import os
        from repro.checkpoint.ckpt import load_checkpoint, save_checkpoint
        p = args.ckpt if args.ckpt.endswith(".npz") else args.ckpt + ".npz"
        if os.path.exists(p):
            state = load_checkpoint(args.ckpt, like={"params": params,
                                                     "counts": counts,
                                                     "round": np.zeros((), np.int64)})
            params = jax.tree_util.tree_map(jnp.asarray, state["params"])
            counts = np.asarray(state["counts"], np.float64)
            start = int(state["round"]) + 1
            print(f"resumed from {p} at round {start}")
    server.init(params)
    faults = None
    if args.fault != "none":
        faults = HostFaultInjector(
            make_fault_process(args.fault, n, frac=args.byzantine_frac),
            fault_seed=args.seed + 0xFA17)
        faults.init(params)
    t0 = time.time()
    try:
        for t in range(start, args.rounds):
            avail = mode.sample(t, avail_rng)
            sel = np.asarray(sampler.sample(avail=avail, m=m, rng=rng,
                                            counts=counts,
                                            data_sizes=sizes), int)
            if len(sel) == 0:
                # empty A_t (samplers return the empty array, PR-4): the
                # round is a params no-op — the zero-weight-guard story
                # end to end
                print(f"round {t:3d}  sel=[]  (no clients available; "
                      f"params kept)", flush=True)
                continue
            locals_, losses = [], []
            with tracer.span("local_train", t=t, m=len(sel)):
                for k in sel:
                    key, sub = jax.random.split(key)
                    pk, lk = local_train(params, pools_j[k],
                                         jnp.float32(args.lr), sub)
                    locals_.append(pk)
                    losses.append(float(lk))
            stacked = jax.tree_util.tree_map(lambda *x: jnp.stack(x),
                                             *locals_)
            if faults is not None:
                stacked = faults.inject(stacked, params, sel, avail, t)
            with tracer.span("aggregate", t=t):
                params = server.apply(stacked, sizes[sel].astype(np.float32),
                                      sel, avail, t)
            counts[sel] += 1
            with tracer.span("eval", t=t):
                vl = float(eval_loss(params, val))
            if sink is not None:
                sink.emit("round", {"engine": "train-lm", "t": t,
                                    "val_loss": vl,
                                    "train_loss": float(np.mean(losses)),
                                    "n_selected": int(len(sel)),
                                    "avail_rate": float(np.mean(avail)),
                                    "count_var":
                                    float(count_variance(counts))})
            print(f"round {t:3d}  sel={sel.tolist()}  "
                  f"train={np.mean(losses):.4f}  "
                  f"val={vl:.4f}  Var(v)={count_variance(counts):.3f}",
                  flush=True)
            if args.ckpt and (t + 1) % 10 == 0:
                from repro.checkpoint.ckpt import save_checkpoint
                with tracer.span("checkpoint_write", round=t):
                    save_checkpoint(
                        args.ckpt, {"params": params, "counts": counts,
                                    "round": np.asarray(t, np.int64)},
                        metadata={"round": t, "arch": cfg.name})
    finally:
        trace = finish_observability(sink, args)
        if trace:
            print(f"trace: {trace}")
    print(f"done in {time.time() - t0:.1f}s; final Var(v^t)={count_variance(counts):.3f}")
    return params, counts


if __name__ == "__main__":
    from repro.fed.runtime import enable_compile_cache
    enable_compile_cache()
    main()
