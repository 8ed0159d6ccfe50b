"""Client-side local training — one vmap'd XLA program over sampled clients.

This replaces the paper's sequential PyTorch client loop with a single
client-batched program (the TPU-native formulation, DESIGN.md §3): all
sampled clients' padded data is stacked and E local SGD steps run under
``vmap`` with per-client batch draws.

Under an enclosing ``vmap`` (the scan engine's cells) the trainer folds
the enclosing axis into its own client axis, so the program trains one
flat axis of cells x clients instead of a vmap nested in a vmap: nested,
every convolution is batched twice and XLA copies each conv activation
and cotangent between the grouped conv's layout and the client-major one
(DESIGN.md §3).  The per-client maths is the same either way.

A client model too large for an (M, P) stack of client models trains client
by client instead (``make_sequential_trainer``): a ``lax.scan`` over the M
sampled clients, each trained from the global params by the same per-client
maths, streams the Eq. 18 weighted sum into one accumulator.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.custom_batching import custom_vmap

from repro.fed.aggregator_device import guard_zero_weight


def _spread(a, batched, per_client, size, k):
    """One argument of a fold: (size, k, ...) -> (size * k, ...).  An
    unbatched argument is broadcast over the enclosing axis, and a shared
    one (no client axis) over the k clients."""
    if not batched:
        a = jnp.broadcast_to(a, (size,) + a.shape)
    if not per_client:
        a = jnp.broadcast_to(a[:, None], (size, k) + a.shape[1:])
    return a.reshape((size * k,) + a.shape[2:])


def _client_update(model_loss, local_steps: int, batch_size: int,
                   prox_mu: float):
    """fn(global_params, x (n_max,...), y, n_k, lr, rng) -> the client's
    params after ``local_steps`` SGD steps on minibatches of
    ``batch_size`` drawn with replacement, one key per step."""

    def one_client(global_params, x, y, n_k, lr, rng):
        def loss_fn(p, xb, yb):
            l = model_loss(p, xb, yb)
            if prox_mu > 0.0:
                sq = sum(jnp.sum(jnp.square(a - b)) for a, b in zip(
                    jax.tree_util.tree_leaves(p),
                    jax.tree_util.tree_leaves(global_params)))
                l = l + 0.5 * prox_mu * sq
            return l

        def step(params, key):
            idx = jax.random.randint(key, (batch_size,), 0, jnp.maximum(n_k, 1))
            g = jax.grad(loss_fn)(params, x[idx], y[idx])
            params = jax.tree_util.tree_map(lambda p, gg: p - lr * gg, params, g)
            return params, None

        params, _ = jax.lax.scan(step, global_params,
                                 jax.random.split(rng, local_steps))
        return params

    return one_client


def make_local_trainer(model_loss, *, local_steps: int, batch_size: int,
                       prox_mu: float = 0.0):
    """Returns jit'd fn(global_params, x (M,n_max,...), y (M,n_max), sizes (M,),
    lr, rng) -> stacked local params (M, ...).

    Its ``fold_stats`` dict is the trace-time record of the fold: how many
    times an enclosing vmap was folded into the client axis (once per vmap
    level around each traced call, again where a scan whose carry turns
    batched batches its body a second time) and the widest folded axis."""
    fold_stats = {"folds": 0, "clients": 0}
    one_client = _client_update(model_loss, local_steps, batch_size, prox_mu)

    def clients(params_axis):
        """vmap(one_client) over K clients whose start params are shared
        (``params_axis`` None) or per client (0); ``lr`` is shared as a
        scalar or per client as (K,).  Nothing differentiates through the
        trainer: a custom_vmap function has no reverse-mode derivative."""
        @custom_vmap
        def train(params, x, y, sizes, lr, rngs):
            lr_axis = 0 if jnp.ndim(lr) else None
            return jax.vmap(one_client, in_axes=(
                params_axis, 0, 0, 0, lr_axis, 0))(params, x, y, sizes, lr,
                                                   rngs)

        @train.def_vmap
        def fold(size, in_batched, params, x, y, sizes, lr, rngs):
            p_b, x_b, y_b, s_b, lr_b, r_b = in_batched
            k = sizes.shape[1] if s_b else sizes.shape[0]
            fold_stats["folds"] += 1
            fold_stats["clients"] = max(fold_stats["clients"], size * k)
            params = jax.tree_util.tree_map(
                lambda a, b: _spread(a, b, params_axis == 0, size, k),
                params, p_b)
            if lr_b or jnp.ndim(lr):
                lr = _spread(lr, lr_b, jnp.ndim(lr) == lr_b + 1, size, k)
            x, y, sizes, rngs = (_spread(a, b, True, size, k) for a, b in
                                 ((x, x_b), (y, y_b), (sizes, s_b),
                                  (rngs, r_b)))
            out = per_client_train(params, x, y, sizes, lr, rngs)
            out = jax.tree_util.tree_map(
                lambda a: a.reshape((size, k) + a.shape[1:]), out)
            return out, jax.tree_util.tree_map(lambda _: True, out)
        return train

    per_client_train = clients(0)
    trainer = jax.jit(clients(None))
    trainer.fold_stats = fold_stats
    return trainer


def make_sequential_trainer(model_loss, *, local_steps: int,
                            batch_size: int, prox_mu: float = 0.0):
    """Returns jit'd fn(global_params, x (N,n_max,...), y (N,n_max), sizes
    (N,), sel (M,), lr, rngs (M, ...), weights (M,)) -> the Eq. 18 mean
    ``sum_k w_k theta_k / sum_k w_k``.

    A ``lax.scan`` over the M clients ``sel``: each starts from the global
    params and takes the same SGD steps, on the same minibatch draws, as in
    ``make_local_trainer``; its params, times its normalised weight, are
    added into one float32 accumulator (scope ``accumulate``), so the
    program holds the global params, one client's params, its gradient and
    the accumulator, and never an (M, P) stack.  A round whose weights are
    all zero returns the global params (``guard_zero_weight``).

    Under an enclosing vmap the cells train one after another too (a unit
    axis is dropped, not batched into every matmul); nothing differentiates
    through the trainer."""
    one_client = _client_update(model_loss, local_steps, batch_size, prox_mu)

    def streamed(params, x, y, sizes, sel, lr, rngs, weights):
        total = jnp.sum(weights)
        w = weights / jnp.maximum(total, 1e-12)

        def client(acc, inp):
            k, rng, w_k = inp
            theta = one_client(params, x[k], y[k], sizes[k], lr, rng)
            with jax.named_scope("accumulate"):
                acc = jax.tree_util.tree_map(
                    lambda a, t: a + w_k * t.astype(jnp.float32), acc, theta)
            return acc, None

        acc0 = jax.tree_util.tree_map(
            lambda a: jnp.zeros(a.shape, jnp.float32), params)
        acc, _ = jax.lax.scan(client, acc0, (sel, rngs, w))
        avg = jax.tree_util.tree_map(lambda a, p: a.astype(p.dtype), acc,
                                     params)
        return guard_zero_weight(avg, params, total)

    @custom_vmap
    def train(*args):
        return streamed(*args)

    @train.def_vmap
    def per_cell(size, in_batched, *args):
        def cell(i):
            return train(*(jax.tree_util.tree_map(
                lambda a, b: a[i] if b else a, arg, bat)
                for arg, bat in zip(args, in_batched)))
        if size == 1:
            out = jax.tree_util.tree_map(lambda a: a[None], cell(0))
        else:
            out = jax.lax.map(cell, jnp.arange(size))
        return out, jax.tree_util.tree_map(lambda _: True, out)

    return jax.jit(train)


def make_loss_prober(model_loss, *, probe_size: int = 64):
    """jit'd fn(params, x (N,n_max,...), y, sizes, rng) -> per-client loss (N,)
    of the *global* model on each client's local data (Power-of-Choice)."""

    def one(params, x, y, n_k, rng):
        idx = jax.random.randint(rng, (probe_size,), 0, jnp.maximum(n_k, 1))
        return model_loss(params, x[idx], y[idx])

    batched = jax.vmap(one, in_axes=(None, 0, 0, 0, 0))
    return jax.jit(batched)
