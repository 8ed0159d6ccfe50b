"""Paper-scale federated models: logistic regression (Synthetic) and the
McMahan-style small CNNs (vision surrogates) — pure JAX — and the model
zoo's mixed-stack language models as client models (``language_model``).

``embed`` exposes the output-layer activations used by the functional-
similarity 3DG construction (Eq. 12, l = output layer).
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np


@dataclass(frozen=True)
class FedModel:
    init: Callable          # rng -> params
    loss: Callable          # (params, x, y) -> scalar
    accuracy: Callable      # (params, x, y) -> scalar
    embed: Callable         # (params, x) -> (B, dim) output-layer embedding


def _xent(logits, y):
    logp = jax.nn.log_softmax(logits)
    return -jnp.mean(jnp.take_along_axis(logp, y[:, None], axis=1))


def logistic_regression(dim: int = 60, classes: int = 10) -> FedModel:
    def init(rng):
        k1, _ = jax.random.split(rng)
        return {"w": jax.random.normal(k1, (dim, classes)) * 0.01,
                "b": jnp.zeros((classes,))}

    def logits(p, x):
        return x @ p["w"] + p["b"]

    return FedModel(
        init=init,
        loss=lambda p, x, y: _xent(logits(p, x), y),
        accuracy=lambda p, x, y: jnp.mean(jnp.argmax(logits(p, x), 1) == y),
        embed=lambda p, x: logits(p, x),
    )


def small_cnn(shape=(8, 8, 3), classes: int = 10, width: int = 16) -> FedModel:
    """Two conv + pool stages, one hidden dense — the McMahan CNN scaled to
    the surrogate resolution."""
    h, w, c = shape

    def init(rng):
        ks = jax.random.split(rng, 4)
        def conv_init(k, kh, kw, cin, cout):
            fan = kh * kw * cin
            return jax.random.normal(k, (kh, kw, cin, cout)) / np.sqrt(fan)
        flat = (h // 4) * (w // 4) * (2 * width)
        return {
            "c1": conv_init(ks[0], 3, 3, c, width),
            "c2": conv_init(ks[1], 3, 3, width, 2 * width),
            "d1": jax.random.normal(ks[2], (flat, 64)) / np.sqrt(flat),
            "b1": jnp.zeros((64,)),
            "d2": jax.random.normal(ks[3], (64, classes)) / np.sqrt(64),
            "b2": jnp.zeros((classes,)),
        }

    def conv(x, w):
        return jax.lax.conv_general_dilated(
            x, w, (1, 1), "SAME", dimension_numbers=("NHWC", "HWIO", "NHWC"))

    def pool(x):
        return jax.lax.reduce_window(x, -jnp.inf, jax.lax.max,
                                     (1, 2, 2, 1), (1, 2, 2, 1), "VALID")

    def logits(p, x):
        x = pool(jax.nn.relu(conv(x, p["c1"])))
        x = pool(jax.nn.relu(conv(x, p["c2"])))
        x = x.reshape(x.shape[0], -1)
        x = jax.nn.relu(x @ p["d1"] + p["b1"])
        return x @ p["d2"] + p["b2"]

    return FedModel(
        init=init,
        loss=lambda p, x, y: _xent(logits(p, x), y),
        accuracy=lambda p, x, y: jnp.mean(jnp.argmax(logits(p, x), 1) == y),
        embed=lambda p, x: logits(p, x),
    )


def language_model(arch) -> FedModel:
    """A mixed-stack LM of the zoo (``models/lm.py``, an ``ArchConfig`` with
    ``layer_types``) as a client model.  Each row of ``x`` is one document
    of S+1 token ids: the model reads ids 0..S-1 and predicts 1..S.  ``y``
    is unused.  Training recomputes each layer in the backward pass."""
    from repro.models import lm

    def batch(x):
        return {"tokens": x[:, :-1], "labels": x[:, 1:]}

    return FedModel(
        init=lambda rng: lm.init_params(rng, arch),
        loss=lambda p, x, y: lm.train_loss(p, arch, batch(x), remat=True),
        accuracy=lambda p, x, y: lm.next_token_accuracy(p, arch, batch(x)),
        embed=lambda p, x: lm.mean_hidden(p, arch, x[:, :-1]))
