"""The CNN a configuration describes, handed to the program as its model
(``repro.fed.models.FedModel``): each of ``conv`` ([kernel, channels])
is a convolution with ``conv_padding``, a ReLU and a 2x2 max pool; then
each of ``dense`` is a dense layer with a ReLU; then the class logits.
Weights and biases are drawn as PyTorch initialises ``Conv2d`` and
``Linear`` layers, uniform on +-1/sqrt(fan_in), from one key per layer in
that order, split into the weight's and the bias's."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def make(cfg: dict, classes: int):
    from repro.fed.models import FedModel
    convs = [tuple(c) for c in cfg["conv"]]
    dense = list(cfg["dense"])
    padding = cfg["conv_padding"]
    h, w, c = cfg["image_shape"]
    for k, _ in convs:
        if padding == "VALID":
            h, w = h - k + 1, w - k + 1
        h, w = h // 2, w // 2
    widths = [h * w * convs[-1][1]] + dense + [classes]

    def init(rng):
        ks = jax.random.split(rng, len(convs) + len(widths) - 1)
        p, cin = {}, cfg["image_shape"][2]
        def layer(key, shape):
            kw, kb = jax.random.split(key)
            bound = 1.0 / np.sqrt(np.prod(shape[:-1]))
            return (jax.random.uniform(kw, shape, minval=-bound, maxval=bound),
                    jax.random.uniform(kb, shape[-1:], minval=-bound,
                                       maxval=bound))
        for i, (k, cout) in enumerate(convs):
            p[f"conv{i}"], p[f"conv{i}_b"] = layer(ks[i], (k, k, cin, cout))
            cin = cout
        for j, (fin, fout) in enumerate(zip(widths, widths[1:])):
            p[f"dense{j}"], p[f"dense{j}_b"] = layer(ks[len(convs) + j],
                                                     (fin, fout))
        return p

    def logits(p, x):
        for i in range(len(convs)):
            x = jax.lax.conv_general_dilated(
                x, p[f"conv{i}"], (1, 1), padding,
                dimension_numbers=("NHWC", "HWIO", "NHWC")) + p[f"conv{i}_b"]
            x = jax.lax.reduce_window(jax.nn.relu(x), -jnp.inf, jax.lax.max,
                                      (1, 2, 2, 1), (1, 2, 2, 1), "VALID")
        x = x.reshape(x.shape[0], -1)
        last = len(widths) - 2
        for j in range(last):
            x = jax.nn.relu(x @ p[f"dense{j}"] + p[f"dense{j}_b"])
        return x @ p[f"dense{last}"] + p[f"dense{last}_b"]

    def loss(p, x, y):
        logp = jax.nn.log_softmax(logits(p, x))
        return -jnp.mean(jnp.take_along_axis(logp, y[:, None], axis=1))

    return FedModel(
        init=init, loss=loss,
        accuracy=lambda p, x, y: jnp.mean(jnp.argmax(logits(p, x), 1) == y),
        embed=logits)
