"""The paper's own learning model: a fully-connected DNN for MNIST-class
data with layout [784, 300, 124, 60, 10] (Sec. V-A). This is the model the
federated MEL simulation trains; the allocator's C_m/S_m constants for it
come from ``repro.core.complexity.mnist_dnn_cost``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models.params import ParamSpec, init_params

__all__ = ["PAPER_LAYERS", "build_specs", "init", "forward", "loss", "accuracy"]

PAPER_LAYERS = [784, 300, 124, 60, 10]


def build_specs(layers=None):
    layers = layers or PAPER_LAYERS
    out = []
    for fan_in, fan_out in zip(layers[:-1], layers[1:]):
        out.append(
            {
                "w": ParamSpec((fan_in, fan_out), ("embed", "mlp"), scale=float(2.0 / fan_in) ** 0.5),
                "b": ParamSpec((fan_out,), ("mlp",), init="zeros"),
            }
        )
    return out


def init(key, layers=None):
    return init_params(build_specs(layers), key)


def forward(params, x):
    h = x
    for i, layer in enumerate(params):
        h = h @ layer["w"] + layer["b"]
        if i < len(params) - 1:
            h = jax.nn.relu(h)
    return h


def loss(params, batch):
    logits = forward(params, batch["x"])
    logp = jax.nn.log_softmax(logits)
    # a one-hot contraction picks logp[y] exactly (one non-zero term per
    # row) and, unlike a gather, lowers inside the Pallas megakernel
    onehot = jax.nn.one_hot(batch["y"], logits.shape[-1], dtype=logp.dtype)
    nll = -(logp * onehot).sum(axis=-1)
    if "mask" in batch:
        m = batch["mask"].astype(jnp.float32)
        return (nll * m).sum() / jnp.maximum(m.sum(), 1.0)
    return nll.mean()


def accuracy(params, x, y):
    return jnp.mean(jnp.argmax(forward(params, x), axis=-1) == y)
