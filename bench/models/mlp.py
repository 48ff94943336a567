"""The fully connected MLP family: ``cfg["model"]["layers"]`` lists the
widths, ReLU between layers, on synthetic MNIST-class data.

The data, the initial weights, the Eq. 5 terms and the work counts are
the yardstick's own copies: the program under test receives what they
build, and nothing but ``program`` imports it, so a change to the
program's generators cannot change a cell's inputs.
"""

from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp

from bench.fleet import sub_seed

#: the MEL task-parallelization scenario ships the samples: F features
#: at 32 bits each per sample, and the model at 32 bits per weight
DATA_BITS = 32
MODEL_BITS = 32
BYTES_F32 = 4


def param_count(layers) -> int:
    """Weights and biases of a fully connected net."""
    return sum(a * b + b for a, b in zip(layers[:-1], layers[1:]))


def weight_count(layers) -> int:
    return sum(a * b for a, b in zip(layers[:-1], layers[1:]))


def synthetic_mnist(n_train: int, n_test: int, seed: int, *,
                    features: int = 784, classes: int = 10,
                    noise: float = 2.5):
    """Class-conditional Gaussians over 784 features whose means are
    low-frequency image patterns (the repo's synthetic MNIST stand-in).
    Returns ``(x_train, y_train, x_test, y_test)``, float32 / int32."""
    side = int(np.sqrt(features))
    yy, xx = np.mgrid[0:side, 0:side].astype(np.float32) / side
    means = []
    for c in range(classes):
        fx, fy = 1 + c % 3, 1 + (c // 3) % 3
        img = (np.sin(2 * np.pi * fx * xx + c * 0.7)
               * np.cos(2 * np.pi * fy * yy + 0.3 * c))
        img += 0.5 * np.sin(2 * np.pi * (xx + yy) * (1 + 0.5 * c))
        means.append(img.reshape(-1))
    means = np.stack(means).astype(np.float32)

    def make(count, part):
        r = np.random.default_rng(sub_seed(seed, part))
        y = r.integers(0, classes, size=count).astype(np.int32)
        x = means[y] + noise * r.standard_normal((count, features), np.float32)
        return x.astype(np.float32), y

    return (*make(n_train, 1), *make(n_test, 2))


def init_fn(layers):
    """A jitted ``seed -> params`` that draws the MLP's He-normal weights
    and zero biases on the device in one call: a list of ``{"w", "b"}``
    dicts, the layout the program's ``mlp.loss`` takes."""

    @jax.jit
    def init(seed):
        keys = jax.random.split(jax.random.key(seed), len(layers) - 1)
        return [
            {"w": jax.random.normal(kk, (a, b), jnp.float32) * np.float32(np.sqrt(2.0 / a)),
             "b": jnp.zeros((b,), jnp.float32)}
            for kk, a, b in zip(keys, layers[:-1], layers[1:])
        ]

    return lambda s: init(np.uint32(s))


# ---------------------------------------------------------------------------
# the family's interface (``bench/models/__init__.py``)
# ---------------------------------------------------------------------------

def data(cfg: dict, seed: int):
    d = cfg["data"]
    return synthetic_mnist(d["train"], d["eval"], seed,
                           features=d["features"], classes=d["classes"])


def init(cfg: dict):
    return init_fn(cfg["model"]["layers"])


def program(cfg: dict):
    from repro.models import mlp

    return mlp.loss, mlp.accuracy


def time_terms(cfg: dict) -> tuple[float, float, int]:
    """C_m = 4 FLOPs per parameter per sample, S_m = the weights at 32
    bits, and F features at 32 bits per sample (paper Sec. V-A)."""
    layers = cfg["model"]["layers"]
    return (4.0 * param_count(layers), float(weight_count(layers)) * MODEL_BITS,
            layers[0] * DATA_BITS)


def flops_per_sample_step(cfg: dict) -> float:
    """6 FLOPs per parameter per sample-step (forward 2, backward 4)."""
    return 6.0 * param_count(cfg["model"]["layers"])


def least_bytes(cfg: dict, rows) -> float:
    """Each learner reads its shard (F f32 features and an int32 label per
    sample) and its start params once, and writes its trained params
    once, per round."""
    layers = cfg["model"]["layers"]
    p = param_count(layers) * BYTES_F32
    per_sample = (layers[0] + 1) * BYTES_F32
    return float(sum(int(r["d"].sum()) * per_sample + 2 * p * len(r["d"])
                     for r in rows))


def _dot(a, b):
    if a.dtype == jnp.float32:
        return jnp.matmul(a, b, precision=jax.lax.Precision.HIGHEST)
    return jnp.matmul(a, b)


def forward(params, x):
    h = x
    for i, layer in enumerate(params):
        h = _dot(h, layer["w"]) + layer["b"]
        if i < len(params) - 1:
            h = jnp.maximum(h, 0)
    return h


def loss(params, x, y, m):
    logits = forward(params, x)
    logp = jax.nn.log_softmax(logits)
    nll = -jnp.take_along_axis(logp, y[:, None], axis=1)[:, 0]
    return (nll * m).sum() / jnp.maximum(m.sum(), 1)


def accuracy(params, x, y):
    return jnp.mean(jnp.argmax(forward(params, x), axis=-1) == y)
