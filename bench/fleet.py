"""Inputs of a benchmark cell, made from the configuration and the seed.

The fleet (the paper's Sec. V-A 802.11 environment and its Eq. 5 time
model), the synthetic MNIST-class data, the initial MLP weights and the
per-campaign seeds. These are the yardstick's own copies: the program
under test receives what they build and nothing here imports it, so a
change to the program's generators cannot change a cell's inputs.
"""

from __future__ import annotations

import dataclasses

import numpy as np

#: 802.11 indoor environment of the paper (Sec. V-A)
RADIUS_M = 50.0
BANDWIDTH_HZ = 5e6
TX_POWER_W = 0.1
NOISE_PSD = 4e-21
FAST_CLOCK_HZ = 2.4e9
SLOW_CLOCK_HZ = 0.7e9
#: the MEL task-parallelization scenario ships the samples: F features
#: at 32 bits each per sample, and the model at 32 bits per weight
DATA_BITS = 32
MODEL_BITS = 32


@dataclasses.dataclass(frozen=True)
class Fleet:
    """Eq. 5 coefficients of K learners and the cycle's sample budget:
    t_k = c2 tau d + c1 d + c0 <= T, sum d = total, d_lo <= d <= d_hi."""

    c2: np.ndarray
    c1: np.ndarray
    c0: np.ndarray
    T: float
    total: int
    d_lo: int
    d_hi: int

    @property
    def k(self) -> int:
        return int(self.c2.shape[0])


def param_count(layers) -> int:
    """Weights and biases of a fully connected net."""
    return sum(a * b + b for a, b in zip(layers[:-1], layers[1:]))


def weight_count(layers) -> int:
    return sum(a * b for a, b in zip(layers[:-1], layers[1:]))


def build_fleet(cfg: dict) -> Fleet:
    """The configuration's fleet: ``indoor_80211_profile(K, seed)`` and
    ``TimeModel.build`` of the paper, with the model's C_m = 4 FLOPs per
    parameter per sample and S_m = its weights at 32 bits (Sec. V-A)."""
    fl = cfg["fleet"]
    k, layers = int(fl["learners"]), cfg["model"]["layers"]
    rng = np.random.default_rng(int(fl["seed"]))
    dist = rng.uniform(2.0, RADIUS_M, size=k)
    pl_db = 40.0 + 10.0 * 3.0 * np.log10(dist) + rng.normal(0.0, 4.0, size=k)
    gain = 10.0 ** (-pl_db / 10.0)
    clock = np.empty(k)
    for i in range(k):
        base = FAST_CLOCK_HZ if i % 2 == 0 else SLOW_CLOCK_HZ
        clock[i] = base * rng.uniform(0.9, 1.1)
    snr = TX_POWER_W * gain / (NOISE_PSD * BANDWIDTH_HZ)
    rate = BANDWIDTH_HZ * np.log2(1.0 + snr)
    c_m = 4.0 * param_count(layers)
    s_m = float(weight_count(layers)) * MODEL_BITS
    total = int(fl["samples_per_cycle"])
    return Fleet(
        c2=c_m / clock,
        c1=layers[0] * DATA_BITS / rate,
        c0=2.0 * s_m / rate,
        T=float(fl["T"]),
        total=total,
        d_lo=max(1, int(fl["d_lower_frac"] * total / k)),
        d_hi=min(total, int(fl["d_upper_frac"] * total / k)),
    )


def sub_seed(*parts: int) -> int:
    """A 31-bit seed derived from any whole numbers (the harness's seed
    may exceed 32 bits)."""
    return int(np.random.SeedSequence([int(p) for p in parts]).generate_state(1)[0]
               & 0x7FFFFFFF)


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
    import jax
    import jax.numpy as jnp

    @jax.jit
    def init(seed):
        keys = jax.random.split(jax.random.key(seed), len(layers) - 1)
        return [
            {"w": jax.random.normal(kk, (a, b), jnp.float32) * np.float32(np.sqrt(2.0 / a)),
             "b": jnp.zeros((b,), jnp.float32)}
            for kk, a, b in zip(keys, layers[:-1], layers[1:])
        ]

    return lambda s: init(np.uint32(s))
