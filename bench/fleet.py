"""The fleet of a benchmark cell, made from the configuration, and the
per-campaign seeds.

The fleet is the paper's Sec. V-A 802.11 environment and its Eq. 5 time
model, whose model terms come from the configuration's learner model
family (``bench/models/``). This is the yardstick's own copy: the
program under test receives what it builds and nothing here imports it,
so a change to the program's generators cannot change a cell's inputs.
"""

from __future__ import annotations

import dataclasses

import numpy as np

from bench import models

#: 802.11 indoor environment of the paper (Sec. V-A)
RADIUS_M = 50.0
BANDWIDTH_HZ = 5e6
TX_POWER_W = 0.1
NOISE_PSD = 4e-21
FAST_CLOCK_HZ = 2.4e9
SLOW_CLOCK_HZ = 0.7e9


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


def build_fleet(cfg: dict) -> Fleet:
    """The configuration's fleet: ``indoor_80211_profile(K, seed)`` and
    ``TimeModel.build`` of the paper, with the model family's C_m, S_m and
    bits per sample (``time_terms``)."""
    fl = cfg["fleet"]
    k = int(fl["learners"])
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
    c_m, s_m, sample_bits = models.load(cfg).time_terms(cfg)
    total = int(fl["samples_per_cycle"])
    return Fleet(
        c2=c_m / clock,
        c1=sample_bits / rate,
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
