"""Per-learner time model of the MEL global cycle (paper Eqs. 1-5).

Each global cycle of wall-clock budget ``T`` covers, for learner ``k``:

  t_k^S  - orchestrator -> learner transfer of the global model w and
           (task-parallelization only) the d_k data samples      (Eq. 1)
  t_k^C  - one local SGD update over d_k samples                  (Eq. 2);
           tau_k updates cost tau_k * t_k^C
  t_k^R  - learner -> orchestrator return of the local model      (Eq. 3)

Total (Eq. 4/5):   t_k = C2_k * tau_k * d_k + C1_k * d_k + C0_k

with
  C2_k = C_m / f_k
  C1_k = (F * P_d + 2 * P_m * S_d) / R_k        (task-parallelization)
       = (        2 * P_m * S_d) / R_k          (distributed-datasets)
  C0_k = 2 * P_m * S_m / R_k
  R_k  = W * log2(1 + P_k h_k / N0)             (achievable rate, bit/s)

Everything is plain float math over numpy arrays so the allocator can run
on hosts without touching jax device state; a jax twin lives in
``solver_numeric`` for the batched jit path.
"""

from __future__ import annotations

import dataclasses
from typing import Sequence

import numpy as np

__all__ = [
    "CapacityDrift",
    "ChannelParams",
    "LearnerProfile",
    "QueueDrift",
    "TimeModel",
    "indoor_80211_profile",
    "is_state_coupled",
    "pod_slice_profile",
]


@dataclasses.dataclass(frozen=True)
class ChannelParams:
    """Link parameters for one learner<->orchestrator channel."""

    bandwidth_hz: float = 5e6        # W
    tx_power_w: float = 0.1          # P_ko (20 dBm)
    gain: float = 1e-8               # h_ko (path loss, linear; ~80 dB)
    noise_psd: float = 4e-21         # N0 (W/Hz), thermal ~ -174 dBm/Hz

    def rate_bps(self) -> float:
        snr = self.tx_power_w * self.gain / (self.noise_psd * self.bandwidth_hz)
        return self.bandwidth_hz * np.log2(1.0 + snr)


@dataclasses.dataclass(frozen=True)
class LearnerProfile:
    """One edge learner: compute rate + channel."""

    clock_hz: float                  # f_k, effective clocks/sec
    channel: ChannelParams
    name: str = "learner"


@dataclasses.dataclass(frozen=True)
class TimeModel:
    """Vectorized coefficients (C2, C1, C0) for K learners.

    Attributes
    ----------
    c2, c1, c0 : np.ndarray shape (K,)
        Quadratic / linear / constant coefficients of Eq. 5.
    """

    c2: np.ndarray
    c1: np.ndarray
    c0: np.ndarray

    @property
    def num_learners(self) -> int:
        return int(self.c2.shape[0])

    @staticmethod
    def build(
        profiles: Sequence[LearnerProfile],
        *,
        model_complexity_flops: float,     # C_m: clocks (~= FLOPs) per sample per epoch
        model_size_bits: float,            # S_m * P_m ... we take bits directly
        features_per_sample: int = 784,    # F
        data_precision_bits: int = 32,     # P_d
        model_precision_bits: int = 32,    # P_m (folded into sizes below)
        sample_model_scaling_bits: float = 0.0,  # P_m * S_d: model bits that scale w/ d_k
        task_parallelization: bool = True,
    ) -> "TimeModel":
        """Build (C2, C1, C0) from learner profiles (paper Sec. II).

        ``model_size_bits`` is the full serialized model (P_m * S_m).
        ``sample_model_scaling_bits`` is P_m * S_d - the per-sample part of
        the model transfer (zero for the architectures we care about).
        """
        k = len(profiles)
        c2 = np.empty(k)
        c1 = np.empty(k)
        c0 = np.empty(k)
        for i, p in enumerate(profiles):
            rate = p.channel.rate_bps()
            c2[i] = model_complexity_flops / p.clock_hz
            data_bits = features_per_sample * data_precision_bits if task_parallelization else 0.0
            c1[i] = (data_bits + 2.0 * sample_model_scaling_bits) / rate
            c0[i] = 2.0 * model_size_bits / rate
        del model_precision_bits  # already folded into the *_bits arguments
        return TimeModel(c2=c2, c1=c1, c0=c0)

    # --- Eq. 5 -----------------------------------------------------------
    def cycle_time(self, tau: np.ndarray, d: np.ndarray) -> np.ndarray:
        """t_k for each learner."""
        tau = np.asarray(tau, dtype=float)
        d = np.asarray(d, dtype=float)
        return self.c2 * tau * d + self.c1 * d + self.c0

    # --- the reduced form used by the solvers ----------------------------
    def tau_of_d(self, d: np.ndarray, T: float) -> np.ndarray:
        """tau_k(d_k) = (T - C0_k - C1_k d_k) / (C2_k d_k)  — Eq. 5 solved
        for tau with t_k = T. May be negative => learner infeasible."""
        d = np.asarray(d, dtype=float)
        return (T - self.c0 - self.c1 * d) / (self.c2 * d)

    def d_of_tau(self, tau: np.ndarray, T: float) -> np.ndarray:
        """d_k(tau_k) = (T - C0_k) / (C2_k tau_k + C1_k) — inverse map."""
        tau = np.asarray(tau, dtype=float)
        return (T - self.c0) / (self.c2 * tau + self.c1)

    def max_tau(self, d: np.ndarray, T: float) -> np.ndarray:
        """Largest integer tau_k with t_k <= T for given integer d_k."""
        d = np.asarray(d, dtype=float)
        with np.errstate(divide="ignore", invalid="ignore"):
            t = np.floor((T - self.c0 - self.c1 * d) / (self.c2 * d))
        t = np.where(d > 0, t, 0.0)
        return np.maximum(t, 0.0).astype(np.int64)


# ---------------------------------------------------------------------------
# Time-varying capacities (per-cycle drift)
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class CapacityDrift:
    """Seeded, jit-compatible per-cycle drift of a fleet's capacities.

    Two independent multiplicative processes, re-drawn each global cycle
    (block model: capacities are constant within a cycle):

      * compute drift — effective clock f_k jitters by a uniform factor in
        ``[1 - clock_jitter, 1 + clock_jitter]`` (thermal throttling,
        co-tenant load), scaling C2_k by its inverse;
      * channel fading — the achievable rate R_k is multiplied by a clipped
        lognormal shadowing factor ``10^(X/10)``, X ~ N(0, fading_sigma_db)
        clipped to ±fading_clip_db (log-distance shadowing re-drawn per
        cycle), scaling C1_k and C0_k by its inverse.

    ``factors_at`` uses ``jax.random.fold_in(key(seed), cycle)`` so it is
    traceable on a traced cycle index (usable inside ``lax.scan``) and the
    whole path is reproducible from ``seed`` alone. Draws are generated in
    float32 regardless of the x64 flag, so the random bits are identical in
    every compilation context; the one transcendental (the dB -> linear
    ``10^(x/10)``) is requested in float64 and rounded once to float32,
    which keeps jit-fused and eager/vmapped evaluations within 1 f32 ULP of
    each other (XLA may narrow the widened pow under jit, so exact bitwise
    equality across compilation contexts is NOT guaranteed — only the
    integer allocations derived from the rows are, pinned by the
    fused-vs-eager orchestrator equivalence tests).
    """

    clock_jitter: float = 0.1
    fading_sigma_db: float = 2.0
    fading_clip_db: float = 6.0
    seed: int = 0

    def factors_at(self, cycle, k: int):
        """(clock_factor, rate_factor), each (K,) float32, for one cycle."""
        import jax
        import jax.numpy as jnp

        key = jax.random.fold_in(jax.random.key(self.seed), cycle)
        kc, kf = jax.random.split(key)
        clock = 1.0 + self.clock_jitter * (
            2.0 * jax.random.uniform(kc, (k,), jnp.float32) - 1.0
        )
        db = jnp.clip(
            self.fading_sigma_db * jax.random.normal(kf, (k,), jnp.float32),
            -self.fading_clip_db, self.fading_clip_db,
        )
        # f64 pow + one rounding: bit-stable across jit/eager/vmap contexts
        # (falls back to plain f32 pow when x64 is disabled)
        rate = jnp.power(
            jnp.asarray(10.0, jnp.float64), db.astype(jnp.float64) / 10.0
        ).astype(jnp.float32)
        return clock, rate

    def coefficient_path(
        self, tm: "TimeModel", cycles: int
    ) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
        """Drifted (c2, c1, c0) float64 numpy arrays of shape (C, K); row c
        is the fleet's true capacity during global cycle c. Runs under
        ``jax.enable_x64`` so the factors match the traced in-scan
        ``factors_at`` consumers as closely as the compiler allows (within
        1 f32 ULP; see class docstring)."""
        import jax
        import jax.numpy as jnp

        k = tm.num_learners
        with jax.enable_x64(True):
            clock, rate = jax.vmap(lambda c: self.factors_at(c, k))(
                jnp.arange(cycles)
            )
        clock = np.asarray(clock, np.float64)
        rate = np.asarray(rate, np.float64)
        return tm.c2[None] / clock, tm.c1[None] / rate, tm.c0[None] / rate


# ---------------------------------------------------------------------------
# State-coupled capacities (queue-driven drift)
# ---------------------------------------------------------------------------

def is_state_coupled(drift) -> bool:
    """True when ``drift`` follows the state-coupled protocol: it carries
    per-fleet state through the run (``state_init`` / ``state_update``) and
    its ``factors_at`` takes that state as a third argument. Consumers use
    this to decide whether the capacity rows can be materialized up front
    (exogenous drift — ``CapacityDrift.coefficient_path``) or must be
    rolled out jointly with the allocations (``QueueDrift.rollout`` on the
    host, the scan carry on the fused path)."""
    return hasattr(drift, "state_update") and hasattr(drift, "state_init")


@dataclasses.dataclass(frozen=True)
class QueueDrift:
    """State-coupled capacity drift: per-learner congestion queues driven by
    the work the allocator itself dispatches.

    ``CapacityDrift`` models exogenous rate/clock processes; real edge
    fleets additionally couple capacity to system state — a learner that
    keeps receiving more than its fair share of samples builds a transfer
    backlog that degrades its achievable rate (queueing at the access
    point, contention on the shared channel). This class closes that loop:

      * **state** — a ``(K,)`` float32 backlog vector ``q``, one queue per
        learner, starting at ``state_init(k)`` (zeros);
      * **dynamics** (``state_update``) — after the cycle's allocation
        ``(tau, d)`` is served, each queue moves by the learner's load
        relative to its fair share,  ``q' = clip(q + gain * (d_k * K /
        sum(d) - service), 0, q_max)`` — a learner at fair share
        (``load = service = 1``) holds its backlog, an over-loaded learner
        accumulates, an under-loaded one drains;
      * **capacity coupling** (``factors_at``) — the achievable rate R_k is
        degraded by the backlog, ``rate_factor = 1 / (1 + congestion *
        q_k)``, scaling C1_k and C0_k by its inverse (the same lever
        ``CapacityDrift`` fades); compute (C2_k) is untouched unless a
        ``base`` exogenous drift is composed on top.

    ``factors_at(cycle, k, state)`` is the state-coupled overload of
    ``CapacityDrift.factors_at(cycle, k)``: same return convention
    ((clock, rate) float32 factor pairs), with the extra ``state``
    argument read from the fused scan's carry. All queue arithmetic is
    elementwise float32 with no transcendentals, so traced (in-scan) and
    host (``rollout``) evaluations are **bitwise identical**; composing a
    ``base`` ``CapacityDrift`` re-introduces that class's documented
    1-f32-ULP pow caveat.

    Because the capacities of cycle c depend on the allocations of cycles
    < c, there is no standalone coefficient path: rows and allocations
    must be produced together, either sequentially on the host
    (``rollout``, used by the eager orchestrator and the async engine's
    scheduler) or inside the fused scan (``Orchestrator.run_fused(
    reallocate=True)``, where ``factors_at`` reads the queue state from
    the scan carry and no host coefficient path enters the program).
    """

    congestion: float = 0.3     # rate degradation per unit backlog
    gain: float = 1.0           # backlog added per unit of excess load
    service: float = 1.0        # fair-share load served per cycle
    q_max: float = 8.0          # backlog clip (bounded buffers)
    base: CapacityDrift | None = None   # exogenous drift composed on top

    def state_init(self, k: int):
        """Initial (K,) float32 backlog: empty queues."""
        import jax.numpy as jnp

        return jnp.zeros((k,), jnp.float32)

    def factors_at(self, cycle, k: int, state):
        """(clock_factor, rate_factor), each (K,) float32, for one cycle
        given the current backlog ``state``. The state-coupled overload of
        ``CapacityDrift.factors_at`` — jit-compatible on a traced cycle
        index AND a traced state (the fused scan's carry)."""
        import jax.numpy as jnp

        if self.base is not None:
            clock, rate = self.base.factors_at(cycle, k)
        else:
            clock = jnp.ones((k,), jnp.float32)
            rate = jnp.ones((k,), jnp.float32)
        q = jnp.asarray(state, jnp.float32)
        rate = rate / (1.0 + jnp.float32(self.congestion) * q)
        return clock, rate

    def state_update(self, cycle, state, tau, d):
        """Next (K,) float32 backlog after serving allocation ``(tau, d)``.

        ``load_k = d_k * K / sum(d)`` is the learner's share of the cycle's
        transfer volume relative to fair share (the sum is exact integer
        arithmetic; everything after is elementwise f32, bit-stable across
        jit/eager contexts). ``tau`` is accepted for protocol generality
        (compute-queue models would read it) but unused here; ``cycle``
        likewise (time-varying service rates would read it)."""
        import jax.numpy as jnp

        del cycle, tau
        k = d.shape[-1]
        tot = jnp.maximum(jnp.sum(d), 1).astype(jnp.float32)
        load = d.astype(jnp.float32) * jnp.float32(k) / tot
        q = jnp.asarray(state, jnp.float32)
        q = q + jnp.float32(self.gain) * (load - jnp.float32(self.service))
        return jnp.clip(q, 0.0, jnp.float32(self.q_max))

    def rollout_iter(self, tm: "TimeModel", cycles: int, solve):
        """Lazy host-side rollout of the coupled system: per cycle,
        evaluate the drifted (c2, c1, c0) row from the current queue
        state, call ``solve(cycle, c2_row, c1_row, c0_row) -> (tau, d)``
        (integer (K,) arrays), advance the state with that allocation, and
        yield ``(c2_row, c1_row, c0_row, tau, d)``. Laziness lets a
        consumer interleave its own per-cycle work (the eager
        orchestrator trains between solves, so an infeasible cycle raises
        only AFTER the feasible prefix ran — the same contract as the
        fused scan's in-scan guard).

        The factor math runs under ``jax.enable_x64`` (entered per cycle so
        the flag never leaks into consumer code between yields) with
        f32-pinned draws, exactly like ``CapacityDrift.coefficient_path``,
        so the rows match the fused scan's in-scan ``factors_at``
        consumers (bitwise for the queue coupling; 1 f32 ULP when a
        ``base`` drift composes its pow). Raises whatever ``solve``
        raises (e.g. infeasibility) at the first offending cycle."""
        import jax
        import jax.numpy as jnp

        k = tm.num_learners
        state = None
        for c in range(cycles):
            with jax.enable_x64(True):
                if state is None:
                    state = self.state_init(k)
                clock, rate = self.factors_at(c, k, state)
                clock = np.asarray(clock, np.float64)
                rate = np.asarray(rate, np.float64)
                c2r = tm.c2 / clock
                c1r = tm.c1 / rate
                c0r = tm.c0 / rate
                tau, d = solve(c, c2r, c1r, c0r)
                state = self.state_update(
                    c, state, jnp.asarray(tau), jnp.asarray(d)
                )
            yield c2r, c1r, c0r, tau, d

    def rollout(self, tm: "TimeModel", cycles: int, solve):
        """Eager collection of ``rollout_iter``: returns
        ``((c2s, c1s, c0s), (taus, ds))`` — (C, K) float64 rows and (C, K)
        int64 allocations (see ``rollout_iter`` for semantics)."""
        k = tm.num_learners
        c2s = np.empty((cycles, k))
        c1s = np.empty((cycles, k))
        c0s = np.empty((cycles, k))
        taus = np.zeros((cycles, k), np.int64)
        ds = np.zeros((cycles, k), np.int64)
        for c, (c2r, c1r, c0r, tau, d) in enumerate(
            self.rollout_iter(tm, cycles, solve)
        ):
            c2s[c], c1s[c], c0s[c] = c2r, c1r, c0r
            taus[c], ds[c] = tau, d
        return (c2s, c1s, c0s), (taus, ds)


# ---------------------------------------------------------------------------
# Reference environments
# ---------------------------------------------------------------------------

def indoor_80211_profile(
    k: int,
    *,
    seed: int = 0,
    radius_m: float = 50.0,
    bandwidth_hz: float = 5e6,
    tx_power_w: float = 0.1,
    noise_psd: float = 4e-21,
    fast_clock_hz: float = 2.4e9,
    slow_clock_hz: float = 0.7e9,
) -> list[LearnerProfile]:
    """The paper's simulation environment (Sec. V-A): K nodes within a 50 m
    radius over 802.11-type links; ~half are desktop/laptop class, half are
    Raspberry-Pi class. Path loss follows a standard indoor log-distance
    model (Table 1 of ref [9]: PL(d) = PL0 + 10 n log10(d), n ~= 3,
    PL0 ~= 40 dB at 1 m, plus lognormal shadowing sigma = 4 dB).
    """
    rng = np.random.default_rng(seed)
    dist = rng.uniform(2.0, radius_m, size=k)
    pl_db = 40.0 + 10.0 * 3.0 * np.log10(dist) + rng.normal(0.0, 4.0, size=k)
    gains = 10.0 ** (-pl_db / 10.0)
    profiles = []
    for i in range(k):
        fast = i % 2 == 0
        clock = fast_clock_hz if fast else slow_clock_hz
        # mild per-node compute jitter (thermal throttling etc.)
        clock *= rng.uniform(0.9, 1.1)
        profiles.append(
            LearnerProfile(
                clock_hz=clock,
                channel=ChannelParams(
                    bandwidth_hz=bandwidth_hz,
                    tx_power_w=tx_power_w,
                    gain=float(gains[i]),
                    noise_psd=noise_psd,
                ),
                name=f"{'edge' if fast else 'mcu'}-{i}",
            )
        )
    return profiles


def pod_slice_profile(
    k: int,
    *,
    seed: int = 0,
    chips_per_slice: int = 256,
    peak_flops: float = 197e12,
    mfu_range: tuple[float, float] = (0.3, 0.55),
    dcn_gbps_range: tuple[float, float] = (25.0, 100.0),
) -> list[LearnerProfile]:
    """TPU-native adaptation: each 'learner' is a pod slice with an effective
    throughput (chips x peak x MFU) and a DCN link to the orchestrator.
    The Shannon-rate channel is replaced by a fixed-rate DCN link encoded as
    an equivalent (W, SNR) pair with rate == dcn_gbps.
    """
    rng = np.random.default_rng(seed)
    profiles = []
    for i in range(k):
        mfu = rng.uniform(*mfu_range)
        flops = chips_per_slice * peak_flops * mfu
        rate_bps = rng.uniform(*dcn_gbps_range) * 1e9
        # encode the fixed rate: W = rate, SNR = 1 -> W*log2(2) = rate
        ch = ChannelParams(
            bandwidth_hz=rate_bps,
            tx_power_w=1.0,
            gain=1.0,
            noise_psd=1.0 / rate_bps,
        )
        profiles.append(LearnerProfile(clock_hz=flops, channel=ch, name=f"slice-{i}"))
    return profiles
