"""Property tests for core/aggregation.py and the staleness helpers, plus
the CapacityDrift seed-determinism pin (host coefficient_path vs per-cycle
traced factors_at)."""

import numpy as np

import jax
import jax.numpy as jnp

from repro.core import (
    CapacityDrift,
    TimeModel,
    aggregate,
    fedavg_weights,
    staleness_weights,
)
from repro.core.staleness import (
    staleness_factor,
    version_staleness,
    version_staleness_profile,
)

from tests._prop import given, settings, st


@settings(max_examples=25, deadline=None)
@given(seed=st.integers(0, 2**20), k=st.integers(1, 12),
       gamma=st.floats(0.1, 5.0))
def test_staleness_weights_zero_staleness_is_fedavg(seed, k, gamma):
    """With every tau equal, the staleness discount is 1 for all learners
    and the weights reduce to FedAvg exactly."""
    rng = np.random.default_rng(seed)
    tau = np.full(k, int(rng.integers(0, 50)))
    d = rng.integers(1, 500, size=k)
    np.testing.assert_allclose(
        staleness_weights(tau, d, gamma=gamma), fedavg_weights(d)
    )


@settings(max_examples=25, deadline=None)
@given(seed=st.integers(0, 2**20), k=st.integers(2, 12),
       gamma=st.floats(0.1, 5.0))
def test_staleness_weights_permutation_equivariant(seed, k, gamma):
    """Relabeling learners permutes the weights the same way (no hidden
    positional dependence), and the weights always form a distribution
    that downweights stale learners."""
    rng = np.random.default_rng(seed)
    tau = rng.integers(0, 30, size=k)
    d = rng.integers(1, 500, size=k)
    w = staleness_weights(tau, d, gamma=gamma)
    np.testing.assert_allclose(w.sum(), 1.0)
    perm = rng.permutation(k)
    np.testing.assert_allclose(
        staleness_weights(tau[perm], d[perm], gamma=gamma), w[perm]
    )
    # stalest learner never outweighs a fresher learner with >= data
    i = int(np.argmin(tau))   # most stale (tau_max - tau largest)
    j = int(np.argmax(tau))
    if d[i] <= d[j]:
        assert w[i] <= w[j] + 1e-12


@settings(max_examples=20, deadline=None)
@given(seed=st.integers(0, 2**20), k=st.integers(1, 6))
def test_aggregate_is_weighted_mean(seed, k):
    """aggregate() reproduces the numpy weighted sum on every leaf."""
    rng = np.random.default_rng(seed)
    models = {
        "w": jnp.asarray(rng.standard_normal((k, 4, 3)).astype(np.float32)),
        "b": jnp.asarray(rng.standard_normal((k, 5)).astype(np.float32)),
    }
    w = rng.random(k).astype(np.float32) + 0.1
    w /= w.sum()
    out = aggregate(models, jnp.asarray(w))
    for name in models:
        ref = np.tensordot(w, np.asarray(models[name]), axes=(0, 0))
        np.testing.assert_allclose(np.asarray(out[name]), ref, atol=1e-6)


def test_staleness_factor_properties():
    s = np.arange(0, 20)
    for kind in ("constant", "hinge", "poly"):
        f = staleness_factor(s, kind=kind, a=0.5, b=4.0)
        assert np.all(f <= 1.0 + 1e-12) and np.all(f > 0)
        assert np.all(np.diff(f) <= 1e-12)          # non-increasing
        assert staleness_factor(0, kind=kind) == 1.0
    # hinge is flat until the knee, then decays
    h = staleness_factor(s, kind="hinge", a=0.5, b=4.0)
    assert np.all(h[:5] == 1.0) and h[5] < 1.0
    # version staleness clamps at zero
    np.testing.assert_array_equal(
        version_staleness([3, 5, 2], [1, 5, 4]), [2, 0, 0]
    )
    prof = version_staleness_profile([0, 1, 2, 3])
    assert prof["max"] == 3 and prof["count"] == 4 and prof["frac_stale"] == 0.75


# ---------------------------------------------------------------------------
# CapacityDrift: host path vs traced per-cycle factors
# ---------------------------------------------------------------------------

def test_capacity_drift_path_matches_traced_factors_at():
    """``coefficient_path`` (the host materialization the eager paths use)
    replays the per-cycle ``factors_at`` sequence the fused scan evaluates
    on the traced cycle index. The f32 random draws are bit-identical in
    both contexts; the dB->linear transcendental may differ by 1 f32 ULP
    between jit-fused and eager compilation (the documented contract), so
    the rows are pinned to ULP tolerance AND the derived integer
    allocations are pinned exactly."""
    k = 7
    tm = TimeModel(
        c2=np.linspace(1e-4, 5e-3, k),
        c1=np.linspace(1e-5, 1e-3, k),
        c0=np.linspace(0.05, 0.5, k),
    )
    drift = CapacityDrift(clock_jitter=0.2, fading_sigma_db=2.5, seed=123)
    cycles = 6
    c2s, c1s, c0s = drift.coefficient_path(tm, cycles)

    @jax.jit
    def traced_row(c):
        clock, rate = drift.factors_at(c, k)
        f64 = jnp.float64
        return (jnp.asarray(tm.c2, f64) / clock.astype(f64),
                jnp.asarray(tm.c1, f64) / rate.astype(f64),
                jnp.asarray(tm.c0, f64) / rate.astype(f64))

    from repro.core import AllocationProblem
    from repro.fed.orchestrator import _jitted_policy, policy_problem_args

    prob = AllocationProblem(time_model=tm, T=1.0, total_samples=70,
                             d_lower=2, d_upper=40)
    policy = _jitted_policy("kkt_sai")
    T1, total1, lo1, hi1, valid1 = policy_problem_args(prob)

    with jax.enable_x64(True):
        for c in range(cycles):
            r2, r1, r0 = traced_row(c)
            # clock factors divide exactly; rate-driven rows to 1 f32 ULP
            np.testing.assert_array_equal(np.asarray(r2), c2s[c])
            np.testing.assert_allclose(np.asarray(r1), c1s[c], rtol=2e-7)
            np.testing.assert_allclose(np.asarray(r0), c0s[c], rtol=2e-7)
            # ...and the integer allocations agree exactly
            args = (jnp.asarray(T1), jnp.asarray(total1), jnp.asarray(lo1),
                    jnp.asarray(hi1), jnp.asarray(valid1))
            ta, da, _ = policy(jnp.asarray(r2[None]), jnp.asarray(r1[None]),
                               jnp.asarray(r0[None]), *args)
            tb, db, _ = policy(jnp.asarray(c2s[c][None]),
                               jnp.asarray(c1s[c][None]),
                               jnp.asarray(c0s[c][None]), *args)
            np.testing.assert_array_equal(np.asarray(ta), np.asarray(tb))
            np.testing.assert_array_equal(np.asarray(da), np.asarray(db))


def test_capacity_drift_seed_determinism():
    """Same seed => identical path; different seed => different path."""
    k, cycles = 5, 4
    tm = TimeModel(c2=np.full(k, 1e-3), c1=np.full(k, 1e-4),
                   c0=np.full(k, 0.1))
    a = CapacityDrift(seed=9).coefficient_path(tm, cycles)
    b = CapacityDrift(seed=9).coefficient_path(tm, cycles)
    c = CapacityDrift(seed=10).coefficient_path(tm, cycles)
    for x, y in zip(a, b):
        np.testing.assert_array_equal(x, y)
    assert any(not np.array_equal(x, y) for x, y in zip(a, c))
