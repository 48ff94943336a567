"""Energy-constrained allocation (``core/energy.py`` + the ``kkt_energy``
pipeline): model construction, the infinite-budget equivalence to
``kkt_sai`` (architecture invariant 7), budget satisfaction by
construction across every solve path, feasible-or-degraded affordability
masking, ``BatteryDrift`` charge dynamics, the async joule ledger, and
the ``-O``-proof ``Allocation.validate`` rejection surface."""

import dataclasses
import subprocess
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import (
    AllocationProblem,
    BatchedProblems,
    BatteryDrift,
    EnergyModel,
    TimeModel,
    batched_policy,
    indoor_80211_profile,
    solve_energy_batched,
    solve_kkt_batched,
    solve_kkt_energy,
    solve_kkt_sai,
)
from repro.data.pipeline import synthetic_mnist
from repro.fed.async_engine import (
    AsyncConfig,
    AsyncFedEngine,
    summarize_async_history,
)
from repro.fed.orchestrator import solve_policy_row, solve_rows_availability
from repro.models import mlp

K = 4


def _models(k: int = K, seed: int = 0):
    profiles = indoor_80211_profile(k, seed=seed)
    tm = TimeModel.build(profiles, model_complexity_flops=1e6,
                         model_size_bits=8e6)
    em = EnergyModel.build(profiles, model_complexity_flops=1e6,
                           model_size_bits=8e6)
    return tm, em


def _prob(e_budget=None, *, total: int = 200, T: float = 5.0, seed: int = 0):
    tm, em = _models(seed=seed)
    return AllocationProblem(
        time_model=tm, T=T, total_samples=total, d_lower=10, d_upper=100,
        energy=em, e_budget=e_budget,
    )


def _energy(prob, alloc):
    return prob.energy.cycle_energy(alloc.tau, alloc.d)


# ---------------------------------------------------------------------------
# the model
# ---------------------------------------------------------------------------

def test_energy_model_shape_and_idle_cost():
    _, em = _models()
    tau = np.array([3, 1, 2, 4]); d = np.array([30, 20, 0, 25])
    e = em.cycle_energy(tau, d)
    assert e.shape == (K,)
    assert e[2] == 0.0                       # idle learner spends nothing
    assert np.all(e[d > 0] >= em.min_dispatch_energy()[d > 0] * (1 - 1e-12))
    # rows: f64, broadcast scalar budget, +inf default
    e2, e1, e0, eb = em.rows(e_budget=3.0)
    assert all(a.dtype == np.float64 for a in (e2, e1, e0, eb))
    np.testing.assert_array_equal(eb, np.full(K, 3.0))
    assert np.isinf(em.rows()[3]).all()


# ---------------------------------------------------------------------------
# invariant 7: infinite budget == kkt_sai, decision for decision
# ---------------------------------------------------------------------------

def test_infinite_budget_reproduces_kkt_sai_everywhere():
    """Pinned: eb = inf is a bitwise no-op through the NumPy reference,
    the batched program AND the traced policy."""
    for seed in range(4):
        prob = _prob(seed=seed)
        free = dataclasses.replace(prob, e_budget=np.inf)
        ref = solve_kkt_sai(prob)

        a_np = solve_kkt_energy(free)
        np.testing.assert_array_equal(a_np.tau, ref.tau)
        np.testing.assert_array_equal(a_np.d, ref.d)

        bp = BatchedProblems.from_problems([free])
        ba = solve_energy_batched(bp)
        np.testing.assert_array_equal(ba.tau[0], ref.tau)
        np.testing.assert_array_equal(ba.d[0], ref.d)
        ref_b = solve_kkt_batched(BatchedProblems.from_problems([prob]))
        np.testing.assert_array_equal(ba.tau, ref_b.tau)
        np.testing.assert_array_equal(ba.d, ref_b.d)

        with jax.enable_x64(True):
            args = tuple(jnp.asarray(a) for a in (
                bp.c2, bp.c1, bp.c0, bp.T, bp.total,
                bp.d_lo, bp.d_hi, bp.valid,
            ))
            en = tuple(jnp.asarray(r) for r in bp.energy_rows())
            tau_t, d_t, feas = batched_policy("kkt_energy")(*args, en)
        np.testing.assert_array_equal(np.asarray(tau_t[0]), ref.tau)
        np.testing.assert_array_equal(np.asarray(d_t[0]), ref.d)
        assert bool(feas[0])


# ---------------------------------------------------------------------------
# finite budgets: satisfaction by construction, blind schemes violate
# ---------------------------------------------------------------------------

def test_budget_satisfied_by_construction_and_blind_violates():
    prob = _prob()
    blind = solve_kkt_sai(prob)
    e_blind = _energy(prob, blind)
    eb = 0.8 * float(np.median(e_blind))    # tight: blind must overdraw
    assert (e_blind > eb).any()

    tight = dataclasses.replace(prob, e_budget=eb)
    alloc = solve_kkt_energy(tight)
    assert np.all(_energy(prob, alloc) <= eb * (1 + 1e-9))
    alloc.validate(tight)                    # strict check passes

    ba = solve_energy_batched(BatchedProblems.from_problems([tight]))
    np.testing.assert_array_equal(ba.tau[0], alloc.tau)
    np.testing.assert_array_equal(ba.d[0], alloc.d)

    # the traced policy row used by the orchestrator/async re-solves
    tm = prob.time_model
    tau_r, d_r = solve_policy_row(
        "kkt_energy", tm.c2, tm.c1, tm.c0, tight, label="test row",
    )
    np.testing.assert_array_equal(tau_r, alloc.tau)
    np.testing.assert_array_equal(d_r, alloc.d)


def test_validate_rejects_overdrawn_allocation():
    prob = _prob()
    blind = solve_kkt_sai(prob)
    eb = 0.8 * float(np.median(_energy(prob, blind)))
    tight = dataclasses.replace(prob, e_budget=eb)
    with pytest.raises(ValueError, match="energy budget violated"):
        blind.validate(tight)
    # ... which is why energy-blind schemes cannot SOLVE a strict
    # budgeted problem at all (their own self-validation trips)
    with pytest.raises(ValueError, match="energy budget violated"):
        solve_kkt_sai(tight)


def test_validate_raises_under_dash_O_semantics():
    """Satellite regression: ``Allocation.validate`` must reject garbage
    through ValueErrors, not bare asserts — ``python -O`` strips asserts,
    so each check is exercised in an optimized subprocess."""
    code = """
import numpy as np
from repro.core import AllocationProblem, TimeModel
from repro.core.allocation import Allocation

tm = TimeModel(c2=np.full(3, 0.04), c1=np.full(3, 0.004), c0=np.full(3, 0.4))
prob = AllocationProblem(time_model=tm, T=6.0, total_samples=60,
                         d_lower=10, d_upper=40)
bad = [
    Allocation(tau=np.array([1, 1]), d=np.array([20, 20])),          # shape
    Allocation(tau=np.array([1, 1, 1]), d=np.array([20, 20, 21])),   # sum
    Allocation(tau=np.array([1, 1, 1]), d=np.array([5, 25, 30])),    # bounds
    Allocation(tau=np.array([-1, 1, 1]), d=np.array([20, 20, 20])),  # tau < 0
    Allocation(tau=np.array([99, 1, 1]), d=np.array([20, 20, 20])),  # deadline
]
n = 0
for a in bad:
    try:
        a.validate(prob)
    except ValueError:
        n += 1
assert __debug__ is False, "subprocess must run under -O"
print("caught", n)
"""
    out = subprocess.run(
        [sys.executable, "-O", "-c", code],
        capture_output=True, text=True, check=True,
    )
    assert "caught 5" in out.stdout


def test_feasible_or_degraded_affordability():
    """A learner whose budget cannot cover d_lower degrades to a padded
    slot; the sample budget clips into the surviving fleet's box."""
    prob = _prob()
    em = prob.energy
    # learner 0 cannot afford its minimal dispatch; the rest are free
    eb = np.full(K, np.inf)
    eb[0] = 0.5 * float(em.cycle_energy(
        np.ones(K, np.int64), np.full(K, prob.d_lower, np.int64))[0])
    alloc = solve_kkt_energy(dataclasses.replace(prob, e_budget=eb))
    assert alloc.tau[0] == 0 and alloc.d[0] == 0
    assert (alloc.d[1:] > 0).all()
    assert alloc.d.sum() <= prob.total_samples
    # all-unaffordable: everything degrades to zeros, no raise
    starved = solve_kkt_energy(dataclasses.replace(
        prob, e_budget=0.25 * em.min_dispatch_energy().min()))
    assert (starved.tau == 0).all() and (starved.d == 0).all()


def test_kkt_energy_pallas_requires_f32():
    bp = BatchedProblems.from_problems([_prob(e_budget=5.0)])
    with pytest.raises(ValueError, match="x64=False"):
        solve_energy_batched(bp, use_pallas=True)


def test_kkt_energy_pallas_interpret_matches_reference():
    """The Pallas residual kernel behind ``use_pallas=True`` lands on the
    same integer decisions as the jnp f32 reference (interpret mode)."""
    probs = [_prob(e_budget=5.0, seed=s) for s in range(3)]
    bp = BatchedProblems.from_problems(probs)
    ref = solve_energy_batched(bp, x64=False)
    pal = solve_energy_batched(bp, x64=False, use_pallas=True, interpret=True)
    np.testing.assert_array_equal(pal.tau, ref.tau)
    np.testing.assert_array_equal(pal.d, ref.d)
    np.testing.assert_array_equal(pal.feasible, ref.feasible)


# ---------------------------------------------------------------------------
# BatteryDrift
# ---------------------------------------------------------------------------

def test_battery_drift_dynamics_and_determinism():
    _, em = _models()
    bd = BatteryDrift(energy=em, capacity_j=10.0, recharge_j=1.0,
                      p_plugged=0.5, seed=3)
    state = bd.state_init(K)
    assert np.allclose(np.asarray(state), 10.0)
    tau = jnp.asarray(np.full(K, 2, np.int64))
    d = jnp.asarray(np.array([30, 0, 20, 25], np.int64))
    drained = bd.state_update(0, state, tau=tau, d=d)
    cost = em.cycle_energy(np.asarray(tau), np.asarray(d))
    # idle learner only recharges; busy learners drain their joule cost
    assert float(np.asarray(drained)[1]) >= 10.0 - 1e-6
    assert np.all(np.asarray(drained) >= -1e-6)
    assert np.all(np.asarray(drained) <= 10.0 + 1e-6)
    spent = 10.0 - np.asarray(drained, np.float64)
    assert np.all(spent[cost > 0] <= cost[cost > 0] + 1e-5)
    # deterministic per (seed, cycle)
    again = bd.state_update(0, bd.state_init(K), tau=tau, d=d)
    np.testing.assert_array_equal(np.asarray(drained), np.asarray(again))
    # flat battery = offline; full battery = online
    assert not bool(np.asarray(
        bd.online_at(1, K, jnp.zeros((K,), jnp.float32))).any())
    assert bool(np.asarray(bd.online_at(1, K, state)).all())
    # budget_at exposes the charge as the per-dispatch solve cap (f64)
    b = bd.budget_at(1, K, drained)
    assert b.dtype == np.float64
    np.testing.assert_allclose(b, np.asarray(drained, np.float64))


def test_battery_rollout_never_overdraws_the_charge():
    prob = _prob(total=120)
    bd = BatteryDrift(energy=prob.energy, capacity_j=7.0, recharge_j=0.8,
                      p_plugged=0.5, seed=11)
    _, (taus, ds), masks = solve_rows_availability(
        "kkt_energy", bd, prob, 10, label="cycle {}")
    assert (ds[~masks] == 0).all() and (taus[~masks] == 0).all()
    state = bd.state_init(K)
    for c in range(10):
        charge = np.asarray(state, np.float64)
        cost = prob.energy.cycle_energy(taus[c], ds[c])
        assert np.all(cost <= charge * (1 + 1e-6) + 1e-9), (c, cost, charge)
        state = bd.state_update(c, state, tau=jnp.asarray(taus[c]),
                                d=jnp.asarray(ds[c]))


# ---------------------------------------------------------------------------
# async energy accounting: the seeded property sweep
# ---------------------------------------------------------------------------

def _async_cfg(mode: str):
    if mode == "cycle":
        return AsyncConfig(mode="buffered", barrier=True, scheme="kkt_energy")
    if mode == "buffered":
        return AsyncConfig(mode="buffered", buffer_size=2,
                           scheme="kkt_energy", reallocate=True)
    return AsyncConfig(mode="fedasync", scheme="kkt_energy", reallocate=True)


@pytest.mark.parametrize("mode", ["fedasync", "buffered", "cycle"])
@pytest.mark.parametrize("budget_frac", [0.6, 1.0, np.inf])
@pytest.mark.parametrize("battery", [False, True])
def test_async_sweep_zero_violations(mode, budget_frac, battery):
    """budgets x drift x async modes: every dispatched task fits its
    budget (ledger violations == 0) while the fleet stays
    feasible-or-degraded — no cell may stall or raise."""
    prob0 = _prob(total=120, T=5.0)
    blind = solve_kkt_sai(prob0)
    eb = (np.inf if np.isinf(budget_frac)
          else float(budget_frac) * float(np.median(_energy(prob0, blind))))
    prob = dataclasses.replace(prob0, e_budget=eb)
    drift = (BatteryDrift(energy=prob0.energy, capacity_j=8.0,
                          recharge_j=1.0, p_plugged=0.5, seed=5)
             if battery else None)
    if battery and mode == "cycle":
        pytest.skip("the barrier regime is the fault-free paper path")
    train, _ = synthetic_mnist(1200, n_test=10, seed=0)
    params = mlp.init(jax.random.key(1))
    eng = AsyncFedEngine(_async_cfg(mode), prob, mlp.loss, params,
                         seed=7, drift=drift)
    if mode == "cycle":
        history = eng.run(train, cycles=3)
    else:
        history = eng.run(train, 3 * prob.T)
    s = summarize_async_history(history, counters=eng.fault_counters,
                                energy=eng.energy_ledger)
    assert s["energy"]["violations"] == 0
    assert s["aggregations"] > 0              # degraded, never dead
    assert s["energy"]["joules_total"] > 0
    # the ledger meters at DISPATCH (in-flight and dropped uploads burned
    # their joules too), so it bounds the flushed-history total from above
    per = np.asarray(s["energy"]["per_learner"])
    assert per.shape == (K,)
    assert per.sum() >= s["energy"]["joules_total"] * (1 - 1e-9)
    # replay every metered dispatch against the static budget
    if not battery and np.isfinite(eb):
        for rec in history:
            e = np.atleast_1d(rec.get("energy", []))
            assert np.all(e <= eb * (1 + 1e-9))


def test_async_ledger_eager_matches_jagged():
    prob = _prob(total=120, e_budget=6.0)
    train, _ = synthetic_mnist(1200, n_test=10, seed=0)
    params = mlp.init(jax.random.key(2))
    cfg = AsyncConfig(mode="buffered", buffer_size=2, scheme="kkt_energy",
                      reallocate=True)
    h_e = AsyncFedEngine(cfg, prob, mlp.loss, params, seed=3).run(
        train, 2 * prob.T)
    h_j = AsyncFedEngine(cfg, prob, mlp.loss, params, seed=3).run_events(
        train, 2 * prob.T)
    assert len(h_e) == len(h_j) > 0
    for r1, r2 in zip(h_e, h_j):
        np.testing.assert_array_equal(
            np.atleast_1d(r1["energy"]), np.atleast_1d(r2["energy"]))


def test_plain_problem_reports_zero_energy():
    tm, _ = _models()
    prob = AllocationProblem(time_model=tm, T=5.0, total_samples=120,
                             d_lower=10, d_upper=100)
    train, _ = synthetic_mnist(1200, n_test=10, seed=0)
    eng = AsyncFedEngine(AsyncConfig(mode="fedasync"), prob, mlp.loss,
                         mlp.init(jax.random.key(0)), seed=1)
    h = eng.run(train, prob.T)
    s = summarize_async_history(h, energy=eng.energy_ledger)
    assert s["energy"]["joules_total"] == 0.0
    assert s["energy"]["violations"] == 0
    np.testing.assert_array_equal(s["energy"]["per_learner"], np.zeros(K))
