"""Batched allocation engine: per-problem equivalence with the NumPy
KKT+SAI pipeline, Pallas water-filling residual parity, mixed-K padding,
and the fused scan-over-cycles orchestrator against the eager loop."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from _prop import batched_problems, given, settings, st
from repro.core import (
    AllocationProblem,
    BatchedProblems,
    CapacityDrift,
    TimeModel,
    batched_avg_staleness,
    batched_max_staleness,
    indoor_80211_profile,
    mnist_dnn_cost,
    pod_slice_profile,
    solve_eta,
    solve_eta_batched,
    solve_kkt_batched,
    solve_kkt_sai,
    solve_pgd_batched,
)
from repro.core.solver_kkt import solve_relaxed


def make_problem(k=10, T=15.0, d=6000, seed=0, profile="edge"):
    cost = mnist_dnn_cost()
    profs = (
        indoor_80211_profile(k, seed=seed)
        if profile == "edge"
        else pod_slice_profile(k, seed=seed)
    )
    tm = TimeModel.build(
        profs,
        model_complexity_flops=cost.flops_per_sample,
        model_size_bits=cost.model_bits,
    )
    return AllocationProblem(
        time_model=tm,
        T=T,
        total_samples=d,
        d_lower=max(1, d // (4 * k)),
        d_upper=min(d, 3 * d // k),
    )


def _random_feasible_problems(n=30):
    """Randomized feasible instances across fleet sizes, budgets, profiles."""
    rng = np.random.default_rng(42)
    probs = []
    while len(probs) < n:
        k = int(rng.integers(3, 14))
        T = float(rng.choice([5.0, 7.5, 15.0, 30.0]))
        d = int(rng.choice([2000, 4000, 6000]))
        profile = str(rng.choice(["edge", "pod"]))
        seed = int(rng.integers(0, 10_000))
        prob = make_problem(k=k, T=T, d=d, seed=seed, profile=profile)
        try:
            solve_relaxed(prob)  # keep only time-feasible instances
        except ValueError:
            continue
        probs.append(prob)
    return probs


# ---------------------------------------------------------------------------
# solve_kkt_batched vs per-problem solve_kkt_sai
# ---------------------------------------------------------------------------

def test_kkt_batched_matches_per_problem_randomized():
    """Per-problem (tau, d) exact match over randomized feasible instances.

    Documented tie-break tolerance: the batched residual reduction can
    differ from NumPy's pairwise sum by last-ulp noise, which may shift
    tau* within the bisection tolerance and flip a remainder tie; we allow
    at most 10% such problems, and they must still be feasible with the
    same max staleness and per-entry |delta d| <= 2.
    """
    probs = _random_feasible_problems(30)
    refs = [solve_kkt_sai(p) for p in probs]
    ba = solve_kkt_batched(probs)
    assert bool(ba.feasible.all())

    mismatched = 0
    for i, (p, ref) in enumerate(zip(probs, refs)):
        got = ba.allocation(i)
        got.validate(p)
        if np.array_equal(got.tau, ref.tau) and np.array_equal(got.d, ref.d):
            continue
        mismatched += 1
        assert int(got.tau.max() - got.tau.min()) == int(ref.tau.max() - ref.tau.min())
        assert np.abs(got.d - ref.d).max() <= 2
    assert mismatched <= len(probs) // 10, f"{mismatched} tie-break mismatches"


@settings(max_examples=12, deadline=None)
@given(case=batched_problems())
def test_kkt_batched_property_mixed_degenerate(case):
    """Property: over mixed-K batches with degenerate (d_lo == d_hi) boxes
    and zero-capacity padded slots, every problem's batched solution matches
    the per-problem NumPy pipeline (same tie-break tolerance as the
    randomized equivalence test)."""
    probs, bp = case
    refs = [solve_kkt_sai(p) for p in probs]
    ba = solve_kkt_batched(bp)
    assert bool(ba.feasible.all())
    for i, (p, ref) in enumerate(zip(probs, refs)):
        got = ba.allocation(i)
        got.validate(p)
        # padded slots never carry work
        assert not ba.d[i, p.num_learners:].any()
        assert not ba.tau[i, p.num_learners:].any()
        if np.array_equal(got.tau, ref.tau) and np.array_equal(got.d, ref.d):
            continue
        assert int(got.tau.max() - got.tau.min()) == int(ref.tau.max() - ref.tau.min())
        assert np.abs(got.d - ref.d).max() <= 2


def test_kkt_batched_relaxed_matches_reference():
    probs = [make_problem(k=8, seed=s) for s in (0, 3, 7)]
    ba = solve_kkt_batched(probs)
    for i, p in enumerate(probs):
        tau_r, d_r, tau_star, _ = solve_relaxed(p)
        np.testing.assert_allclose(ba.relaxed_d[i, : p.num_learners], d_r, rtol=1e-8)
        np.testing.assert_allclose(ba.relaxed_tau[i, : p.num_learners], tau_r, rtol=1e-8)
        np.testing.assert_allclose(ba.tau_star[i], tau_star, rtol=1e-6)


def test_kkt_batched_mixed_fleet_sizes_padded():
    """Fleets of different K batch together via the valid mask."""
    probs = [make_problem(k=k, seed=k) for k in (4, 7, 11)]
    ba = solve_kkt_batched(probs)
    assert ba.tau.shape == (3, 11)
    for i, p in enumerate(probs):
        ref = solve_kkt_sai(p)
        got = ba.allocation(i)
        got.validate(p)
        np.testing.assert_array_equal(got.tau, ref.tau)
        np.testing.assert_array_equal(got.d, ref.d)
        # padded slots carry no work
        assert not ba.d[i, p.num_learners:].any()
        assert not ba.tau[i, p.num_learners:].any()


def test_kkt_batched_flags_infeasible():
    """A deadline too tight to absorb d is flagged, not silently solved,
    and does not poison the feasible problems sharing the batch."""
    ok = make_problem(k=6, T=15.0, d=2000)
    tm = ok.time_model
    bad = AllocationProblem(
        time_model=tm, T=float(np.max(tm.c0) * 1.01), total_samples=2000,
        d_lower=1, d_upper=2000,
    )
    with pytest.raises(ValueError):
        solve_relaxed(bad)
    ba = solve_kkt_batched([ok, bad])
    assert bool(ba.feasible[0]) and not bool(ba.feasible[1])
    ref = solve_kkt_sai(ok)
    np.testing.assert_array_equal(ba.allocation(0).tau, ref.tau)
    with pytest.raises(ValueError):
        ba.allocation(1)


def test_eta_batched_matches_per_problem():
    probs = [make_problem(k=k, T=7.5, seed=s) for k in (5, 9) for s in (0, 4)]
    be = solve_eta_batched(probs)
    for i, p in enumerate(probs):
        ref = solve_eta(p)
        got = be.allocation(i)
        got.validate(p)
        np.testing.assert_array_equal(got.tau, ref.tau)
        np.testing.assert_array_equal(got.d, ref.d)


def test_batched_staleness_metrics():
    tau = np.array([[3, 7, 5, 0], [2, 2, 2, 9]])
    valid = np.array([[True, True, True, False], [True, True, True, False]])
    np.testing.assert_array_equal(batched_max_staleness(tau, valid), [4, 0])
    np.testing.assert_allclose(
        batched_avg_staleness(tau, valid), [(4 + 2 + 2) / 3.0, 0.0]
    )


# ---------------------------------------------------------------------------
# Pallas water-filling residual kernel
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("b,k", [(4, 10), (8, 128), (13, 37), (3, 150)])
def test_waterfill_residual_pallas_parity(b, k):
    from repro.kernels import ops
    from repro.kernels.ref import waterfill_residual_ref

    rng = np.random.default_rng(b * 100 + k)
    c2 = jnp.asarray(rng.uniform(1e-4, 1e-2, (b, k)), jnp.float32)
    c1 = jnp.asarray(rng.uniform(1e-4, 1e-2, (b, k)), jnp.float32)
    c0 = jnp.asarray(rng.uniform(0.1, 2.0, (b, k)), jnp.float32)
    T = jnp.asarray(rng.uniform(5.0, 20.0, (b,)), jnp.float32)
    lo = jnp.full((b, k), 10.0, jnp.float32)
    hi = jnp.full((b, k), 900.0, jnp.float32)
    tot = jnp.asarray(rng.uniform(1e3, 5e3, (b,)), jnp.float32)
    tau = jnp.asarray(rng.uniform(0.0, 50.0, (b,)), jnp.float32)

    want = waterfill_residual_ref(tau, c2, c1, c0, T, lo, hi, tot)
    got = ops.waterfill_residual(
        tau, c2, c1, c0, T, lo, hi, tot, use_pallas=True, interpret=True
    )
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-3)


def test_kkt_batched_via_pallas_residual():
    """The full batched solve with every bisection step through the Pallas
    kernel (interpret mode, f32) stays feasible and near the f64 solution."""
    probs = [make_problem(k=6, T=15.0, d=2000, seed=s) for s in (0, 1)]
    ba64 = solve_kkt_batched(probs)
    ba32 = solve_kkt_batched(probs, x64=False, use_pallas=True, interpret=True)
    for i, p in enumerate(probs):
        got = ba32.allocation(i)
        got.validate(p)
        s64 = int(ba64.tau[i].max() - ba64.tau[i].min())
        s32 = int(ba32.tau[i, : p.num_learners].max() - ba32.tau[i, : p.num_learners].min())
        assert abs(s32 - s64) <= 1


# ---------------------------------------------------------------------------
# PGD routed through the BatchedProblems struct
# ---------------------------------------------------------------------------

def test_pgd_batched_struct_routing():
    probs = [make_problem(k=6, T=15.0, d=3000, seed=s) for s in range(4)]
    bp = BatchedProblems.from_problems(probs)
    tau, d = solve_pgd_batched(bp, steps=300)
    assert tau.shape == (4, 6) and d.shape == (4, 6)
    np.testing.assert_allclose(np.asarray(d.sum(1)), bp.total.astype(float), rtol=1e-3)
    assert np.all(np.asarray(d) >= bp.d_lo - 1e-3)
    assert np.all(np.asarray(d) <= bp.d_hi + 1e-3)


def test_pgd_batched_padded_mixed_k_regression():
    """Mixed-K padded batches solve exactly like their unpadded rows —
    regression for the pre-mask behavior where padded slots entered the
    smoothed staleness objective and the projection mass, silently skewing
    every real learner's d."""
    from repro.core.solver_numeric import _pgd_run

    small = make_problem(k=4, T=15.0, d=2000, seed=9)
    probs = [make_problem(k=6, T=15.0, d=3000, seed=0), small]
    bp = BatchedProblems.from_problems(probs)
    tau, d = solve_pgd_batched(bp, steps=300)
    tau, d = np.asarray(tau), np.asarray(d)

    # padded slots carry exactly zero work and zero tau
    assert not d[1, 4:].any() and not tau[1, 4:].any()
    for i, p in enumerate(probs):
        kk = p.num_learners
        np.testing.assert_allclose(d[i, :kk].sum(), p.total_samples, rtol=1e-3)
        assert np.all(d[i, :kk] >= p.d_lower - 1e-3)
        assert np.all(d[i, :kk] <= p.d_upper + 1e-3)

    # the padded row reproduces the standalone unpadded solve up to float
    # noise (padded slots contribute exact zeros, but the wider K axis
    # reassociates reductions, and 300 annealed steps amplify the ULPs)
    tm = small.time_model
    d0 = np.full(4, small.total_samples / 4, np.float32)
    tau_s, d_s = _pgd_run(
        jnp.asarray(d0), jnp.asarray(tm.c2, jnp.float32),
        jnp.asarray(tm.c1, jnp.float32), jnp.asarray(tm.c0, jnp.float32),
        jnp.float32(small.T), jnp.float32(small.d_lower),
        jnp.float32(small.d_upper), jnp.float32(small.total_samples), 300,
    )
    np.testing.assert_allclose(d[1, :4], np.asarray(d_s), rtol=1e-2, atol=1.0)
    np.testing.assert_allclose(tau[1, :4], np.asarray(tau_s), rtol=1e-2, atol=1.0)


# ---------------------------------------------------------------------------
# fused scan-over-cycles orchestrator vs eager loop
# ---------------------------------------------------------------------------

def test_fused_orchestrator_matches_eager_history():
    from repro.fed.simulation import run_experiment

    eager = run_experiment(k=4, T=15.0, cycles=3, total_samples=1200, seed=3)
    fused = run_experiment(k=4, T=15.0, cycles=3, total_samples=1200, seed=3,
                           fused=True)
    he, hf = eager["history"], fused["history"]
    assert len(he) == len(hf) == 3
    for re_, rf in zip(he, hf):
        np.testing.assert_array_equal(re_["tau"], rf["tau"])
        np.testing.assert_array_equal(re_["d"], rf["d"])
        assert re_["max_staleness"] == rf["max_staleness"]
        assert re_["cycle"] == rf["cycle"] and re_["elapsed_s"] == rf["elapsed_s"]
    np.testing.assert_allclose(
        [h["accuracy"] for h in he], [h["accuracy"] for h in hf], atol=1e-4
    )


def test_fused_realloc_matches_eager_drift_history():
    """run_fused(reallocate=True): the in-scan per-cycle KKT re-solve on
    drifted capacities reproduces the eager per-cycle-reallocation history
    (tau, d, shard draws) exactly for a fixed seed; accuracies agree to
    float tolerance (different zero-padding widths reassociate the masked
    loss reductions)."""
    from repro.fed.simulation import run_experiment

    drift = CapacityDrift(clock_jitter=0.15, fading_sigma_db=2.0, seed=5)
    kw = dict(k=4, T=15.0, cycles=3, total_samples=1200, seed=3,
              reallocate=True, drift=drift)
    eager = run_experiment(**kw)
    fused = run_experiment(**kw, fused=True)
    he, hf = eager["history"], fused["history"]
    assert len(he) == len(hf) == 3
    for re_, rf in zip(he, hf):
        np.testing.assert_array_equal(re_["tau"], rf["tau"])
        np.testing.assert_array_equal(re_["d"], rf["d"])
        assert re_["max_staleness"] == rf["max_staleness"]
        assert re_["cycle"] == rf["cycle"] and re_["elapsed_s"] == rf["elapsed_s"]
    # the drift actually moves the allocation between cycles
    taus = np.stack([h["tau"] for h in he])
    ds = np.stack([h["d"] for h in he])
    assert not (np.all(taus == taus[0]) and np.all(ds == ds[0]))
    np.testing.assert_allclose(
        [h["accuracy"] for h in he], [h["accuracy"] for h in hf], atol=5e-3
    )


def test_fused_realloc_policy_swap_eta():
    """The in-scan reallocation policy follows MELConfig.scheme: the eta
    baseline swaps in for the KKT pipeline and still matches its eager
    twin exactly."""
    from repro.fed.simulation import run_experiment

    drift = CapacityDrift(seed=7)
    kw = dict(k=4, T=15.0, cycles=2, total_samples=1200, seed=3,
              scheme="eta", reallocate=True, drift=drift)
    eager = run_experiment(**kw)
    fused = run_experiment(**kw, fused=True)
    for re_, rf in zip(eager["history"], fused["history"]):
        np.testing.assert_array_equal(re_["tau"], rf["tau"])
        np.testing.assert_array_equal(re_["d"], rf["d"])


def test_fused_realloc_infeasible_drift_raises_from_in_scan_guard():
    """An infeasible drifted cycle raises from the IN-SCAN feasibility
    guard: the scan latches dead at the first bad cycle (no training runs
    on a neutralized allocation from that point on), the error names that
    cycle, and the orchestrator's params stay usable — they hold the state
    trained through the feasible prefix only (finite, and bitwise equal to
    an eager run truncated at the same cycle)."""
    from repro.data.pipeline import synthetic_mnist
    from repro.fed.orchestrator import MELConfig, Orchestrator
    from repro.models import mlp

    train, _ = synthetic_mnist(3000, n_test=10, seed=0)
    prob = make_problem(k=4, T=15.0, d=1200)
    # seed 1: cycle 0 is feasible, cycle 1 is not
    drift = CapacityDrift(fading_sigma_db=30.0, fading_clip_db=30.0, seed=1)
    orch = Orchestrator(MELConfig(T=15.0, total_samples=1200), prob, mlp.loss,
                        mlp.init(jax.random.key(0)), drift=drift)
    with pytest.raises(ValueError, match="cannot absorb") as ei:
        orch.run(train, 3, fused=True, reallocate=True)
    assert "at cycle" in str(ei.value)
    for leaf in jax.tree_util.tree_leaves(orch.params):
        assert np.isfinite(np.asarray(leaf)).all()


def test_fused_realloc_rejects_untraced_scheme():
    from repro.data.pipeline import synthetic_mnist
    from repro.fed.orchestrator import MELConfig, Orchestrator
    from repro.models import mlp

    train, _ = synthetic_mnist(2000, n_test=10, seed=0)
    prob = make_problem(k=4, T=15.0, d=1000)
    mel = MELConfig(T=15.0, total_samples=1000, scheme="slsqp")
    orch = Orchestrator(mel, prob, mlp.loss, mlp.init(jax.random.key(0)))
    with pytest.raises(ValueError, match="no batched/traced policy"):
        orch.run(train, 2, fused=True, reallocate=True)


def test_drift_staleness_sweep_adaptive_beats_static():
    """The paper's core claim under time-varying capacities: re-solving
    each cycle (adaptive) never does worse than freezing the allocation
    (static), and the KKT scheme strictly improves on this drift path."""
    from repro.fed.simulation import staleness_sweep

    rows = staleness_sweep(
        [5, 8], 7.5, schemes=("kkt_sai", "eta"), reallocate=True, cycles=6,
        total_samples=4000,
    )
    by = {(r["K"], r["scheme"], r["mode"]): r for r in rows}
    for k in (5, 8):
        for scheme in ("kkt_sai", "eta"):
            ada = by[(k, scheme, "adaptive")]
            sta = by[(k, scheme, "static")]
            assert ada["max_staleness_mean"] <= sta["max_staleness_mean"] + 1e-9
        assert (by[(k, "kkt_sai", "adaptive")]["max_staleness_mean"]
                < by[(k, "kkt_sai", "static")]["max_staleness_mean"])


def test_batched_sweep_matches_eager_sweep():
    from repro.fed.simulation import staleness_sweep

    kw = dict(schemes=("kkt_sai", "eta"), seed=0, total_samples=4000)
    assert staleness_sweep([5, 8], 7.5, **kw) == staleness_sweep(
        [5, 8], 7.5, use_batched=False, **kw
    )
