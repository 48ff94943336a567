"""Multi-tenant scheduler (``fed.multimodel`` + the cross-model allocation
layer in ``core.solver_batched``).

Pins the subsystem's acceptance contracts:
  * S = 1 ``MultiModelEngine`` reproduces ``AsyncFedEngine`` record for
    record (versions / weights / staleness / times bitwise, params to
    float tolerance) under faults, drift and availability alike — and
    via the barrier regime, ``Orchestrator.run`` bitwise;
  * the cross-model split never over-commits a learner: summed time (and
    joule) commitments across the S tenants stay within the single-tenant
    budgets, for every split policy, staleness discount and fault mix;
  * the split is permutation-equivariant across models and monotone in
    each model's own deficit — and reads ONLY version deficits (model-
    value-free), so schedules stay bit-reproducible.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import QueueDrift
from repro.core.availability import MarkovAvailability
from repro.core.solver_batched import (
    batched_policy,
    cross_model_weights,
    multimodel_policy,
)
from repro.data.pipeline import synthetic_mnist
from repro.fed.async_engine import AsyncConfig, AsyncFedEngine
from repro.fed.fleet import FleetConfig, FleetEngine, build_fleet_problems
from repro.fed.multimodel import MultiModelEngine, solve_multimodel_rows
from repro.fed.orchestrator import MELConfig, Orchestrator
from repro.fed.simulation import build_energy_problem, build_problem
from repro.models import mlp

from tests._prop import given, settings, st


@pytest.fixture(scope="module")
def data():
    return synthetic_mnist(1500, n_test=300, seed=0)


def _assert_trees_equal(a, b, **kw):
    la, lb = jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        if kw:
            np.testing.assert_allclose(np.asarray(x), np.asarray(y), **kw)
        else:
            np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def _assert_history_match(h1, h2):
    assert len(h1) == len(h2)
    for r1, r2 in zip(h1, h2):
        assert r1["learners"] == r2["learners"]
        assert r1["staleness_list"] == r2["staleness_list"]
        assert r1["server_version"] == r2["server_version"]
        assert r1["keep"] == r2["keep"]
        assert r1["t"] == r2["t"]
        np.testing.assert_array_equal(r1["weights"], r2["weights"])
        np.testing.assert_array_equal(r1["tau"], r2["tau"])
        np.testing.assert_array_equal(r1["d"], r2["d"])


def _run_pair(cfg, prob, train, horizon, *, seed=2, drift=None):
    """(AsyncFedEngine history, S=1 MultiModelEngine history) plus both
    engines, from identical seeds and init params."""
    p1 = mlp.init(jax.random.key(1))
    e1 = AsyncFedEngine(cfg, prob, mlp.loss, p1, seed=seed, drift=drift)
    h1 = e1.run(train, horizon)
    p2 = mlp.init(jax.random.key(1))
    e2 = MultiModelEngine(cfg, [prob], mlp.loss, p2, seed=seed, drift=drift)
    h2 = e2.run(train, horizon)[0]
    return e1, h1, e2, h2


# ---------------------------------------------------------------------------
# S = 1: the single-tenant engine is a fixed point (acceptance anchor)
# ---------------------------------------------------------------------------

def test_s1_matches_async_engine_fedasync_with_faults(data):
    train, _ = data
    prob = build_problem(3, 6.0, total_samples=60, seed=0)
    cfg = AsyncConfig(mode="fedasync", alpha=0.5, staleness_fn="poly",
                      drop_rate=0.2, delay_rate=0.3, straggler_rate=0.2,
                      deadline=15.0)
    e1, h1, e2, h2 = _run_pair(cfg, prob, train, 30.0)
    _assert_history_match(h1, h2)
    _assert_trees_equal(e1.params, e2.params[0], rtol=1e-6, atol=1e-6)
    assert e1.fault_counters == e2.fault_counters


def test_s1_matches_async_engine_buffered_quorum(data):
    train, _ = data
    prob = build_problem(3, 6.0, total_samples=60, seed=0)
    cfg = AsyncConfig(mode="buffered", buffer_size=3, quorum=2,
                      flush_timeout=4.0, delay_rate=0.3,
                      aggregation="staleness")
    e1, h1, e2, h2 = _run_pair(cfg, prob, train, 30.0)
    _assert_history_match(h1, h2)
    assert e1.fault_counters == e2.fault_counters


def test_s1_matches_async_engine_under_availability(data):
    """The churn anchors: adaptive per-block masked re-solves AND the
    frozen-allocation regime both reproduce the single-model engine."""
    train, _ = data
    prob = build_problem(3, 6.0, total_samples=60, seed=0)
    av = MarkovAvailability(p_drop=0.3, p_join=0.6, seed=5)
    for realloc in (True, False):
        cfg = AsyncConfig(mode="fedasync", alpha=0.5, reallocate=realloc)
        e1, h1, e2, h2 = _run_pair(cfg, prob, train, 30.0, drift=av)
        _assert_history_match(h1, h2)
        assert e1.fault_counters == e2.fault_counters


def test_s1_matches_async_engine_energy_ledger(data):
    """With an EnergyModel attached, the per-learner joule ledger (charged
    at dispatch) matches the single-model engine bitwise."""
    train, _ = data
    prob = build_energy_problem(3, 8.0, total_samples=120, seed=0)
    cfg = AsyncConfig(mode="fedasync", alpha=0.5)
    e1, h1, e2, h2 = _run_pair(cfg, prob, train, 40.0)
    _assert_history_match(h1, h2)
    np.testing.assert_array_equal(
        e1.energy_ledger["per_learner"], e2.energy_ledger["per_learner"]
    )
    assert e1.energy_ledger["violations"] == e2.energy_ledger["violations"]


def test_s1_barrier_matches_orchestrator_bitwise(data):
    """PINNED: barrier + M = K at S = 1 IS the paper scheme — tau/d and
    the aggregated params reproduce ``Orchestrator.run`` bitwise."""
    train, _ = data
    prob = build_problem(3, 6.0, total_samples=60, seed=0)
    p0 = mlp.init(jax.random.key(1))
    orch = Orchestrator(MELConfig(T=6.0, total_samples=60), prob,
                        mlp.loss, p0, seed=7)
    ho = orch.run(train, 4)
    p1 = mlp.init(jax.random.key(1))
    eng = MultiModelEngine(
        AsyncConfig(mode="buffered", barrier=True, aggregation="staleness"),
        [prob], mlp.loss, p1, seed=7,
    )
    hm = eng.run(train, cycles=4)[0]
    assert len(ho) == len(hm) == 4
    for ro, rm in zip(ho, hm):
        np.testing.assert_array_equal(ro["tau"], rm["tau"])
        np.testing.assert_array_equal(ro["d"], rm["d"])
        assert ro["max_staleness"] == rm["max_staleness"]
        assert ro["avg_staleness"] == rm["avg_staleness"]
    _assert_trees_equal(orch.params, eng.params[0])


def test_s1_run_events_matches_run(data):
    train, _ = data
    prob = build_problem(3, 6.0, total_samples=60, seed=0)
    cfg = AsyncConfig(mode="buffered", buffer_size=2,
                      aggregation="staleness", delay_rate=0.3)
    p1 = mlp.init(jax.random.key(1))
    e1 = AsyncFedEngine(cfg, prob, mlp.loss, p1, seed=3)
    h1 = e1.run_events(train, 30.0)
    p2 = mlp.init(jax.random.key(1))
    e2 = MultiModelEngine(cfg, [prob], mlp.loss, p2, seed=3)
    h2 = e2.run_events(train, 30.0)[0]
    _assert_history_match(h1, h2)
    _assert_trees_equal(e1.params, e2.params[0], rtol=1e-6, atol=1e-6)


@settings(max_examples=10, deadline=None)
@given(seed=st.integers(0, 2**20), s=st.integers(1, 4))
def test_s1_policy_is_static_passthrough(seed, s):
    """At S = 1 ``multimodel_policy`` hands the base ``batched_policy``
    bitwise-identical operands (no mask, no scaling); at S > 1 with all-
    zero deficits the equal and deficit splits coincide."""
    rng = np.random.default_rng(seed)
    k = int(rng.integers(2, 7))
    with jax.enable_x64(True):
        c2 = jnp.asarray(rng.uniform(1e-4, 5e-3, (1, k)))
        c1 = jnp.asarray(rng.uniform(1e-5, 1e-3, (1, k)))
        c0 = jnp.asarray(rng.uniform(0.05, 0.3, (1, k)))
        lo = jnp.full((1, k), 5.0)
        hi = jnp.full((1, k), 200.0)
        T = jnp.asarray([float(np.max(np.asarray(c0)) + 8.0)])
        total = jnp.asarray([40 * k], jnp.int64)
        valid = jnp.ones((1, k), bool)
        base = batched_policy("kkt_sai")
        mm = multimodel_policy("kkt_sai", split="deficit")
        t0, d0, ok0 = base(c2, c1, c0, T, total, lo, hi, valid)
        t1, d1, ok1, w = mm(jnp.zeros(1), c2, c1, c0, T, total, lo, hi, valid)
        np.testing.assert_array_equal(np.asarray(t0), np.asarray(t1))
        np.testing.assert_array_equal(np.asarray(d0), np.asarray(d1))
        assert np.asarray(w).tolist() == [1.0]
        if s > 1:
            tile = lambda a: jnp.tile(a, (s,) + (1,) * (a.ndim - 1))
            args = (tile(c2), tile(c1), tile(c0), tile(T), tile(total),
                    tile(lo), tile(hi), tile(valid))
            te, de, _, we = multimodel_policy("kkt_sai", split="equal")(
                jnp.zeros(s), *args)
            td, dd, _, wd = multimodel_policy("kkt_sai", split="deficit")(
                jnp.zeros(s), *args)
            np.testing.assert_array_equal(np.asarray(we), np.asarray(wd))
            np.testing.assert_array_equal(np.asarray(te), np.asarray(td))
            np.testing.assert_array_equal(np.asarray(de), np.asarray(dd))


# ---------------------------------------------------------------------------
# budget partition: no learner is ever over-committed
# ---------------------------------------------------------------------------

@settings(max_examples=10, deadline=None)
@given(
    seed=st.integers(0, 2**20),
    s=st.integers(2, 4),
    split=st.sampled_from(["deficit", "equal"]),
    scheme=st.sampled_from(["kkt_sai", "kkt_energy"]),
)
def test_split_never_overcommits_a_learner(seed, s, split, scheme):
    """Summed per-learner time cost across the S tenants <= T, and summed
    joules <= e_budget (energy scheme), for random deficits and fleets."""
    rng = np.random.default_rng(seed)
    k = int(rng.integers(2, 6))
    T = 10.0
    builder = build_energy_problem if scheme == "kkt_energy" else build_problem
    kw = {"e_budget": 6.0} if scheme == "kkt_energy" else {}
    probs = [
        builder(k, T, total_samples=int(rng.integers(40, 200)),
                seed=int(rng.integers(100)), **kw)
        for _ in range(s)
    ]
    # shared fleet: every tenant sees model 0's capacities
    tm = probs[0].time_model
    probs = [
        type(p)(time_model=tm, T=p.T, total_samples=p.total_samples,
                d_lower=p.d_lower, d_upper=p.d_upper,
                energy=probs[0].energy, e_budget=p.e_budget)
        for p in probs
    ]
    deficits = rng.uniform(0.0, 5.0, s)
    tau, d, w = solve_multimodel_rows(
        scheme, tm.c2.astype(np.float64), tm.c1.astype(np.float64),
        tm.c0.astype(np.float64), probs, deficits, split=split,
        label="property",
    )
    assert float(np.asarray(w).sum()) <= 1.0
    on = (d > 0).astype(np.float64)
    cost = (tm.c2[None] * tau * d + tm.c1[None] * d + tm.c0[None] * on)
    assert (cost.sum(axis=0) <= T * (1 + 1e-9)).all()
    if scheme == "kkt_energy":
        e2, e1, e0, eb = probs[0].energy_rows()
        joules = (e2[None] * tau * d + e1[None] * d + e0[None] * on)
        assert (joules.sum(axis=0) <= eb * (1 + 1e-9)).all()


# ---------------------------------------------------------------------------
# split-weight laws: equivariance, monotonicity, grid exactness
# ---------------------------------------------------------------------------

@settings(max_examples=10, deadline=None)
@given(seed=st.integers(0, 2**20), s=st.integers(2, 6),
       floor=st.floats(0.0, 0.15))
def test_split_weights_laws(seed, s, floor):
    rng = np.random.default_rng(seed)
    deficits = rng.uniform(0.0, 10.0, s)
    with jax.enable_x64(True):
        w = np.asarray(cross_model_weights(
            jnp.asarray(deficits), policy="deficit", share_floor=floor))
        # sum exactly representable and <= 1 (2^-20 grid floor)
        assert w.sum() <= 1.0
        # permutation equivariance
        perm = rng.permutation(s)
        wp = np.asarray(cross_model_weights(
            jnp.asarray(deficits[perm]), policy="deficit",
            share_floor=floor))
        np.testing.assert_array_equal(wp, w[perm])
        # monotone in own deficit
        j = int(rng.integers(s))
        bumped = deficits.copy()
        bumped[j] += rng.uniform(0.5, 3.0)
        wb = np.asarray(cross_model_weights(
            jnp.asarray(bumped), policy="deficit", share_floor=floor))
        assert wb[j] >= w[j]
        # floor honored
        if floor > 0:
            grid_floor = np.floor(floor * 2**20) / 2**20
            assert (w >= grid_floor - 2**-20).all()


def test_engine_schedule_is_permutation_equivariant(data):
    """Permuting the tenant models (same engine seed... per-model
    partitioner seeds are drawn in model order, so permute the SAME seed
    set) permutes the schedules: the event system reads only deficits,
    never which slot a model sits in."""
    train, _ = data
    probs = [build_problem(3, 6.0, total_samples=t, seed=0)
             for t in (60, 60, 180)]
    cfg = AsyncConfig(mode="fedasync", alpha=0.5)
    params = tuple(mlp.init(jax.random.key(i)) for i in range(3))
    perm = [2, 0, 1]

    e1 = MultiModelEngine(cfg, probs, mlp.loss, params, seed=2)
    h1 = e1.run([train] * 3, 60.0)
    e2 = MultiModelEngine(cfg, [probs[i] for i in perm], mlp.loss,
                          tuple(params[i] for i in perm), seed=2)
    h2 = e2.run([train] * 3, 60.0)
    # model at permuted slot i is original model perm[i]: its schedule
    # (times, allocations, versions) must transfer — shard draws differ
    # (partitioner seeds are drawn in slot order), so params may not
    for i, src in enumerate(perm):
        ha, hb = h1[src], h2[i]
        assert len(ha) == len(hb)
        for ra, rb in zip(ha, hb):
            assert ra["t"] == rb["t"]
            assert ra["server_version"] == rb["server_version"]
            np.testing.assert_array_equal(ra["tau"], rb["tau"])
            np.testing.assert_array_equal(ra["d"], rb["d"])


# ---------------------------------------------------------------------------
# S > 1 behavior: deficit feedback and validation surface
# ---------------------------------------------------------------------------

def test_deficit_split_self_balances_versions(data):
    """A tenant with 3x the per-round samples completes rounds slower;
    the deficit split must keep final versions close (the FedAST goal),
    where the equal split lets the fast tenants run away."""
    train, _ = data
    probs = [build_problem(3, 6.0, total_samples=t, seed=0)
             for t in (60, 60, 180)]
    cfg = AsyncConfig(mode="fedasync", alpha=0.5)
    params = tuple(mlp.init(jax.random.key(i)) for i in range(3))
    eng = MultiModelEngine(cfg, probs, mlp.loss, params, seed=2,
                           split="deficit")
    hs = eng.run([train] * 3, 60.0)
    vers = np.array([h[-1]["server_version"] for h in hs])
    assert vers.min() > 0
    assert vers.max() - vers.min() <= 3
    # the split layer logged deficit-driven (non-uniform) weights
    w_log = np.stack(eng.split_weight_log)
    assert (np.abs(w_log - w_log[:, :1]) > 1e-6).any()


def test_multimodel_run_events_matches_run(data):
    """The S = 3 device-resident replay matches the eager replay on the
    SAME schedule (histories bitwise, params to float tolerance)."""
    train, _ = data
    probs = [build_problem(3, 6.0, total_samples=t, seed=0)
             for t in (60, 120)]
    cfg = AsyncConfig(mode="buffered", buffer_size=2,
                      aggregation="staleness")
    params = tuple(mlp.init(jax.random.key(i)) for i in range(2))
    e1 = MultiModelEngine(cfg, probs, mlp.loss, params, seed=4)
    h1 = e1.run([train] * 2, 40.0)
    e2 = MultiModelEngine(cfg, probs, mlp.loss, params, seed=4)
    h2 = e2.run_events([train] * 2, 40.0)
    for ha, hb, pa, pb in zip(h1, h2, e1.params, e2.params):
        _assert_history_match(ha, hb)
        _assert_trees_equal(pa, pb, rtol=1e-6, atol=1e-6)


def test_validation_surface():
    prob = build_problem(3, 6.0, total_samples=60, seed=0)
    p = mlp.init(jax.random.key(0))
    # scheduler-level knobs must agree
    with pytest.raises(ValueError, match="scheduler-level"):
        MultiModelEngine(
            [AsyncConfig(mode="fedasync", alpha=0.5),
             AsyncConfig(mode="fedasync", alpha=0.5, scheme="eta")],
            [prob, prob], mlp.loss, p,
        )
    # per-model server knobs may differ
    eng = MultiModelEngine(
        [AsyncConfig(mode="fedasync", alpha=0.5),
         AsyncConfig(mode="buffered", buffer_size=2)],
        [prob, prob], mlp.loss, p,
    )
    assert eng.num_models == 2
    # one physical fleet: K and T must match
    other = build_problem(4, 6.0, total_samples=60, seed=0)
    with pytest.raises(ValueError, match="physical fleet"):
        MultiModelEngine(AsyncConfig(), [prob, other], mlp.loss, p)
    # ... and so must the TimeModel coefficients
    different = build_problem(3, 6.0, total_samples=60, seed=9)
    with pytest.raises(ValueError, match="TimeModel"):
        MultiModelEngine(AsyncConfig(), [prob, different], mlp.loss, p)
    # per-model params tuple must have S entries
    with pytest.raises(ValueError, match="per-model pytrees"):
        MultiModelEngine(AsyncConfig(), [prob, prob], mlp.loss, (p,))
    # state-coupled drift has no S > 1 rollout
    with pytest.raises(ValueError, match="state-coupled"):
        MultiModelEngine(
            AsyncConfig(reallocate=True), [prob, prob], mlp.loss, p,
            drift=QueueDrift(),
        )
    # unknown split policy
    with pytest.raises(ValueError, match="split"):
        MultiModelEngine(AsyncConfig(), [prob], mlp.loss, p, split="greedy")


# ---------------------------------------------------------------------------
# fleet-scale face
# ---------------------------------------------------------------------------

def test_fleet_solve_multimodel():
    bp = build_fleet_problems(3, k=4, T=6.0, total_samples=80, seed=0)
    eng = FleetEngine(FleetConfig(), bp, mlp.loss,
                      mlp.init(jax.random.key(0)), seed=0)
    # S = 1 short-circuits to the single-tenant solve bitwise
    t1, d1, w1 = eng.solve_multimodel(np.zeros(1))
    t0, d0 = eng._solve(eng._real)
    np.testing.assert_array_equal(t1[0], t0)
    np.testing.assert_array_equal(d1[0], d0)
    assert w1.tolist() == [1.0]
    # S = 3: per-learner summed commitment within every fleet's deadline
    t3, d3, w3 = eng.solve_multimodel(np.array([2.0, 1.0, 0.0]))
    assert t3.shape == (3,) + t0.shape
    f = bp.num_problems
    on = (d3[:, :f] > 0).astype(np.float64)
    cost = (bp.c2[None] * t3[:, :f] * d3[:, :f] + bp.c1[None] * d3[:, :f]
            + bp.c0[None] * on).sum(axis=0)
    assert (cost <= bp.T[None].T * (1 + 1e-9)).all()
    # zero-deficit tenant yields the pool to the laggards
    totals = d3[:, :f].sum(axis=(1, 2))
    assert totals[0] >= totals[2]
