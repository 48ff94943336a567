"""Fleet-of-fleets engine (``fed/fleet.py``) pins.

  * the F = 1 exactness contract: full participation on the 1-device mesh
    reproduces ``Orchestrator.run`` record for record and parameter for
    parameter (bitwise);
  * sampling-mask semantics under the fleet axis: a sampled-out fleet IS
    an all-offline fleet IS a row of padded slots, the clipped budget
    never leaves the live box, and the policies solve masked rows to
    zeros without going infeasible (property-tested);
  * engine behavior: fleet padding, partial-participation staleness
    bookkeeping, config validation;
  * keyed partitioner draws: draw i depends only on (seed, i, total) —
    pinned to concrete indices, so any iteration-order or global-PRNG
    dependence shows up as a cross-process diff.

The multi-device shard_map path needs >= 8 devices and lives in
``tests/test_fleet_sharded.py`` (the fleet-scale CI step runs it under
``XLA_FLAGS=--xla_force_host_platform_device_count=8``).
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import BatchedProblems, apply_active_mask, apply_sampling_mask
from repro.core.solver_batched import batched_policy
from repro.data.pipeline import FederatedPartitioner, synthetic_mnist
from repro.fed.fleet import FleetConfig, FleetEngine, build_fleet_problems
from repro.fed.orchestrator import MELConfig, Orchestrator
from repro.fed.simulation import build_spread_problem
from repro.launch.mesh import make_mesh_by_name
from repro.models import mlp

from tests._prop import given, settings, st, make_batched_problems


@pytest.fixture(scope="module")
def data():
    return synthetic_mnist(1200, n_test=200, seed=0)


def _cpu_mesh():
    return make_mesh_by_name("cpu")


# ---------------------------------------------------------------------------
# the F = 1 exactness contract (ISSUE acceptance)
# ---------------------------------------------------------------------------

def test_f1_full_participation_reproduces_orchestrator_bitwise(data):
    """One fleet, full participation, 1-device mesh: the two-tier engine
    degenerates to the single-fleet paper scheme — same initial solve,
    same shard draws, same training, same aggregation — so every history
    field and every final parameter matches ``Orchestrator.run`` exactly."""
    train, test = data
    prob = build_spread_problem(3, 6.0, total_samples=60)
    params = mlp.init(jax.random.key(1))
    ex, ey = jnp.asarray(test.x), jnp.asarray(test.y)

    orch = Orchestrator(MELConfig(T=6.0, total_samples=60), prob,
                        mlp.loss, params, seed=3)
    hist_o = orch.run(train, 4, eval_fn=lambda p: mlp.accuracy(p, ex, ey))

    eng = FleetEngine(
        FleetConfig(), BatchedProblems.from_problems([prob]),
        mlp.loss, params, seed=3, mesh=_cpu_mesh(),
    )
    hist_f = eng.run(train, 4, eval_fn=mlp.accuracy,
                     eval_batch=(test.x, test.y))

    assert len(hist_o) == len(hist_f) == 4
    for ro, rf in zip(hist_o, hist_f):
        assert rf["fleets"] == rf["sampled_fleets"] == 1
        np.testing.assert_array_equal(rf["tau"][0], ro["tau"])
        np.testing.assert_array_equal(rf["d"][0], ro["d"])
        assert rf["accuracy"] == ro["accuracy"]          # float-exact
        assert float(rf["max_staleness"][0]) == ro["max_staleness"]
        assert float(rf["avg_staleness"][0]) == ro["avg_staleness"]
        assert rf["elapsed_s"] == ro["elapsed_s"]
        assert rf["wall_clock_s"] == ro["wall_clock_s"]
        assert rf["fleet_staleness_max"] == 0            # always fresh
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_array_equal(
            np.asarray(a), np.asarray(b)
        ),
        eng.global_params, orch.params,
    )


# ---------------------------------------------------------------------------
# sampling-mask semantics (property)
# ---------------------------------------------------------------------------

@settings(deadline=None, max_examples=20)
@given(seed=st.integers(0, 2**20), sample_bits=st.integers(0, 2**6 - 1))
def test_sampling_mask_is_offline_is_padded(seed, sample_bits):
    """Row f of ``apply_sampling_mask`` with ``sampled[f]=False`` equals
    ``apply_active_mask`` with every learner offline equals a row of
    ``BatchedProblems`` padded slots; sampled-in rows pass through the
    learner-mask identity; the clipped budget stays in the live box."""
    _, bp = make_batched_problems(seed)
    b, k = bp.c2.shape
    sampled = np.array([(sample_bits >> i) & 1 == 1 for i in range(b)])
    total = np.asarray(bp.total, np.int64)
    lo, hi = np.asarray(bp.d_lo), np.asarray(bp.d_hi)
    valid = np.asarray(bp.valid, bool)

    tot_s, lo_s, hi_s, v_s = (np.asarray(a) for a in apply_sampling_mask(
        total, lo, hi, valid, sampled))

    # budget clipping never leaves the (masked) live box
    assert (tot_s >= lo_s.sum(axis=1)).all()
    assert (tot_s <= hi_s.sum(axis=1)).all()

    for f in range(b):
        if sampled[f]:
            # sampled-in row == the plain active-mask identity on valid
            tot_a, lo_a, hi_a, v_a = (np.asarray(a) for a in
                                      apply_active_mask(
                                          total[f], lo[f], hi[f], valid[f],
                                          valid[f]))
            np.testing.assert_array_equal(lo_s[f], lo_a)
            np.testing.assert_array_equal(hi_s[f], hi_a)
            np.testing.assert_array_equal(v_s[f], v_a)
            assert tot_s[f] == tot_a
        else:
            # sampled-out == all-offline == padded slots
            tot_o, lo_o, hi_o, v_o = (np.asarray(a) for a in
                                      apply_active_mask(
                                          total[f], lo[f], hi[f], valid[f],
                                          np.zeros(k, bool)))
            np.testing.assert_array_equal(lo_s[f], lo_o)
            np.testing.assert_array_equal(hi_s[f], hi_o)
            np.testing.assert_array_equal(v_s[f], v_o)
            assert tot_s[f] == tot_o == 0
            assert (lo_s[f] == 0).all() and (hi_s[f] == 0).all()
            assert not v_s[f].any()


@settings(deadline=None, max_examples=5)
@given(seed=st.integers(0, 2**20))
def test_policy_solves_sampled_out_rows_to_zero(seed):
    """The traced policy on sampling-masked tensors: sampled-out rows are
    feasible with tau = d = 0; sampled rows allocate their full budget
    within bounds."""
    _, bp = make_batched_problems(seed)
    b, _ = bp.c2.shape
    sampled = np.zeros(b, bool)
    sampled[::2] = True
    with jax.enable_x64(True):
        tot, lo, hi, v = apply_sampling_mask(
            jnp.asarray(bp.total, jnp.int64),
            jnp.asarray(bp.d_lo, jnp.float64),
            jnp.asarray(bp.d_hi, jnp.float64),
            jnp.asarray(bp.valid), jnp.asarray(sampled),
        )
        tau, d, feas = batched_policy("kkt_sai")(
            jnp.asarray(bp.c2, jnp.float64), jnp.asarray(bp.c1, jnp.float64),
            jnp.asarray(bp.c0, jnp.float64), jnp.asarray(bp.T, jnp.float64),
            tot, lo, hi, v,
        )
        tau, d, feas = np.asarray(tau), np.asarray(d), np.asarray(feas)
        tot = np.asarray(tot)
    assert feas.all()
    out = ~sampled
    assert (tau[out] == 0).all() and (d[out] == 0).all()
    np.testing.assert_array_equal(d[sampled].sum(axis=1), tot[sampled])
    assert (d >= np.asarray(lo)).all() and (d <= np.asarray(hi)).all()


# ---------------------------------------------------------------------------
# engine behavior
# ---------------------------------------------------------------------------

def test_fleet_padding_is_padded_slot_semantics():
    bp = build_fleet_problems(3, 4, seed=5)
    padded = FleetEngine._pad_problems(bp, 8)
    assert padded.c2.shape == (8, 4)
    np.testing.assert_array_equal(padded.c2[:3], bp.c2)
    assert not padded.valid[3:].any()
    assert (padded.d_lo[3:] == 0).all() and (padded.d_hi[3:] == 0).all()
    assert (padded.total[3:] == 0).all()


def test_padded_fleet_keeps_its_model(data):
    """An all-invalid fleet row — what the engine pads F with on a
    multi-device mesh — has d = 0 everywhere, so its aggregation weights
    are 0/0. It must keep its model rather than turn it into NaN, which the
    global merge would carry even at zero weight (0 * NaN) to every fleet."""
    train, _ = data
    bp = FleetEngine._pad_problems(
        build_fleet_problems(3, 3, T=6.0, total_samples=30, seed=2), 4)
    params = mlp.init(jax.random.key(0))
    # lr small enough that the real fleets' 10-sample shards do not diverge
    eng = FleetEngine(FleetConfig(lr=0.01), bp, mlp.loss, params, seed=1,
                      mesh=_cpu_mesh())
    assert (eng.d[3] == 0).all()
    eng.run(train, 2)
    for leaf in jax.tree_util.tree_leaves((eng.global_params,
                                           eng.fleet_params)):
        assert np.isfinite(np.asarray(leaf)).all()


def test_partial_participation_staleness(data):
    """Partial participation: each round samples the configured fleet
    count, unsampled fleets keep their dispatch and accrue version
    staleness, and pull versions advance only on merge."""
    train, _ = data
    eng = FleetEngine(
        FleetConfig(participation=0.5),
        build_fleet_problems(4, 3, T=6.0, total_samples=30, seed=2),
        mlp.loss, mlp.init(jax.random.key(0)), seed=1, mesh=_cpu_mesh(),
    )
    hist = eng.run(train, 4)
    assert [r["sampled_fleets"] for r in hist] == [2, 2, 2, 2]
    assert eng.global_version == 4
    pv = eng.pull_version[eng._real]
    assert pv.max() == 4                     # last round's fleets are fresh
    assert pv.min() < 4                      # someone was left out
    assert max(r["fleet_staleness_max"] for r in hist) >= 1
    # determinism: the sampling stream is keyed by (seed, stream, round)
    eng2 = FleetEngine(
        FleetConfig(participation=0.5),
        build_fleet_problems(4, 3, T=6.0, total_samples=30, seed=2),
        mlp.loss, mlp.init(jax.random.key(0)), seed=1, mesh=_cpu_mesh(),
    )
    for r in range(4):
        np.testing.assert_array_equal(eng2._sample_mask(r),
                                      eng._sample_mask(r))


def test_fleet_config_validation():
    with pytest.raises(ValueError, match="batched_policy"):
        FleetConfig(scheme="slsqp")
    with pytest.raises(ValueError, match="participation"):
        FleetConfig(participation=0.0)
    with pytest.raises(ValueError, match="server_mix"):
        FleetConfig(server_mix=1.5)
    with pytest.raises(ValueError, match="staleness fn"):
        FleetConfig(staleness_fn="nope")


def test_build_fleet_problems_keyed_and_pinned():
    """The population generator is keyed by (seed, F, K) and draws whole
    tensors — identical across processes (pinned values) and across
    repeated builds."""
    bp = build_fleet_problems(3, 4, seed=5)
    bp2 = build_fleet_problems(3, 4, seed=5)
    np.testing.assert_array_equal(bp.c2, bp2.c2)
    np.testing.assert_array_equal(bp.c1, bp2.c1)
    np.testing.assert_allclose(
        bp.c2[0],
        [0.037716867339, 0.030758195571, 0.027533832447, 0.044979222428],
        rtol=0, atol=1e-12,
    )
    assert bp.total.tolist() == [60, 60, 60]
    assert (bp.d_lo == 7.0).all() and (bp.d_hi == 30.0).all()


# ---------------------------------------------------------------------------
# keyed partitioner draws (determinism seam)
# ---------------------------------------------------------------------------

def test_partitioner_draws_keyed_by_seed_and_index(data):
    """``draw_indices`` derives draw i from ``SeedSequence((seed, i))``
    alone: pinned indices hold across processes, and draw i is unchanged
    by the sizes of earlier draws (no iteration-order or global-PRNG
    dependence)."""
    train, _ = data
    p = FederatedPartitioner(train, seed=7)
    np.testing.assert_array_equal(p.draw_indices(5),
                                  [748, 819, 1130, 693, 1075])
    second = p.draw_indices(8)
    np.testing.assert_array_equal(
        second, [919, 1038, 191, 226, 1046, 262, 133, 309])
    # same draw index + total, DIFFERENT first-draw size: identical result
    q = FederatedPartitioner(train, seed=7)
    q.draw_indices(200)
    np.testing.assert_array_equal(q.draw_indices(8), second)
    # distinct seeds give distinct streams
    r = FederatedPartitioner(train, seed=8)
    assert not np.array_equal(r.draw_indices(5), [748, 819, 1130, 693, 1075])
