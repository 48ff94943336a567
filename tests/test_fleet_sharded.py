"""Sharded fleet path: the fleet engine on a REAL multi-device mesh.

Needs >= 8 devices — the fleet-scale CI step provides them by setting
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` before jax
initializes, so ``host_mesh()`` builds the (2, 4) data x model mesh and
``jax.shard_map`` genuinely partitions the fleet axis. On fewer
devices every test here skips (tier-1 covers the 1-device semantics in
``tests/test_fleet.py``).

The sharded solve is NOT asserted equal to the 1-device solve: the
per-shard batch shape changes the residual-sum reduction order, which can
move tau* within the bisection tolerance and shift +-1 sample between
remainder-tied learners (the repo's documented reduction-order ULP
tolerance). The invariants below are what the engine actually relies on:
feasibility, exact budget totals, box bounds, and padded/sampled-out rows
solving to zeros.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.fed.fleet import FleetConfig, FleetEngine, build_fleet_problems
from repro.launch.mesh import host_device_flags, host_mesh
from repro.models import mlp
from repro.sharding.rules import fleet_partition_axes

pytestmark = pytest.mark.skipif(
    len(jax.devices()) < 8,
    reason=f"needs >= 8 devices: set XLA_FLAGS={host_device_flags(8)} "
           "before jax import (the fleet-scale CI step does)",
)


@pytest.fixture(scope="module")
def data():
    from repro.data.pipeline import synthetic_mnist

    return synthetic_mnist(1200, n_test=200, seed=0)


def test_host_mesh_resolves_test_spec():
    mesh = host_mesh()
    assert dict(mesh.shape) == {"data": 2, "model": 4}
    assert fleet_partition_axes(16, mesh) == ("data", "model")


def test_sharded_solve_invariants():
    """One shard_map'd batched_policy call over 16 fleets split across 8
    devices: feasible rows, exact per-fleet budgets, box bounds, zeros in
    sampled-out rows."""
    mesh = host_mesh()
    bp = build_fleet_problems(16, 4, T=6.0, total_samples=40, seed=0)
    eng = FleetEngine(FleetConfig(), bp, mlp.loss,
                      mlp.init(jax.random.key(0)), seed=0, mesh=mesh)
    assert eng.fleet_axes == ("data", "model")

    sampled = np.zeros(16, bool)
    sampled[::2] = True
    tau, d = eng._solve(sampled)
    assert eng._last_feasible.all()
    assert (tau[~sampled] == 0).all() and (d[~sampled] == 0).all()
    np.testing.assert_array_equal(
        d[sampled].sum(axis=1), np.asarray(bp.total)[sampled]
    )
    assert (d[sampled] >= np.asarray(bp.d_lo)[sampled]).all()
    assert (d[sampled] <= np.asarray(bp.d_hi)[sampled]).all()


def test_engine_runs_sharded_with_padding(data):
    """F = 10 pads to 16 on the 8-device mesh: the run trains, merges and
    re-solves with padded fleets never sampled, never weighted, and real
    fleets accruing version staleness under 50% participation."""
    train, test = data
    eng = FleetEngine(
        FleetConfig(participation=0.5),
        build_fleet_problems(10, 3, T=6.0, total_samples=30, seed=2),
        mlp.loss, mlp.init(jax.random.key(0)), seed=1,
    )
    assert eng.problems.c2.shape[0] == 16          # padded to the mesh
    assert eng._real.sum() == 10
    hist = eng.run(train, 3, eval_fn=mlp.accuracy,
                   eval_batch=(test.x, test.y))
    assert [r["sampled_fleets"] for r in hist] == [5, 5, 5]
    assert all(np.isfinite(r["accuracy"]) for r in hist)
    assert all((r["d"].sum(axis=1) == 30).all() for r in hist)
    assert max(r["fleet_staleness_max"] for r in hist) >= 1
    # padded fleets never merge: their pull version stays at the origin
    assert (eng.pull_version[~eng._real] == 0).all()
    assert eng.pull_version[eng._real].max() == eng.global_version == 3
