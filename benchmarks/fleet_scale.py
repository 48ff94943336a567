"""Fleet-of-fleets scale benchmark: simulated learners per virtual-time
unit sustained by the two-tier ``FleetEngine`` (``fed/fleet.py``).

Two row families, merged into ``BENCH_alloc.json`` under ``fleet_scale``:

  * ``train`` — full engine rounds (vmapped per-fleet train + two-tier
    staleness-discounted merge + the next dispatch's masked policy solve,
    all one XLA program) at F x K = 512 and 10^4 learners on a compact
    MLP. Every fleet trains during every virtual round of length T, so
    ``learners_per_vtu`` is exactly F x K.
  * ``solve`` — the dispatch tier alone: ONE sharded ``batched_policy``
    call allocating (tau, d) for 10^6 learners, the population the
    engine's allocation path sustains per round.

Run under ``XLA_FLAGS=--xla_force_host_platform_device_count=8`` (the
fleet-scale CI step does) to put the rows on the real (2, 4) ``"test"``
shard_map mesh; elsewhere they fall back to the 1-device ``"cpu"`` mesh.

  PYTHONPATH=src python -m benchmarks.run --only fleet
"""

from __future__ import annotations

import time

import numpy as np

import jax
import jax.numpy as jnp

from benchmarks.alloc_bench import _merge_out
from repro.fed.fleet import _fleet_solve, build_fleet_problems
from repro.fed.simulation import fleet_scale_sweep
from repro.launch.mesh import host_mesh
from repro.sharding.rules import fleet_partition_axes


def solve_only_row(f: int, k: int = 8, *, mesh=None, scheme: str = "kkt_sai",
                   T: float = 6.0, total_samples: int = 60,
                   seed: int = 0) -> dict:
    """Time the sharded fleet dispatch solve on an (F, K) population —
    compile on a warmup call, then one timed solve."""
    mesh = host_mesh() if mesh is None else mesh
    bp = build_fleet_problems(f, k, T=T, total_samples=total_samples,
                              seed=seed)
    axes = fleet_partition_axes(f, mesh)
    with jax.enable_x64(True):
        args = (
            jnp.asarray(bp.c2, jnp.float64), jnp.asarray(bp.c1, jnp.float64),
            jnp.asarray(bp.c0, jnp.float64), jnp.asarray(bp.T, jnp.float64),
            jnp.asarray(bp.total, jnp.int64),
            jnp.asarray(bp.d_lo, jnp.float64),
            jnp.asarray(bp.d_hi, jnp.float64),
            jnp.asarray(bp.valid), jnp.ones(f, bool),
        )
        kw = dict(scheme=scheme, mesh=mesh, fleet_axes=axes)
        jax.block_until_ready(_fleet_solve(*args, **kw))   # compile + warmup
        t0 = time.time()
        tau, d, feas = jax.block_until_ready(_fleet_solve(*args, **kw))
        solve_s = time.time() - t0
    assert bool(np.asarray(feas).all())
    assert bool((np.asarray(d).sum(axis=1) == total_samples).all())
    return {
        "F": f,
        "K": k,
        "learners": f * k,
        "learners_per_vtu": f * k,
        "solve_s": round(solve_s, 4),
        "learners_per_s": round(f * k / max(solve_s, 1e-9), 1),
        "fleet_axes": list(axes),
    }


def main(*, quick: bool = True) -> None:
    mesh = host_mesh()
    n_dev = int(np.prod(list(mesh.shape.values())))
    print(f"# mesh: {dict(mesh.shape)} ({n_dev} devices, "
          f"backend={jax.default_backend()})")

    # full engine rounds: 512 learners, then the 10^4 acceptance point
    counts = (64, 1250) if quick else (64, 1250, 5000)
    rows = fleet_scale_sweep(
        counts, k=8, rounds=2 if quick else 3, participation=0.5, mesh=mesh,
    )
    for r in rows:
        print(f"train F={r['F']:>6} K={r['K']} learners={r['learners']:>6} "
              f"lpvtu={r['learners_per_vtu']:>6} wall={r['wall_s']:>7.3f}s "
              f"acc={r['final_accuracy']:.3f}")

    # dispatch tier alone at population scale: 10^6 learners in one solve
    solve_rows = [solve_only_row(125_000, 8, mesh=mesh)]
    for r in solve_rows:
        print(f"solve F={r['F']:>6} K={r['K']} learners={r['learners']:>7} "
              f"solve={r['solve_s']:.3f}s ({r['learners_per_s']:.0f} "
              f"learners/s)")

    _merge_out("fleet_scale", {
        "mesh_devices": n_dev,
        "mesh_axes": dict(mesh.shape),
        "train": rows,
        "solve": solve_rows,
    })


if __name__ == "__main__":
    main()
