"""Bring-up check: the federated training path end to end on a TPU.

  python3 chip_smoke.py               # one chip: every phase below
  python3 chip_smoke.py --four-chips  # four chips: the fleet phase only

One process drives every phase through the library's normal entry points
and compares two paths run on the same device:

  device   the first device must be a TPU (``--four-chips``: four
           devices; four virtual CPU devices make a rehearsal)
  barrier  ``run_experiment`` at paper scale (K=10, T=15, 6000 samples,
           the [784, 300, 124, 60, 10] MLP), fused scan vs the eager
           ``Orchestrator.run``: tau/d identical, accuracies within
           ``ACC_ATOL`` and rising
  realloc  the same with a seeded ``CapacityDrift`` and the in-scan f64
           policy solve: integer tau/d identical to the eager re-solves
  async    ``AsyncFedEngine.run_events`` (fedasync, K=10) vs the eager
           ``run``: versions, staleness and weights exact, params within
           ``ASYNC_PARAM_ATOL``
  kernels  ``fed_agg``, ``waterfill_residual``,
           ``waterfill_energy_residual`` and both forms of
           ``train_agg_step``, compiled (no interpreter), each against its
           ``kernels/ref.py`` oracle with ``tpu_custom_call`` in the
           compiled HLO; then ``run_fused(use_pallas=True)`` at K=10 with
           the paper's largest shard (d_cap = d_upper = 1800)
  fleet    (``--four-chips``) ``FleetEngine`` rounds (10^4 learners) and
           the sharded ``_fleet_solve`` (10^6 learners) on the four-device
           mesh and on one device: feasible, exact budget totals, box
           bounds, zero padded and sampled-out rows

Any failed phase exits non-zero before the result line. The last line of
standard output is ``{"ok": true, "device": {...}}``.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import numpy as np  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

#: the paper's barrier configuration (Sec. V): K learners, cycle T, d/cycle
PAPER = dict(k=10, T=15.0, total_samples=6000, cycles=3, scheme="kkt_sai")
#: seed of the capacity drift the realloc phase re-solves under
DRIFT_SEED = 7
#: async phase: virtual-time horizon in cycles of T
ASYNC_CYCLES = 2

#: kernel sizes: paper-MLP leaves stacked over K=10 learners (fed_agg), a
#: 4096-fleet x K=10 population solve (waterfill), and K=10 learners with
#: the paper's largest shard, d_cap = d_upper = 1800 (train_agg_step)
FED_AGG_SHAPE = (10, 784, 300)
WATERFILL_SHAPE = (4096, 10)
TRAIN_K, TRAIN_D_CAP, TRAIN_TAU_MAX = 10, 1800, 8
#: fleet phase (``benchmarks/fleet_scale.py``'s sizes): F fleets x K
#: learners trained for R rounds on a [features, 32, 10] MLP, and the
#: 10^6-learner dispatch solve
FLEET_TRAIN = (1250, 8, 2, 64)
FLEET_SOLVE = (125_000, 8)

# Tolerances, each printed beside the difference it bounds.
#: |fused - eager| accuracy on the 2000-sample eval batch
ACC_ATOL = 0.02
#: |run_events - run| on the server params after the async horizon
ASYNC_PARAM_ATOL = 1e-3
#: compiled kernel vs its oracle: fed_agg and the waterfill residuals
#: (whose oracle divides and sums the same f32 rows); the megakernel's is
#: ``kernels.train_step.CHIP_RTOL``/``CHIP_ATOL``
FED_AGG_TOL = dict(rtol=1e-6, atol=1e-6)
WATERFILL_TOL = dict(rtol=2e-5, atol=2e-3)


class SmokeFailure(RuntimeError):
    pass


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def say(msg: str) -> None:
    print(msg, flush=True)


def max_diff(a, b) -> float:
    la, lb = jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)
    return max(float(np.max(np.abs(np.asarray(x, np.float64)
                                   - np.asarray(y, np.float64))))
               for x, y in zip(la, lb))


def close(name: str, got, want, *, rtol: float, atol: float) -> None:
    """Raise unless every leaf of ``got`` is within rtol/atol of ``want``;
    print the largest difference beside the tolerance."""
    diff = max_diff(got, want)
    say(f"  {name}: max |diff| {diff:.3e} (rtol {rtol:g}, atol {atol:g})")
    for x, y in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        x, y = np.asarray(x, np.float64), np.asarray(y, np.float64)
        check(x.shape == y.shape and bool(np.all(np.isfinite(x))),
              f"{name}: non-finite or misshapen output")
        check(bool(np.all(np.abs(x - y) <= atol + rtol * np.abs(y))),
              f"{name}: outside tolerance")


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------

def same_schedule(name: str, h1, h2) -> None:
    check(len(h1) == len(h2), f"{name}: history lengths differ")
    for c, (r1, r2) in enumerate(zip(h1, h2)):
        check(np.array_equal(r1["tau"], r2["tau"]), f"{name}: tau differs at cycle {c}")
        check(np.array_equal(r1["d"], r2["d"]), f"{name}: d differs at cycle {c}")


def accuracies(name: str, h1, h2, paths=("fused", "eager")) -> list[float]:
    a1 = [r["accuracy"] for r in h1]
    a2 = [r["accuracy"] for r in h2]
    diff = float(np.max(np.abs(np.subtract(a1, a2))))
    say(f"  {name}: accuracy {paths[0]} {np.round(a1, 4).tolist()} "
        f"{paths[1]} {np.round(a2, 4).tolist()}; max |diff| {diff:.4f} "
        f"(atol {ACC_ATOL})")
    check(diff <= ACC_ATOL, f"{name}: {paths[0]} and {paths[1]} accuracies differ")
    return a1


def phase_barrier() -> dict:
    from repro.fed.simulation import run_experiment

    fused = run_experiment(**PAPER, fused=True)
    eager = run_experiment(**PAPER, fused=False)
    same_schedule("barrier", fused["history"], eager["history"])
    acc = accuracies("barrier", fused["history"], eager["history"])
    check(acc[-1] > acc[0], "barrier: accuracy does not rise over the cycles")
    tau = fused["history"][0]["tau"]
    say(f"  barrier: tau {tau.min()}-{tau.max()}, "
        f"d max {fused['history'][0]['d'].max()}")
    return fused


def phase_realloc() -> dict:
    from repro.core import CapacityDrift
    from repro.fed.simulation import run_experiment

    drift = CapacityDrift(seed=DRIFT_SEED)
    fused = run_experiment(**PAPER, fused=True, reallocate=True, drift=drift)
    eager = run_experiment(**PAPER, fused=False, reallocate=True, drift=drift)
    same_schedule("realloc", fused["history"], eager["history"])
    accuracies("realloc", fused["history"], eager["history"])
    allocs = [(r["tau"].tolist(), r["d"].tolist()) for r in fused["history"]]
    check(any(a != allocs[0] for a in allocs[1:]),
          "realloc: the drift never moved the allocation")
    say("  realloc: (tau, d) per cycle " + "; ".join(
        f"tau {min(t)}-{max(t)} d {min(d)}-{max(d)}" for t, d in allocs))
    return fused


def phase_async() -> None:
    from repro.data.pipeline import synthetic_mnist
    from repro.fed.async_engine import AsyncConfig, AsyncFedEngine
    from repro.fed.simulation import build_problem
    from repro.models import mlp

    prob = build_problem(PAPER["k"], PAPER["T"],
                         total_samples=PAPER["total_samples"])
    train, test = synthetic_mnist(2 * PAPER["total_samples"], seed=0)
    horizon = ASYNC_CYCLES * PAPER["T"]
    cfg = AsyncConfig(mode="fedasync")
    ev = (test.x[:2000], test.y[:2000])
    params = mlp.init(jax.random.key(0))
    eager = AsyncFedEngine(cfg, prob, mlp.loss, params, seed=0)
    h1 = eager.run(train, horizon, eval_fn=mlp.accuracy, eval_batch=ev)
    scan = AsyncFedEngine(cfg, prob, mlp.loss, params, seed=0)
    h2 = scan.run_events(train, horizon, eval_fn=mlp.accuracy, eval_batch=ev)
    check(len(h1) == len(h2) > 0, "async: history lengths differ")
    for r1, r2 in zip(h1, h2):
        check(r1["server_version"] == r2["server_version"], "async: versions differ")
        check(r1["staleness_list"] == r2["staleness_list"], "async: staleness differs")
        check(np.array_equal(r1["weights"], r2["weights"]), "async: weights differ")
    stale = max(max(r["staleness_list"]) for r in h1)
    say(f"  async: {len(h1)} aggregations, max version staleness {stale}, "
        f"accuracy {h1[-1]['accuracy']:.4f} (eager) "
        f"{h2[-1]['accuracy']:.4f} (run_events)")
    close("async params", scan.params, eager.params, rtol=0.0,
          atol=ASYNC_PARAM_ATOL)


def oracle(fn, *args, **kw):
    """A ``kernels/ref.py`` oracle, jitted and run at highest matmul
    precision (the chip's default rounds f32 matmul inputs to bf16)."""
    with jax.default_matmul_precision("highest"):
        return jax.jit(lambda *a: fn(*a, **kw))(*args)


def compiled(fn, *args):
    """Compile ``fn`` for the device, insist on a Pallas kernel in its
    HLO, and run it."""
    exe = jax.jit(fn).lower(*args).compile()
    check("tpu_custom_call" in exe.as_text(),
          f"{getattr(fn, '__name__', fn)}: no tpu_custom_call in the HLO")
    return exe(*args)


def waterfill_rows(rng, b, k):
    """f32 (B, K) coefficient rows with tau* inside the clipping box."""
    f32 = lambda a: jnp.asarray(a, jnp.float32)
    return dict(
        tau=f32(rng.uniform(20.0, 80.0, b)),
        c2=f32(rng.uniform(1e-4, 1e-2, (b, k))),
        c1=f32(rng.uniform(1e-4, 1e-2, (b, k))),
        c0=f32(rng.uniform(0.1, 2.0, (b, k))),
        T=f32(rng.uniform(5.0, 20.0, b)),
        e2=f32(rng.uniform(1e-4, 1e-2, (b, k))),
        e1=f32(rng.uniform(1e-4, 1e-2, (b, k))),
        e0=f32(rng.uniform(0.05, 1.0, (b, k))),
        eb=f32(rng.uniform(2.0, 12.0, (b, k))),
        lo=f32(np.full((b, k), 10.0)),
        hi=f32(np.full((b, k), 900.0)),
        total=f32(rng.uniform(1e3, 5e3, b)),
    )


def train_case(seed: int = 0):
    """Megakernel operands at the smoke size: the paper MLP per learner,
    a padded (K, d_cap, 784) shard block with a random data mask."""
    from repro.models import mlp

    rng = np.random.default_rng(seed)
    k, n = TRAIN_K, TRAIN_D_CAP
    stack = [mlp.init(jax.random.key(int(s))) for s in rng.integers(2**31, size=k)]
    disp = jax.tree_util.tree_map(lambda *ls: jnp.stack(ls), *stack)
    x = jnp.asarray(rng.standard_normal((k, n, mlp.PAPER_LAYERS[0])), jnp.float32)
    y = jnp.asarray(rng.integers(0, mlp.PAPER_LAYERS[-1], (k, n)), jnp.int32)
    m = jnp.asarray(rng.random((k, n)) < 0.8, jnp.float32)
    tau = jnp.asarray(rng.integers(1, TRAIN_TAU_MAX + 1, k), jnp.int32)
    w = jnp.asarray(rng.uniform(0.1, 1.0, k), jnp.float32)
    server = mlp.init(jax.random.key(int(rng.integers(2**31))))
    acc = jax.tree_util.tree_map(
        lambda l: jnp.asarray(rng.standard_normal(l.shape) * 0.01, jnp.float32),
        server)
    return disp, x, y, m, tau, w, server, acc


def phase_kernels(barrier: dict, realloc: dict) -> None:
    from repro.core import CapacityDrift
    from repro.fed.simulation import build_problem, run_experiment
    from repro.kernels import ops, ref
    from repro.kernels.train_step import CHIP_ATOL, CHIP_RTOL
    from repro.models import mlp

    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal(FED_AGG_SHAPE), jnp.float32)
    w = jnp.asarray(rng.dirichlet(np.ones(FED_AGG_SHAPE[0])), jnp.float32)
    got = compiled(lambda x, w: ops.fed_agg(x, w, use_pallas=True), x, w)
    close("fed_agg", got, oracle(ref.fed_agg_ref, x, w), **FED_AGG_TOL)

    r = waterfill_rows(rng, *WATERFILL_SHAPE)
    t_args = (r["tau"], r["c2"], r["c1"], r["c0"], r["T"], r["lo"], r["hi"],
              r["total"])
    got = compiled(lambda *a: ops.waterfill_residual(*a, use_pallas=True),
                   *t_args)
    close("waterfill_residual", got,
          oracle(ref.waterfill_residual_ref, *t_args), **WATERFILL_TOL)
    e_args = (r["tau"], r["c2"], r["c1"], r["c0"], r["T"], r["e2"], r["e1"],
              r["e0"], r["eb"], r["lo"], r["hi"], r["total"])
    got = compiled(
        lambda *a: ops.waterfill_energy_residual(*a, use_pallas=True), *e_args)
    close("waterfill_energy_residual", got,
          oracle(ref.waterfill_energy_residual_ref, *e_args), **WATERFILL_TOL)

    disp, x, y, m, tau, w, server, acc = train_case()
    lr, keep, flush = jnp.float32(0.05), jnp.float32(0.4), jnp.float32(0.6)
    train_ref = dict(loss_fn=mlp.loss, max_tau=TRAIN_TAU_MAX)

    got, _ = compiled(lambda *a: ops.train_agg_step(
        *a, loss_fn=mlp.loss, use_pallas=True), disp, x, y, m, tau, w, lr)
    want, _ = oracle(ref.train_agg_step_ref, disp, x, y, m, tau, w, lr,
                     **train_ref)
    close("train_agg_step cycle form", got, want, rtol=CHIP_RTOL, atol=CHIP_ATOL)
    got = compiled(lambda d, x, y, m, t, w, lr, s, a, kp, fl: ops.train_agg_step(
        d, x, y, m, t, w, lr, loss_fn=mlp.loss, server=s, acc=a, keep=kp,
        flush=fl, use_pallas=True),
        disp, x, y, m, tau, w, lr, server, acc, keep, flush)
    want = oracle(ref.train_agg_step_ref, disp, x, y, m, tau, w, lr,
                  server=server, acc=acc, keep=keep, flush=flush, **train_ref)
    close("train_agg_step accumulate/flush form", got, want, rtol=CHIP_RTOL, atol=CHIP_ATOL)

    paths = ("megakernel", "unfused")
    pal = run_experiment(**PAPER, fused=True, use_pallas=True)
    same_schedule("megakernel barrier", pal["history"], barrier["history"])
    accuracies("megakernel barrier", pal["history"], barrier["history"], paths)
    pal = run_experiment(**PAPER, fused=True, reallocate=True,
                         drift=CapacityDrift(seed=DRIFT_SEED), use_pallas=True)
    same_schedule("megakernel realloc", pal["history"], realloc["history"])
    accuracies("megakernel realloc", pal["history"], realloc["history"], paths)
    d_cap = build_problem(PAPER["k"], PAPER["T"],
                          total_samples=PAPER["total_samples"]).d_upper
    say(f"  megakernel run_fused ran at K={PAPER['k']} x d_cap={d_cap} "
        "(the realloc scan's shard width)")


def fleet_invariants(name, tau, d, feas, sampled, bp) -> None:
    """``tests/test_fleet_sharded.py``'s invariants on one solve: feasible,
    exact budget totals, box bounds, zeros in sampled-out rows."""
    lo, hi = np.asarray(bp.d_lo), np.asarray(bp.d_hi)
    check(bool(np.asarray(feas)[sampled].all()), f"{name}: infeasible solve")
    check((tau[~sampled] == 0).all() and (d[~sampled] == 0).all(),
          f"{name}: sampled-out or padded rows are not zero")
    check((d[sampled].sum(axis=1) == np.asarray(bp.total)[sampled]).all(),
          f"{name}: budget totals are not exact")
    check((d[sampled] >= lo[sampled]).all() and (d[sampled] <= hi[sampled]).all(),
          f"{name}: allocation outside the box")


def phase_fleet() -> None:
    from repro.data.pipeline import synthetic_mnist
    from repro.fed.fleet import (FleetConfig, FleetEngine, _fleet_solve,
                                 build_fleet_problems)
    from repro.launch.mesh import host_mesh
    from repro.models import mlp
    from repro.sharding.rules import fleet_partition_axes

    f, k, rounds, features = FLEET_TRAIN
    bp = build_fleet_problems(f, k, T=6.0, total_samples=40, seed=0)
    train, test = synthetic_mnist(6000, n_test=2000, features=features, seed=0)
    params = mlp.init(jax.random.key(0), layers=[features, 32, 10])
    f_solve, k_solve = FLEET_SOLVE
    bp_solve = build_fleet_problems(f_solve, k_solve, seed=0)
    sampled = np.zeros(f_solve, bool)
    sampled[::2] = True
    meshes = {
        "4-device": host_mesh(),
        "1-device": jax.make_mesh((1, 1), ("data", "model"),
                                  devices=jax.devices()[:1]),
    }
    check(meshes["4-device"].devices.size == 4, "fleet: host_mesh is not 4 devices")
    seen = {}
    for name, mesh in meshes.items():
        eng = FleetEngine(FleetConfig(participation=0.5), bp, mlp.loss,
                          params, seed=0, mesh=mesh)
        hist = eng.run(train, rounds, eval_fn=mlp.accuracy,
                       eval_batch=(test.x, test.y))
        for r in hist:
            check((r["d"].sum(axis=1) == 40).all(),
                  f"fleet {name}: a round's budget is not exact")
        leaves = jax.tree_util.tree_leaves(eng.global_params)
        check(all(bool(np.isfinite(np.asarray(l)).all()) for l in leaves),
              f"fleet {name}: non-finite global model")
        check((eng.pull_version[~eng._real] == 0).all(),
              f"fleet {name}: a padded fleet merged")
        acc = [round(r["accuracy"], 4) for r in hist]
        check(acc[-1] > acc[0], f"fleet {name}: accuracy does not rise")

        axes = fleet_partition_axes(f_solve, mesh)
        with jax.enable_x64(True):
            args = [jnp.asarray(a, dt) for a, dt in (
                (bp_solve.c2, jnp.float64), (bp_solve.c1, jnp.float64),
                (bp_solve.c0, jnp.float64), (bp_solve.T, jnp.float64),
                (bp_solve.total, jnp.int64), (bp_solve.d_lo, jnp.float64),
                (bp_solve.d_hi, jnp.float64), (bp_solve.valid, bool),
                (sampled, bool))]
            tau, d, feas = _fleet_solve(*args, scheme="kkt_sai", mesh=mesh,
                                        fleet_axes=axes)
            tau, d = np.asarray(tau, np.int64), np.asarray(d, np.int64)
        fleet_invariants(f"fleet solve {name}", tau, d, feas, sampled, bp_solve)
        seen[name] = ([r["sampled_fleets"] for r in hist], d)
        say(f"  fleet {name} mesh {dict(mesh.shape)}: {f} fleets x {k} "
            f"(padded to {eng._real.size}, axes {eng.fleet_axes}), sampled "
            f"{seen[name][0]}, accuracy {acc}; solve {f_solve} x {k_solve} "
            f"learners over axes {axes}: feasible, exact")
    check(seen["4-device"][0] == seen["1-device"][0],
          "fleet: the two meshes sampled different fleets")
    moved = int((seen["4-device"][1] != seen["1-device"][1]).any(axis=1).sum())
    say(f"  fleet solve: {moved} of {f_solve} fleets split their budget "
        "differently on 4 devices than on 1 (reduction order; both exact)")


# ---------------------------------------------------------------------------

def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the fleet phase on a four-device mesh")
    args = ap.parse_args()

    devs = jax.devices()
    dev = devs[0]
    if args.four_chips:
        if len(devs) != 4:
            print(f"chip_smoke: --four-chips needs 4 devices, found "
                  f"{len(devs)} {dev.platform}", file=sys.stderr)
            return 2
    elif dev.platform != "tpu":
        print(f"chip_smoke: no TPU: JAX found {len(devs)} {dev.platform} "
              "device(s)", file=sys.stderr)
        return 2
    say(f"device: {dev.platform} {dev.device_kind} x{len(devs)}")

    from repro.launch.compile_cache import enable_compile_cache

    cache = {"hits": 0, "misses": 0}

    def count(event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            cache["hits"] += 1
        elif event == "/jax/compilation_cache/cache_misses":
            cache["misses"] += 1

    jax.monitoring.register_event_listener(count)
    cache_dir = enable_compile_cache()

    phases = ([("fleet", phase_fleet)] if args.four_chips else [
        ("barrier", phase_barrier), ("realloc", phase_realloc),
        ("async", phase_async), ("kernels", None),
    ])
    results = {}
    for name, fn in phases:
        t0 = time.perf_counter()
        say(f"[{name}]")
        if name == "kernels":
            phase_kernels(results["barrier"], results["realloc"])
        else:
            results[name] = fn()
        say(f"[{name}] passed in {time.perf_counter() - t0:.1f} s")
    say(f"compile cache {cache_dir}: {cache['hits']} hits, "
        f"{cache['misses']} misses")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devs)}}), flush=True)
    return 0


if __name__ == "__main__":
    try:
        sys.exit(main())
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        sys.exit(1)
