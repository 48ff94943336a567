"""End-to-end MEL experiment driver (reproduces the paper's Figs. 2-3).

Builds the 802.11 indoor environment, derives the time-model coefficients
from the paper's exact MNIST-DNN constants (S_m = 8,974,080 bits,
C_m = 1,123,736 FLOPs/sample), allocates with the requested scheme, and
runs asynchronous federated training on synthetic MNIST-class data.
"""

from __future__ import annotations

import dataclasses
import functools
import time

import numpy as np

import jax

from repro.core import (
    AllocationProblem,
    BatchedProblems,
    CapacityDrift,
    EnergyModel,
    TimeModel,
    batched_avg_staleness,
    batched_max_staleness,
    batched_summary,
    indoor_80211_profile,
    mnist_dnn_cost,
    solve_energy_batched,
    solve_eta_batched,
    solve_kkt_batched,
)
from repro.data.pipeline import Dataset, synthetic_mnist
from repro.fed.orchestrator import MELConfig, Orchestrator, SCHEMES
from repro.models import mlp

__all__ = [
    "build_problem",
    "build_spread_problem",
    "run_experiment",
    "staleness_sweep",
    "drift_staleness_sweep",
    "run_async_experiment",
    "async_mode_sweep",
    "churn_sweep",
    "build_energy_problem",
    "energy_sweep",
    "fleet_scale_sweep",
    "multi_model_sweep",
    "laggard_time_to_accuracy",
]


def build_problem(
    k: int,
    T: float,
    *,
    total_samples: int = 6000,
    d_lower_frac: float = 0.25,
    d_upper_frac: float = 3.0,
    seed: int = 0,
) -> AllocationProblem:
    cost = mnist_dnn_cost()
    profiles = indoor_80211_profile(k, seed=seed)
    tm = TimeModel.build(
        profiles,
        model_complexity_flops=cost.flops_per_sample,
        model_size_bits=cost.model_bits,
    )
    d_l = max(1, int(d_lower_frac * total_samples / k))
    d_u = min(total_samples, int(d_upper_frac * total_samples / k))
    return AllocationProblem(
        time_model=tm, T=T, total_samples=total_samples, d_lower=d_l, d_upper=d_u
    )


def build_spread_problem(
    k: int = 3, T: float = 6.0, *, total_samples: int = 60,
) -> AllocationProblem:
    """A small (K <= 5) fleet whose integer-rounded cycle times land well
    apart — the regime where the async engine's exact bucket grid stays
    small and local training stays cheap. The KKT allocator equalizes
    *relaxed* finish times, so the spread comes from the integer tau
    rounding: the coefficients are hand-picked to make that slack differ
    per learner. Shared by the async tests and ``benchmarks/async_bench``
    so the spread property is tuned in one place."""
    if not (1 <= k <= 5):
        raise ValueError("the hand-tuned spread fleet has at most 5 learners")
    c2 = np.array([0.050, 0.031, 0.022, 0.045, 0.027])[:k]
    c1 = np.array([0.004, 0.006, 0.003, 0.005, 0.002])[:k]
    c0 = np.array([0.40, 0.55, 0.30, 0.25, 0.45])[:k]
    return AllocationProblem(
        time_model=TimeModel(c2=c2, c1=c1, c0=c0), T=T,
        total_samples=total_samples,
        d_lower=max(1, total_samples // (2 * k)),
        d_upper=min(total_samples, 2 * total_samples // k),
    )


_BATCHED_SCHEMES = {
    "kkt_sai": solve_kkt_batched,
    "eta": solve_eta_batched,
    "kkt_energy": solve_energy_batched,
}


def staleness_sweep(ks, T: float, *, schemes=("kkt_sai", "slsqp", "eta"), seed: int = 0,
                    total_samples: int = 6000, seeds=None,
                    use_batched: bool = True, reallocate: bool = False,
                    drift: CapacityDrift | None = None,
                    cycles: int = 8) -> list[dict]:
    """Fig. 2: max/avg staleness vs number of learners K per scheme.

    With ``use_batched`` (default) every (K, seed) fleet is padded into one
    ``BatchedProblems`` tensor and each batched scheme (kkt_sai, eta) is ONE
    ``solve_*_batched`` call for the whole sweep; remaining schemes fall
    back to the per-problem solvers. On feasible points the rows are
    identical to the eager path (the batched engine replicates the NumPy
    solvers exactly); infeasible points carry the same error message for
    the bisection-infeasibility case the batched solver detects.

    ``reallocate=True`` switches to the time-varying sweep: capacities
    drift per cycle (``drift``, default ``CapacityDrift(seed=seed)``) and
    each scheme is scored both adaptively (re-solved every cycle — ALL
    case x cycle problems batched into one ``solve_*_batched`` call) and
    statically (solved once on the base capacities, staleness then measured
    under the drifted capacities) — see ``drift_staleness_sweep``.
    """
    if reallocate:
        return drift_staleness_sweep(
            ks, T, cycles=cycles, drift=drift, schemes=schemes, seed=seed,
            total_samples=total_samples, seeds=seeds,
        )
    seeds = (seed,) if seeds is None else tuple(seeds)
    cases = [(k, s) for k in ks for s in seeds]
    probs = [
        build_problem(k, T, seed=s, total_samples=total_samples)
        for k, s in cases
    ]

    rows: list[dict] = []
    batched = {}
    if use_batched:
        bp = BatchedProblems.from_problems(probs)
        for scheme in schemes:
            if scheme in _BATCHED_SCHEMES:
                ba = _BATCHED_SCHEMES[scheme](bp)
                batched[scheme] = (ba, ba.summary(bp))

    for i, ((k, s), prob) in enumerate(zip(cases, probs)):
        for scheme in schemes:
            row = {"K": k, "T": T, "scheme": scheme}
            if len(seeds) > 1:
                row["seed"] = s
            if scheme in batched:
                ba, summ = batched[scheme]
                if not ba.feasible[i]:
                    # same wording as solver_kkt.solve_relaxed's ValueError
                    row["error"] = (
                        "infeasible: even with tau=0 the deadline T cannot "
                        "absorb d samples"
                    )
                else:
                    row.update(
                        max_staleness=int(summ["max_staleness"][i]),
                        avg_staleness=float(summ["avg_staleness"][i]),
                        total_updates=int(summ["total_updates"][i]),
                    )
                rows.append(row)
                continue
            try:
                alloc = SCHEMES[scheme](prob)
                sm = alloc.summary(prob)
                row.update(
                    max_staleness=sm["max_staleness"],
                    avg_staleness=sm["avg_staleness"],
                    total_updates=sm["total_updates"],
                )
            except ValueError as e:
                row["error"] = str(e)
            rows.append(row)
    return rows


def drift_staleness_sweep(ks, T: float, *, cycles: int = 8,
                          drift: CapacityDrift | None = None,
                          schemes=("kkt_sai", "eta"), seed: int = 0,
                          total_samples: int = 6000, seeds=None) -> list[dict]:
    """Adaptive-vs-static staleness under time-varying edge capacities.

    For every (K, seed) fleet the drifted capacity path (C cycles) is
    scored two ways per scheme:

      * ``mode="adaptive"`` — the allocation is re-solved on each cycle's
        true capacities; ALL case x cycle problems are padded into ONE
        mixed-K ``BatchedProblems`` struct and solved with a single
        ``solve_*_batched`` call per scheme;
      * ``mode="static"`` — the allocation is solved once on the base
        (cycle-averaged) capacities and frozen; each cycle's realized
        tau_k is then the largest integer feasible under that cycle's TRUE
        capacities with the frozen d_k, so staleness reflects the drift the
        static scheduler ignored.

    Rows report mean/worst max-staleness and mean avg-staleness over the C
    cycles. Schemes are restricted to the batched engines (kkt_sai, eta).
    """
    drift = CapacityDrift(seed=seed) if drift is None else drift
    seeds_ = (seed,) if seeds is None else tuple(seeds)
    cases = [(k, s) for k in ks for s in seeds_]
    probs = [
        build_problem(k, T, seed=s, total_samples=total_samples)
        for k, s in cases
    ]
    unsupported = [s for s in schemes if s not in _BATCHED_SCHEMES]
    schemes = [s for s in schemes if s in _BATCHED_SCHEMES]
    n = len(cases)
    kmax = max(p.num_learners for p in probs)

    # one (n * cycles, kmax) struct holding every drifted cycle-problem
    paths = [drift.coefficient_path(p.time_model, cycles) for p in probs]
    b = n * cycles
    c2 = np.ones((b, kmax)); c1 = np.ones((b, kmax)); c0 = np.zeros((b, kmax))
    d_lo = np.zeros((b, kmax)); d_hi = np.zeros((b, kmax))
    valid = np.zeros((b, kmax), bool)
    Tb = np.full(b, T); total = np.full(b, total_samples, np.int64)
    for i, (p, (c2s, c1s, c0s)) in enumerate(zip(probs, paths)):
        kk = p.num_learners
        rows = slice(i * cycles, (i + 1) * cycles)
        c2[rows, :kk], c1[rows, :kk], c0[rows, :kk] = c2s, c1s, c0s
        d_lo[rows, :kk] = p.d_lower
        d_hi[rows, :kk] = p.d_upper
        valid[rows, :kk] = True
    bp_drift = BatchedProblems(c2, c1, c0, Tb, total, d_lo, d_hi, valid)
    bp_base = BatchedProblems.from_problems(probs)

    out: list[dict] = []
    for scheme in unsupported:
        # requested schemes without a batched engine get explicit error
        # rows (mirrors the non-realloc sweep's row-per-scheme contract)
        for (k, s) in cases:
            row = {"K": k, "T": T, "scheme": scheme, "cycles": cycles,
                   "error": (f"scheme {scheme!r} has no batched engine; the "
                             "drift sweep supports "
                             + " | ".join(sorted(_BATCHED_SCHEMES)))}
            if len(seeds_) > 1:
                row["seed"] = s
            out.append(row)
    for scheme in schemes:
        solver = _BATCHED_SCHEMES[scheme]
        ba = solver(bp_drift)
        summ = ba.summary(bp_drift)
        ba_static = solver(bp_base)
        for i, ((k, s), p, (c2s, c1s, c0s)) in enumerate(zip(cases, probs, paths)):
            rows = slice(i * cycles, (i + 1) * cycles)
            base = {"K": k, "T": T, "scheme": scheme, "cycles": cycles}
            if len(seeds_) > 1:
                base["seed"] = s
            if not ba.feasible[rows].all() or not ba_static.feasible[i]:
                out.append({**base, "error": (
                    "infeasible: even with tau=0 the deadline T cannot "
                    "absorb d samples"
                )})
                continue
            smax = summ["max_staleness"][rows]
            savg = summ["avg_staleness"][rows]
            out.append({
                **base, "mode": "adaptive",
                "max_staleness_mean": float(smax.mean()),
                "max_staleness_worst": int(smax.max()),
                "avg_staleness_mean": float(savg.mean()),
                "total_updates_mean": float(summ["total_updates"][rows].mean()),
            })
            # frozen allocation, realized tau under each cycle's true caps:
            # a (C, K)-broadcast TimeModel reuses max_tau's clamp semantics
            kk = p.num_learners
            d0 = ba_static.d[i, :kk].astype(float)
            tau_c = TimeModel(c2=c2s, c1=c1s, c0=c0s).max_tau(
                np.broadcast_to(d0, c2s.shape), T
            )
            smax_s = batched_max_staleness(tau_c)
            savg_s = batched_avg_staleness(tau_c)
            upd = (tau_c * d0[None]).sum(axis=1)
            out.append({
                **base, "mode": "static",
                "max_staleness_mean": float(smax_s.mean()),
                "max_staleness_worst": int(smax_s.max()),
                "avg_staleness_mean": float(savg_s.mean()),
                "total_updates_mean": float(upd.mean()),
            })
    return out


def run_experiment(
    *,
    k: int = 10,
    T: float = 15.0,
    cycles: int = 12,
    scheme: str = "kkt_sai",
    aggregation: str = "staleness",
    total_samples: int = 6000,
    lr: float = 0.1,
    seed: int = 0,
    train: Dataset | None = None,
    test: Dataset | None = None,
    fused: bool = False,
    use_pallas: bool = False,
    reallocate: bool = False,
    drift: CapacityDrift | None = None,
) -> dict:
    """One full MEL run; returns history with accuracy per global cycle.

    ``fused=True`` routes through the orchestrator's scan-over-cycles fast
    path (one XLA program for the whole run, eval inside the scan) and
    reproduces the eager history for the same seed. ``reallocate=True``
    re-solves the allocation every cycle — on the fused path this happens
    inside the scan on the traced capacity state; pass a ``CapacityDrift``
    to make the re-solve react to time-varying capacities. ``drift``
    without ``reallocate`` is ignored (with a warning): the training loop
    simulates the base capacities; frozen-allocation-under-drift staleness
    analysis lives in ``drift_staleness_sweep``.
    """
    if train is None or test is None:
        train, test = synthetic_mnist(max(total_samples * 2, 12_000), seed=seed)
    prob = build_problem(k, T, total_samples=total_samples, seed=seed)
    mel = MELConfig(
        T=T, total_samples=total_samples, lr=lr, scheme=scheme, aggregation=aggregation
    )
    params = mlp.init(jax.random.key(seed))
    orch = Orchestrator(mel, prob, mlp.loss, params, seed=seed, drift=drift)

    if fused:
        history = orch.run(
            train, cycles, fused=True, eval_fn=mlp.accuracy,
            eval_batch=(test.x[:2000], test.y[:2000]), use_pallas=use_pallas,
            reallocate=reallocate,
        )
    else:
        eval_fn = functools.partial(_accuracy, x=test.x[:2000], y=test.y[:2000])
        history = orch.run(train, cycles, eval_fn=eval_fn, reallocate=reallocate)
    return {
        "scheme": scheme,
        "K": k,
        "T": T,
        "history": history,
        "final_accuracy": history[-1]["accuracy"],
        "allocation": orch.allocation.summary(prob),
    }


@functools.partial(jax.jit, static_argnames=())
def _acc_jit(params, x, y):
    return mlp.accuracy(params, x, y)


def _accuracy(params, *, x, y):
    return _acc_jit(params, x, y)


# ---------------------------------------------------------------------------
# event-driven asynchronous federation (fed.async_engine)
# ---------------------------------------------------------------------------

def run_async_experiment(
    *,
    k: int = 6,
    T: float = 10.0,
    cycles: int = 6,
    mode: str = "fedasync",
    scheme: str = "kkt_sai",
    aggregation: str = "staleness",
    total_samples: int = 2000,
    lr: float = 0.1,
    seed: int = 0,
    drift: CapacityDrift | None = None,
    reallocate: bool = False,
    alpha: float = 0.6,
    staleness_fn: str = "poly",
    buffer_size: int = 0,
    bucketed: bool = False,
    num_buckets: int = 0,
    strict: bool = True,
    train: Dataset | None = None,
    test: Dataset | None = None,
    problem=None,
    max_events: int = 100_000,
    faults: dict | None = None,
) -> dict:
    """One event-driven async MEL run to virtual time ``cycles * T``.

    ``mode`` selects the server: ``"cycle"`` is the paper's cycle-gated
    scheme expressed as the engine's barrier regime (buffered, M = K, so
    the three modes share one code path and one rng discipline),
    ``"fedasync"`` mixes per upload with version-staleness discounting,
    ``"buffered"`` flushes a size-M buffer (default M = K/2, min 2).
    ``bucketed=True`` routes through the device-resident scan (event
    modes only): ``num_buckets=0`` (default) takes the exact
    event-indexed path (``run_events``, no grid needed);
    ``num_buckets > 0`` forces the legacy fixed grid (``run_bucketed``,
    benchmarking only). Pass ``problem`` to override the default
    MNIST-constants environment (``build_problem``) with a custom fleet.
    ``drift`` accepts a ``CapacityDrift``, a state-coupled ``QueueDrift``
    (``reallocate=True`` required), or an availability process
    (``core.availability``) for client churn. ``faults`` forwards fault
    knobs (``drop_rate``, ``straggler_rate``, ``deadline``, ``quorum``,
    ... — see ``AsyncConfig``) into the config; event modes only (the
    cycle barrier is the fault-free paper regime and rejects them). The
    returned summary's ``"faults"`` dict carries the schedule's fault
    counters.
    """
    from repro.fed.async_engine import (
        AsyncConfig, AsyncFedEngine, summarize_async_history,
    )

    if problem is None:
        problem = build_problem(k, T, total_samples=total_samples, seed=seed)
    else:
        k, T = problem.num_learners, problem.T
        total_samples = problem.total_samples
    # dataset sizing must see the RESOLVED per-cycle budget (a problem=
    # override replaces total_samples above)
    if train is None or test is None:
        train, test = synthetic_mnist(max(total_samples * 2, 12_000), seed=seed)
    horizon = cycles * T
    common = dict(scheme=scheme, aggregation=aggregation, lr=lr,
                  reallocate=reallocate, **(faults or {}))
    if mode == "cycle":
        cfg = AsyncConfig(mode="buffered", barrier=True, **common)
    elif mode == "buffered":
        cfg = AsyncConfig(
            mode="buffered", alpha=alpha, staleness_fn=staleness_fn,
            buffer_size=buffer_size or max(2, k // 2), **common,
        )
    else:
        cfg = AsyncConfig(
            mode=mode, alpha=alpha, staleness_fn=staleness_fn, **common
        )
    params = mlp.init(jax.random.key(seed))
    eng = AsyncFedEngine(cfg, problem, mlp.loss, params, seed=seed, drift=drift)
    eval_batch = (test.x[:2000], test.y[:2000])
    if bucketed:
        if mode == "cycle":
            raise ValueError(
                "mode='cycle' is the barrier regime: its one-XLA-program "
                "path is Orchestrator.run_fused (run_experiment(fused="
                "True)); bucketed=True applies to the event-driven modes"
            )
        if num_buckets:
            history = eng.run_bucketed(
                train, horizon, num_buckets, eval_fn=mlp.accuracy,
                eval_batch=eval_batch, strict=strict, max_events=max_events,
            )
        else:
            history = eng.run_events(
                train, horizon, eval_fn=mlp.accuracy, eval_batch=eval_batch,
                max_events=max_events,
            )
    else:
        history = eng.run(
            train, horizon, eval_fn=mlp.accuracy, eval_batch=eval_batch,
            max_events=max_events,
        )
    summary = summarize_async_history(
        history, counters=eng.fault_counters, energy=eng.energy_ledger
    )
    return {
        "mode": mode,
        "scheme": scheme,
        "K": k,
        "T": T,
        "cycles": cycles,
        "bucketed": bucketed,
        "history": history,
        "summary": summary,
        "final_accuracy": summary["final_accuracy"],
        "accuracy_trace": [
            (round(float(r["t"]), 3), round(float(r["accuracy"]), 4))
            for r in history if "accuracy" in r
        ],
    }


def async_mode_sweep(
    ks,
    T: float,
    *,
    cycles: int = 6,
    modes=("cycle", "fedasync", "buffered"),
    drift: CapacityDrift | None = None,
    scheme: str = "kkt_sai",
    seed: int = 0,
    total_samples: int = 2000,
    reallocate: bool = True,
    alpha: float = 0.6,
    staleness_fn: str = "poly",
    problem=None,
    train: Dataset | None = None,
    test: Dataset | None = None,
) -> list[dict]:
    """Score the paper's cycle-gated scheme against FedAsync and buffered
    asynchronous aggregation at EQUAL virtual time (``cycles * T`` seconds
    of simulated wall clock) under time-varying capacities.

    Every mode trains the same model on the same data stream discipline
    and reports final accuracy, the version-staleness profile of its
    aggregations, and the aggregation/upload counts — the async twin of
    ``drift_staleness_sweep``. ``drift`` defaults to
    ``CapacityDrift(seed=seed)``; pass ``reallocate=False`` to freeze
    every mode's allocation at the base capacities instead.
    """
    drift = CapacityDrift(seed=seed) if drift is None else drift
    rows: list[dict] = []
    for k in np.atleast_1d(ks):
        for mode in modes:
            try:
                res = run_async_experiment(
                    k=int(k), T=T, cycles=cycles, mode=mode, scheme=scheme,
                    seed=seed, total_samples=total_samples, drift=drift,
                    reallocate=reallocate, alpha=alpha,
                    staleness_fn=staleness_fn, problem=problem,
                    train=train, test=test,
                )
            except ValueError as e:
                rows.append({"K": int(k), "T": T, "mode": mode,
                             "cycles": cycles, "error": str(e)})
                continue
            s = res["summary"]
            rows.append({
                "K": res["K"],      # a problem= override resolves K and T
                "T": res["T"],
                "mode": mode,
                "cycles": cycles,
                "scheme": scheme,
                "reallocate": reallocate,
                "final_accuracy": res["final_accuracy"],
                "aggregations": s["aggregations"],
                "uploads": s["uploads"],
                "virtual_time": s["virtual_time"],
                "staleness_mean": s["staleness"]["mean"],
                "staleness_max": s["staleness"]["max"],
                "accuracy_trace": res["accuracy_trace"][:40],
            })
    return rows


def churn_sweep(
    drop_rates=(0.0, 0.2, 0.4),
    *,
    mode: str = "buffered",
    cycles: int = 10,
    seed: int = 0,
    policies=("adaptive", "static", "equal"),
    problem=None,
    train: Dataset | None = None,
    test: Dataset | None = None,
) -> list[dict]:
    """Adaptive KKT reallocation vs frozen/equal allocation as the fleet
    churns: one event-driven run per (dropout rate, policy) cell under a
    compound fault schedule, at equal virtual time.

    Each ``rate`` drives BOTH the client-availability Markov chain
    (``MarkovAvailability(p_drop=rate)`` — learners go offline between
    blocks) and upload loss (``drop_rate = rate / 2``), on top of a fixed
    straggler/delay/deadline-retry background and, in buffered mode, a
    quorum of 2 with graceful degradation — the regime the paper's
    allocator is supposed to absorb. Policies: ``"adaptive"`` re-solves
    the masked KKT allocation per drift block, ``"static"`` freezes the
    base KKT solve (dispatched whenever a learner is online), and
    ``"equal"`` re-solves the equal-task baseline (``eta``) per block.

    Every cell runs the exact event-indexed scan path (``run_events``)
    and reports accuracy, staleness quantiles and the schedule's fault
    counters; no cell may stall or raise, so a degraded fleet must still
    produce a history. The churn twin of ``async_mode_sweep``; feeds
    ``benchmarks/churn_bench.py``.
    """
    from repro.core.availability import MarkovAvailability

    prob = problem or build_spread_problem(k=4, total_samples=80)
    k, T = prob.num_learners, prob.T
    if train is None or test is None:
        train, test = synthetic_mnist(6000, seed=seed)
    policy_kw = {
        "adaptive": dict(scheme="kkt_sai", reallocate=True),
        "static": dict(scheme="kkt_sai", reallocate=False),
        "equal": dict(scheme="eta", reallocate=True),
    }
    rows: list[dict] = []
    for rate in drop_rates:
        availability = MarkovAvailability(
            p_drop=float(rate), p_join=0.5, seed=seed,
        )
        faults = dict(
            drop_rate=float(rate) / 2,
            straggler_rate=0.2, straggler_factor=3.0,
            delay_rate=0.2, delay_mean=0.5 * T,
            deadline=2.5 * T, retry_backoff=0.25 * T, retry_backoff_cap=T,
        )
        if mode == "buffered":
            faults.update(quorum=2, flush_timeout=1.5 * T)
        for policy in policies:
            res = run_async_experiment(
                mode=mode, cycles=cycles, seed=seed, problem=prob,
                train=train, test=test, drift=availability,
                buffer_size=min(3, k), bucketed=True, faults=faults,
                **policy_kw[policy],
            )
            s = res["summary"]
            rows.append({
                "K": k,
                "T": T,
                "mode": mode,
                "cycles": cycles,
                "drop_rate": float(rate),
                "policy": policy,
                "final_accuracy": res["final_accuracy"],
                "aggregations": s["aggregations"],
                "uploads": s["uploads"],
                "virtual_time": s["virtual_time"],
                "staleness_mean": s["staleness"]["mean"],
                "staleness_p50": s["staleness"]["p50"],
                "staleness_p90": s["staleness"]["p90"],
                "staleness_p99": s["staleness"]["p99"],
                "staleness_max": s["staleness"]["max"],
                "faults": s["faults"],
            })
    return rows


def build_energy_problem(
    k: int,
    T: float,
    *,
    total_samples: int = 2000,
    d_lower_frac: float = 0.25,
    d_upper_frac: float = 3.0,
    e_budget=None,
    seed: int = 0,
) -> AllocationProblem:
    """``build_problem`` with the matching per-cycle ``EnergyModel``
    attached: the same 802.11 profiles and MNIST-DNN constants feed both
    the time model (Eq. 5) and its energy mirror, so the (tau, d) decision
    variables carry a joule cost per cycle. ``e_budget=None`` attaches the
    model for ACCOUNTING only (any scheme may run; ``Allocation.validate``
    has nothing to enforce); a finite budget makes the problem strict —
    only energy-aware schemes (``kkt_energy``, the budgeted ``pgd``) can
    solve it."""
    cost = mnist_dnn_cost()
    profiles = indoor_80211_profile(k, seed=seed)
    tm = TimeModel.build(
        profiles,
        model_complexity_flops=cost.flops_per_sample,
        model_size_bits=cost.model_bits,
    )
    em = EnergyModel.build(
        profiles,
        model_complexity_flops=cost.flops_per_sample,
        model_size_bits=cost.model_bits,
    )
    d_l = max(1, int(d_lower_frac * total_samples / k))
    d_u = min(total_samples, int(d_upper_frac * total_samples / k))
    return AllocationProblem(
        time_model=tm, T=T, total_samples=total_samples,
        d_lower=d_l, d_upper=d_u, energy=em, e_budget=e_budget,
    )


def energy_sweep(
    budget_fracs=(0.5, 0.75, 1.0),
    *,
    k: int = 4,
    T: float = 10.0,
    cycles: int = 8,
    mode: str = "fedasync",
    schemes=("kkt_energy", "kkt_sai", "eta"),
    total_samples: int = 800,
    seed: int = 0,
    train: Dataset | None = None,
    test: Dataset | None = None,
) -> list[dict]:
    """Accuracy-vs-energy frontier: the budgeted KKT allocation against the
    energy-blind schemes across per-learner battery budgets, at equal
    virtual time.

    The budget axis is anchored to the fleet's OWN unconstrained spend:
    the blind ``kkt_sai`` allocation's per-learner cycle energies ``E0``
    set the scale, and each level dispatches under the uniform budget
    ``frac * median(E0)`` joules per cycle. ``kkt_energy`` solves WITH the
    budget (per-dispatch re-solves included — ``reallocate=True`` routes
    every re-dispatch through the budgeted policy) and must report zero
    violations by construction; the blind schemes run on the same fleet
    with the energy model attached for accounting only (a strict budgeted
    problem would be rejected by ``Allocation.validate`` at solve time),
    and their overruns are counted EXTERNALLY against the same budget from
    the per-dispatch joules in the history. Rows report final accuracy,
    total/percentile joules, and the violation counts — the frontier data
    for ``benchmarks/energy_bench.py``."""
    prob_free = build_energy_problem(
        k, T, total_samples=total_samples, seed=seed
    )
    em = prob_free.energy
    alloc0 = SCHEMES["kkt_sai"](prob_free)
    e_blind = em.cycle_energy(alloc0.tau, alloc0.d)
    if train is None or test is None:
        train, test = synthetic_mnist(max(total_samples * 2, 12_000), seed=seed)
    rows: list[dict] = []
    for frac in budget_fracs:
        eb = float(frac) * float(np.median(e_blind))
        for scheme in schemes:
            aware = scheme in ("kkt_energy", "pgd")
            prob = (dataclasses.replace(prob_free, e_budget=eb)
                    if aware else prob_free)
            res = run_async_experiment(
                mode=mode, cycles=cycles, seed=seed, problem=prob,
                train=train, test=test, scheme=scheme, reallocate=True,
                bucketed=(mode != "cycle"),
            )
            s = res["summary"]
            # blind schemes never see the budget: score their dispatches
            # against it after the fact (the frontier's violation axis)
            overruns = sum(
                int((np.atleast_1d(r.get("energy", [])) > eb * (1 + 1e-9)).sum())
                for r in res["history"]
            )
            rows.append({
                "K": k,
                "T": T,
                "mode": mode,
                "cycles": cycles,
                "scheme": scheme,
                "energy_aware": aware,
                "budget_frac": float(frac),
                "e_budget_j": round(eb, 4),
                "final_accuracy": res["final_accuracy"],
                "aggregations": s["aggregations"],
                "uploads": s["uploads"],
                "joules_total": round(s["energy"]["joules_total"], 3),
                "joules_p50": round(s["energy"]["joules_p50"], 4),
                "joules_p99": round(s["energy"]["joules_p99"], 4),
                "violations": int(s["energy"]["violations"]) if aware
                              else overruns,
                "staleness_mean": s["staleness"]["mean"],
                "staleness_max": s["staleness"]["max"],
            })
    return rows


# ---------------------------------------------------------------------------
# population-scale fleet-of-fleets federation (fed.fleet)
# ---------------------------------------------------------------------------

def fleet_scale_sweep(
    fleet_counts=(4, 16),
    *,
    k: int = 4,
    rounds: int = 3,
    T: float = 6.0,
    total_samples: int = 40,
    participation: float = 0.5,
    features: int = 64,
    hidden: int = 32,
    seed: int = 0,
    mesh=None,
    train: Dataset | None = None,
    test: Dataset | None = None,
) -> list[dict]:
    """Population-scale rows: one two-tier ``FleetEngine`` run per fleet
    count F — F fleets x ``k`` learners on sharded fleet tensors, FedAST
    partial participation at ``participation``, a compact
    ``[features, hidden, 10]`` model so the per-round cost is dominated by
    the fleet machinery rather than one matmul.

    Every fleet trains every round (unsampled fleets keep working on their
    stale pull), so one global round of virtual-time T simulates F x k
    busy learners: the reported ``learners_per_vtu`` is exactly F x k.
    ``mesh=None`` takes ``launch.mesh.host_mesh()`` — a mesh over every
    device the process has ((2, 4) under
    ``XLA_FLAGS=--xla_force_host_platform_device_count=8``, (2, 2) on a
    four-chip TPU host, one device elsewhere). Feeds ``benchmarks/fleet_scale.py``."""
    from repro.fed.fleet import FleetConfig, FleetEngine, build_fleet_problems

    if train is None or test is None:
        train, test = synthetic_mnist(
            6000, n_test=2000, features=features, seed=seed
        )
    params = mlp.init(jax.random.key(seed), layers=[features, hidden, 10])
    cfg = FleetConfig(participation=participation)
    rows: list[dict] = []
    for f in fleet_counts:
        bp = build_fleet_problems(
            int(f), k, T=T, total_samples=total_samples, seed=seed
        )
        eng = FleetEngine(cfg, bp, mlp.loss, params, seed=seed, mesh=mesh)
        t0 = time.time()
        hist = eng.run(
            train, rounds, eval_fn=mlp.accuracy,
            eval_batch=(test.x[:1000], test.y[:1000]),
        )
        wall = time.time() - t0
        learners = int(f) * k
        rows.append({
            "F": int(f),
            "K": k,
            "learners": learners,
            "rounds": rounds,
            "participation": participation,
            "mesh_devices": int(np.prod(list(eng.mesh.shape.values()))),
            "fleet_axes": list(eng.fleet_axes),
            "learners_per_vtu": learners,
            "final_accuracy": float(hist[-1]["accuracy"]),
            "fleet_staleness_max": max(
                r["fleet_staleness_max"] for r in hist
            ),
            "wall_s": round(wall, 3),
            "learner_rounds_per_s": round(
                learners * rounds / max(wall, 1e-9), 1
            ),
        })
    return rows


# ---------------------------------------------------------------------------
# multi-tenant simultaneous training (fed.multimodel)
# ---------------------------------------------------------------------------

def multi_model_sweep(
    totals=(200, 200, 600),
    *,
    k: int = 4,
    T: float = 8.0,
    cycles: int = 8,
    splits=("deficit", "equal"),
    mode: str = "fedasync",
    alpha: float = 0.6,
    lr: float = 0.05,
    share_floor: float = 0.1,
    seed: int = 0,
    train: Dataset | None = None,
    test: Dataset | None = None,
) -> list[dict]:
    """S tenant models time-sharing one fleet, deficit split vs equal
    split (``fed.multimodel.MultiModelEngine``), at equal virtual time.

    The tenants differ only in per-round sample budget (``totals``): the
    LAGGARD (largest total) needs more learner-seconds per aggregation,
    so under the equal split it falls behind in server versions while the
    light tenants spin. The deficit split reads that version gap
    (FedAST-style behind-ness — model-value-free) and shifts each
    learner's time budget toward the laggard; the frontier question is
    the laggard's time-to-accuracy. Each row reports per-model accuracy
    traces, final versions, and the laggard's trace for the
    time-to-accuracy comparison in ``benchmarks/multimodel_bench.py``.

    ``share_floor`` defaults to 0.1: a floored split keeps every
    tenant's slice of the deadline large enough that the deadline-filling
    solver doesn't pile hundreds of local iterations onto a handful of
    samples (tiny ``w`` => tiny ``d`` at the box floor => huge ``tau``,
    which diverges plain GD). ``lr`` is likewise gentler than the
    single-model default for the same reason."""
    from repro.fed.async_engine import AsyncConfig
    from repro.fed.multimodel import MultiModelEngine

    s = len(totals)
    probs = [
        build_problem(k, T, total_samples=int(t), seed=seed) for t in totals
    ]
    if train is None or test is None:
        train, test = synthetic_mnist(
            max(max(totals) * 2, 12_000), seed=seed
        )
    eval_batch = (test.x[:2000], test.y[:2000])
    params = tuple(
        mlp.init(jax.random.key(seed + i)) for i in range(s)
    )
    laggard = int(np.argmax(totals))
    horizon = cycles * T
    rows: list[dict] = []
    for split in splits:
        cfg = AsyncConfig(mode=mode, alpha=alpha, lr=lr, staleness_fn="poly")
        eng = MultiModelEngine(
            cfg, probs, mlp.loss, params, seed=seed, split=split,
            share_floor=share_floor,
        )
        histories = eng.run(
            [train] * s, horizon,
            eval_fns=[mlp.accuracy] * s, eval_batches=[eval_batch] * s,
        )
        traces = [
            [(round(float(r["t"]), 3), round(float(r["accuracy"]), 4))
             for r in h if "accuracy" in r]
            for h in histories
        ]
        rows.append({
            "S": s,
            "K": k,
            "T": T,
            "cycles": cycles,
            "mode": mode,
            "lr": lr,
            "split": split,
            "share_floor": share_floor,
            "totals": [int(t) for t in totals],
            "laggard": laggard,
            "versions": [int(h[-1]["server_version"]) if h else 0
                         for h in histories],
            "final_accuracy": [t[-1][1] if t else 0.0 for t in traces],
            "laggard_trace": traces[laggard],
            "events": sum(len(h) for h in histories),
            "split_weights_seen": [
                [round(float(x), 4) for x in w]
                for w in eng.split_weight_log[:8]
            ],
        })
    return rows


def laggard_time_to_accuracy(rows, target: float | None = None):
    """First virtual time each split's laggard reaches ``target`` accuracy
    (default: 95% of the worst split's laggard final accuracy, so every
    row has a finite crossing). Returns ``{split: t}``."""
    if target is None:
        finals = [r["laggard_trace"][-1][1] for r in rows
                  if r["laggard_trace"]]
        target = 0.95 * min(finals)
    out = {}
    for r in rows:
        t_hit = next(
            (t for t, acc in r["laggard_trace"] if acc >= target), None
        )
        out[r["split"]] = t_hit
    return out, float(target)
