"""Asynchronous MEL orchestrator (paper Sec. II + V).

One global cycle of wall-clock budget ``T``:
  1. allocate (tau_k, d_k) with the chosen scheme (KKT+SAI / numeric / ETA /
     synchronous),
  2. dispatch the global model + per-learner batches,
  3. every learner runs tau_k local updates — implemented as a **masked
     lax.scan to max(tau)**, vmapped over the learner axis, so the whole
     heterogeneous fleet is one XLA program (and the learner axis can be
     sharded over the mesh's data axes for pod-scale fleets),
  4. staleness-aware aggregation (ref [10]) of the returned models.

The simulated wall-clock of a cycle is T by construction (constraint 7b of
the paper: every learner works the full cycle).

Two execution paths:

  * ``run`` / ``run_cycle`` — eager: one host round-trip per global cycle
    (NumPy shard staging -> jit local_train -> aggregate). Supports
    per-cycle re-allocation and arbitrary host eval callbacks.
  * ``run_fused`` (or ``run(..., fused=True)``) — fast path: shards for
    ALL cycles are drawn up front as one (C, K, d_max) block of sample
    indices (``SampleIndex``), the training set goes to the device once,
    and gather -> local_train -> staleness-weighted aggregation runs as a
    single jitted ``lax.scan`` over global cycles with the carried params
    buffer donated. The aggregation contraction goes through
    ``kernels.ops.fed_agg`` (Pallas on TPU via ``use_pallas=True``).

Adaptive in-scan reallocation: with ``reallocate=True`` (both paths) and a
``CapacityDrift`` model, the allocation program is re-solved EVERY cycle
on that cycle's drifted (c2, c1, c0) capacities. On the fused path the
re-solve happens *inside* the scan — ``core.solver_batched.batched_policy``
(KKT water-filling + SAI, equal-task eta, or masked PGD, per
``MELConfig.scheme``) runs on the traced (1, K) capacity state each cycle,
so a fleet-scale run with per-cycle reallocation is still ONE XLA program
with zero per-cycle host round-trips. The drifted capacity rows themselves
are generated inside the scan — ``CapacityDrift.factors_at`` on the traced
cycle index — so no host-precomputed coefficient path enters the program
(the eager twin still materializes ``coefficient_path`` host-side; the two
contexts agree on the f32 factors to 1 ULP and on the resulting integer
tau/d exactly, pinned by the equivalence tests). Shards are pre-drawn flat (the
partitioner's rng consumption depends only on the constant per-cycle
total) and split by the traced d inside the scan, so for the same seed the
tau/d history and the per-learner shard contents match the eager
reallocation path exactly (allocation math runs in f64 under
``jax.enable_x64``; training stays f32).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Callable, NamedTuple

import numpy as np

import jax
import jax.numpy as jnp

from repro.core import (
    Allocation,
    AllocationProblem,
    CapacityDrift,
    aggregate,
    batched_policy,
    TRACED_POLICIES,
    fedavg_weights,
    solve_eta,
    solve_kkt_energy,
    solve_kkt_sai,
    solve_pgd_jax,
    solve_slsqp,
    solve_synchronous,
    staleness_weights,
)
from repro.core.availability import has_availability
from repro.core.solver_batched import apply_active_mask
from repro.core.staleness import avg_staleness, max_staleness
from repro.core.time_model import is_state_coupled
from repro.data.pipeline import Dataset, FederatedPartitioner
from repro.fed.tracing import span, to_device

__all__ = ["MELConfig", "Orchestrator", "local_train", "local_train_stacked"]

SCHEMES: dict[str, Callable[[AllocationProblem], Allocation]] = {
    "kkt_sai": solve_kkt_sai,
    "kkt_energy": solve_kkt_energy,
    "slsqp": solve_slsqp,
    "pgd": solve_pgd_jax,
    "eta": solve_eta,
    "sync": solve_synchronous,
}

# schemes whose traced policy takes the extra (e2, e1, e0, e_budget) operand
# (with e_budget = +inf the operand is decision-inert, so listing a scheme
# here never changes its energy-blind allocations)
ENERGY_SCHEMES = frozenset({"kkt_energy", "pgd"})


@dataclasses.dataclass(frozen=True)
class MELConfig:
    T: float = 15.0
    total_samples: int = 6000          # d dispatched per cycle
    d_lower_frac: float = 0.25         # d_l = frac * d/K
    d_upper_frac: float = 3.0          # d_u = frac * d/K
    lr: float = 0.1
    scheme: str = "kkt_sai"
    aggregation: str = "staleness"     # staleness | fedavg
    staleness_gamma: float = 1.0


@functools.partial(jax.jit, static_argnames=("max_tau", "loss_fn"))
def local_train_stacked(stacked, x, y, mask, tau, lr, *, max_tau: int, loss_fn):
    """Run tau_k local GD updates on each of K learners, vectorized, where
    every learner starts from its OWN params (leading K axis on each leaf) —
    the general form the event-driven async engine needs, since in-flight
    learners hold different dispatched model versions.

    x: (K, d_max, F); y, mask: (K, d_max); tau: (K,) int32.
    Returns stacked per-learner params (leading K axis).
    """

    def one_learner(params, xk, yk, mk, tau_k):
        batch = {"x": xk, "y": yk, "mask": mk}

        def step(p, i):
            def do(p):
                g = jax.grad(loss_fn)(p, batch)
                return jax.tree_util.tree_map(lambda pi, gi: pi - lr * gi, p, g)

            return jax.lax.cond(i < tau_k, do, lambda p: p, p), None

        p, _ = jax.lax.scan(step, params, jnp.arange(max_tau))
        return p

    with jax.named_scope("local_train"):
        return jax.vmap(one_learner)(stacked, x, y, mask, tau)


def local_train(global_params, x, y, mask, tau, lr, *, max_tau: int, loss_fn):
    """``local_train_stacked`` with every learner starting from the same
    global model (the paper's cycle-gated dispatch)."""
    k = x.shape[0]
    stacked = jax.tree_util.tree_map(
        lambda p: jnp.broadcast_to(p, (k,) + p.shape), global_params
    )
    return local_train_stacked(
        stacked, x, y, mask, tau, lr, max_tau=max_tau, loss_fn=loss_fn
    )


class SampleIndex(NamedTuple):
    """A campaign's shards as sample indices into the training set, which
    the fused programs hold on the device whole and gather from one cycle
    at a time, in place of host-staged shard blocks.

    idx : int32 (C, K, d_max) per-learner slots of a static allocation
        (padded slots hold 0), or (C, total) flat per-cycle draws that the
        reallocating scan splits by its traced d
    x, y : the training set, (N, F) f32 and (N,) int32
    d : (K,) int32 shard sizes of a static allocation, which mask its
        padded slots; None for the flat draws
    """

    idx: jax.Array
    x: jax.Array
    y: jax.Array
    d: jax.Array | None = None


def _device_table(train: Dataset):
    """``train`` as the f32 features and int32 labels the programs read."""
    return np.asarray(train.x, np.float32), np.asarray(train.y, np.int32)


def _stage_shards(shards: "list[Dataset]", d_max: int, feat: int):
    """Zero-pad per-learner shards into (K, d_max, ...) host arrays with a
    validity mask, the eager per-cycle path's operands (the fused programs
    build the same operands on the device from a ``SampleIndex``)."""
    k = len(shards)
    x = np.zeros((k, d_max, feat), np.float32)
    y = np.zeros((k, d_max), np.int32)
    m = np.zeros((k, d_max), np.float32)
    for i, sh in enumerate(shards):
        n = sh.size
        x[i, :n], y[i, :n], m[i, :n] = sh.x, sh.y, 1.0
    return x, y, m


@functools.partial(
    jax.jit,
    static_argnames=("max_tau", "loss_fn", "eval_fn", "use_pallas", "interpret"),
    donate_argnums=(0,),
)
def _fused_cycles(params, shards, tau, weights, lr, eval_x, eval_y, *,
                  max_tau: int, loss_fn, eval_fn, use_pallas: bool,
                  interpret: bool):
    """One XLA program for C global cycles: scan(allocated local_train ->
    fed_agg) with the params carry donated. tau/weights: (K,).

    ``shards`` is a ``SampleIndex`` with (C, K, d_max) slots: each cycle
    gathers its rows from the device-resident training set before its
    local steps, with padded rows and labels zero and the mask from ``d``
    (the eager path's ``_stage_shards`` operands)."""
    from repro.kernels import ops

    valid = jnp.arange(shards.idx.shape[-1]) < shards.d[:, None]
    mask = valid.astype(jnp.float32)

    def batch_of(idx_c):
        # every index is in range: clip only spares the bounds masks
        x = jnp.take(shards.x, idx_c, axis=0, mode="clip")
        y = jnp.take(shards.y, idx_c, axis=0, mode="clip")
        return jnp.where(valid[..., None], x, 0), jnp.where(valid, y, 0), mask

    def one_cycle(p, idx_c):
        x, y, m = batch_of(idx_c)
        k = x.shape[0]
        disp = jax.tree_util.tree_map(
            lambda leaf: jnp.broadcast_to(leaf, (k,) + leaf.shape), p
        )
        new, _ = ops.train_agg_step(
            disp, x, y, m, tau, weights, lr, loss_fn=loss_fn,
            max_tau=max_tau, use_pallas=use_pallas, interpret=interpret,
        )
        with jax.named_scope("eval"):
            acc = (eval_fn(new, eval_x, eval_y) if eval_fn is not None
                   else jnp.float32(0))
        return new, acc

    return jax.lax.scan(one_cycle, params, shards.idx)


def _local_train_dynamic(params, x, y, mask, tau, lr, *, loss_fn):
    """Traced-tau twin of ``local_train``: a ``while_loop`` to the TRACED
    fleet-max tau (so a reallocating scan only pays for the updates each
    cycle actually runs, not a static worst-case bound), with per-learner
    masked updates. The per-step select matches ``local_train``'s vmapped
    ``lax.cond`` numerics exactly, so both produce identical params."""
    k = x.shape[0]
    stacked = jax.tree_util.tree_map(
        lambda p: jnp.broadcast_to(p, (k,) + p.shape), params
    )
    tau_max = jnp.max(tau)

    def one_step(i, pk, xk, yk, mk, tau_k):
        batch = {"x": xk, "y": yk, "mask": mk}
        g = jax.grad(loss_fn)(pk, batch)
        return jax.tree_util.tree_map(
            lambda p, gi: jnp.where(i < tau_k, p - lr * gi, p), pk, g
        )

    def body(state):
        p, i = state
        p = jax.vmap(functools.partial(one_step, i))(p, x, y, mask, tau)
        return p, i + 1

    with jax.named_scope("local_train"):
        p, _ = jax.lax.while_loop(
            lambda s: s[1] < tau_max, body, (stacked, jnp.zeros((), tau.dtype))
        )
    return p


@functools.lru_cache(maxsize=None)
def _jitted_policy(scheme: str):
    """One jitted wrapper per scheme so per-cycle eager re-solves hit the
    same compilation cache (the fused path inlines the identical traced
    policy inside its scan, and ``fed.async_engine`` re-solves through the
    same wrapper at every redispatch)."""
    return jax.jit(batched_policy(scheme))


def policy_problem_args(prob: AllocationProblem):
    """Static (1,)/(1, K) f64 problem tensors for a single-fleet call into a
    ``batched_policy`` — shared by the orchestrator's re-solves and the
    async engine's per-block allocation so all consumers see identical
    values."""
    k = prob.num_learners
    return (
        np.asarray([prob.T], np.float64),
        np.asarray([prob.total_samples], np.int64),
        np.full((1, k), float(prob.d_lower), np.float64),
        np.full((1, k), float(prob.d_upper), np.float64),
        np.ones((1, k), bool),
    )


def policy_energy_args(prob: AllocationProblem):
    """Static (1, K) f64 energy rows ``(e2, e1, e0, e_budget)`` for the
    ``kkt_energy`` traced policy — the problem's attached
    ``EnergyModel``/budget, or the zero-coefficient / infinite-budget
    defaults (under which the policy is decision-identical to
    ``kkt_sai``) when none is attached."""
    rows = prob.energy_rows()
    if rows is None:
        k = prob.num_learners
        z = np.zeros((1, k), np.float64)
        return z, z.copy(), z.copy(), np.full((1, k), np.inf)
    e2, e1, e0, eb = rows
    return (
        np.asarray(e2, np.float64)[None],
        np.asarray(e1, np.float64)[None],
        np.asarray(e0, np.float64)[None],
        np.asarray(eb, np.float64)[None],
    )


def require_standalone_rows(drift, *, remedy: str) -> None:
    """THE shared guard for code paths that need standalone capacity rows
    fixed up front: a state-coupled drift (``QueueDrift``) or an
    availability process has no such rows — they depend on the run state
    (past allocations, who was online) — so every consumer rejects them
    through this one helper with one actionable message. ``remedy`` names
    what the caller should do instead."""
    if drift is None:
        return
    avail = has_availability(drift)
    if not avail and not is_state_coupled(drift):
        return
    kind = "an availability process" if avail else "a state-coupled drift"
    raise TypeError(
        f"{type(drift).__name__} is {kind} and has no standalone "
        f"coefficient path (its rows depend on the run state); {remedy}"
    )


def coefficient_rows(prob: AllocationProblem, drift: CapacityDrift | None,
                     cycles: int):
    """(C, K) f64 capacity rows per global cycle / drift block — drifted
    when a CapacityDrift is attached, else the base coefficients tiled.
    THE shared row source for the orchestrator's eager re-solves and the
    async engine's schedule (their bitwise equivalence depends on it).
    State-coupled drifts (``QueueDrift``) and availability processes have
    no standalone row path — their rows/masks depend on the run state —
    so they are rejected here; callers roll rows and allocations out
    together via ``solve_rows_state_coupled`` /
    ``solve_rows_availability`` / the fused scan instead."""
    tm = prob.time_model
    require_standalone_rows(
        drift,
        remedy="roll rows and allocations out together via "
        "drift.rollout(...), solve_rows_state_coupled(...) or "
        "solve_rows_availability(...)",
    )
    if drift is None:
        tile = lambda a: np.broadcast_to(
            a, (cycles, tm.num_learners)
        ).astype(np.float64)
        return tile(tm.c2), tile(tm.c1), tile(tm.c0)
    return drift.coefficient_path(tm, cycles)


def solve_policy_row(scheme: str, c2r, c1r, c0r, prob: AllocationProblem,
                     *, label: str, active=None, e_budget=None
                     ) -> tuple[np.ndarray, np.ndarray]:
    """One fleet's (tau, d) on a single (K,) capacity row through the
    jitted traced policy, f64 under ``jax.enable_x64`` — THE single-row solve
    shared by the orchestrator's eager per-cycle re-solve and the async
    engine's per-block allocation (the barrier-equivalence guarantee
    depends on both paths using this exact code). Raises ValueError with
    ``label`` naming the infeasible capacity state.

    ``active`` (optional ``(K,)`` bool) masks offline learners out of the
    solve: their slots get the ``BatchedProblems`` padded-slot semantics
    and the sample budget is clipped into the live fleet's box
    (``apply_active_mask``), so tau/d budget flows to online learners.
    An all-offline row short-circuits to zeros without a policy call.

    ``e_budget`` (optional ``(K,)`` joules, ``kkt_energy`` only) tightens
    the problem's static per-learner budget with a per-dispatch one —
    min of the two — so a ``BatteryDrift`` charge state caps what each
    dispatch may spend."""
    policy = _jitted_policy(scheme)
    T1, total1, lo1, hi1, valid1 = policy_problem_args(prob)
    k = prob.num_learners
    energy1 = None
    if scheme in ENERGY_SCHEMES:
        e2r, e1r, e0r, ebr = policy_energy_args(prob)
        if e_budget is not None:
            ebr = np.minimum(ebr, np.asarray(e_budget, np.float64).reshape(1, k))
        energy1 = (e2r, e1r, e0r, ebr)
    elif e_budget is not None:
        raise ValueError(
            f"e_budget needs an energy-aware scheme "
            f"({' | '.join(sorted(ENERGY_SCHEMES))}); scheme {scheme!r} "
            "cannot honor it"
        )
    if active is not None:
        act = np.asarray(active, bool).reshape(1, k)
        if not act.any():
            z = np.zeros(k, np.int64)
            return z, z.copy()
    with span("allocate", k=k), jax.enable_x64(True):
        total_j, lo_j, hi_j, valid_j = (
            jnp.asarray(total1), jnp.asarray(lo1),
            jnp.asarray(hi1), jnp.asarray(valid1),
        )
        if active is not None:
            total_j, lo_j, hi_j, valid_j = apply_active_mask(
                total_j, lo_j, hi_j, valid_j, jnp.asarray(act)
            )
        base_args = (
            jnp.asarray(c2r[None]), jnp.asarray(c1r[None]),
            jnp.asarray(c0r[None]), jnp.asarray(T1), total_j,
            lo_j, hi_j, valid_j,
        )
        if energy1 is not None:
            en_j = tuple(jnp.asarray(e) for e in energy1)
            tau, d, ok = policy(*base_args, en_j)
        else:
            tau, d, ok = policy(*base_args)
        tau = np.asarray(tau[0]); d = np.asarray(d[0]); ok = bool(ok[0])
    if not ok:
        sub = (
            f"; {int(np.asarray(active, bool).sum())}/{k} learners online"
            if active is not None else ""
        )
        raise ValueError(
            "infeasible: even with tau=0 the deadline T cannot absorb "
            f"d samples ({label}{sub})"
        )
    return tau.astype(np.int64), d.astype(np.int64)


def solve_rows_state_coupled(scheme: str, drift, prob: AllocationProblem,
                             cycles: int, *, label: str, lazy: bool = False):
    """Joint host rollout of capacity rows AND allocations for a
    state-coupled drift (``QueueDrift``): cycle by cycle, the drifted row
    is produced from the current drift state, solved through the SAME
    jitted traced policy as every other re-solve path
    (``solve_policy_row``), and the state advanced with the solved
    allocation. Shared by the orchestrator's eager reallocation path and
    the async engine's scheduler so both replay the fused scan's coupled
    trajectory. ``label`` is a format string receiving the cycle index for
    infeasibility errors.

    Returns ``((c2s, c1s, c0s), (taus, ds))``, or with ``lazy=True`` the
    underlying per-cycle iterator (``QueueDrift.rollout_iter``) so the
    caller can interleave work between solves — the eager orchestrator
    uses this to train the feasible prefix before an infeasible cycle
    raises, mirroring the fused scan's in-scan guard."""

    def _solve(c, c2r, c1r, c0r):
        return solve_policy_row(
            scheme, c2r, c1r, c0r, prob, label=label.format(c)
        )

    if lazy:
        return drift.rollout_iter(prob.time_model, cycles, _solve)
    return drift.rollout(prob.time_model, cycles, _solve)


def solve_rows_availability(scheme: str, drift, prob: AllocationProblem,
                            cycles: int, *, label: str):
    """Joint host rollout of capacity rows, allocations AND online masks
    for an availability process: per cycle, the online mask is read from
    the availability state, the (possibly base-drifted or
    backlog-coupled) capacity row materialized, the *masked* allocation
    solved through the SAME jitted traced policy as every other re-solve
    path (``solve_policy_row(active=...)``), and the joint state advanced
    with the solved allocation. Offline learners get tau = d = 0 and the
    budget degrades to the live fleet's box instead of going infeasible;
    all-offline cycles solve to all-zeros. ``label`` is a format string
    receiving the cycle index.

    Returns ``((c2s, c1s, c0s), (taus, ds), masks)`` with shapes
    ``(C, K)`` (masks bool) — the per-cycle numerics mirror
    ``QueueDrift.rollout_iter`` (f64 rows under ``jax.enable_x64``).

    When the drift also exposes ``budget_at`` (a :class:`BatteryDrift`)
    and the scheme is energy-aware, each cycle's solve is additionally
    capped by the current per-learner charge — no dispatched task can
    cost more than its battery holds."""
    tm = prob.time_model
    k = tm.num_learners
    budgeted = scheme in ENERGY_SCHEMES and hasattr(drift, "budget_at")
    c2s = np.empty((cycles, k)); c1s = np.empty((cycles, k))
    c0s = np.empty((cycles, k))
    taus = np.zeros((cycles, k), np.int64)
    ds = np.zeros((cycles, k), np.int64)
    masks = np.zeros((cycles, k), bool)
    state = drift.state_init(k)
    for c in range(cycles):
        mask = np.asarray(drift.online_at(c, k, state))
        with jax.enable_x64(True):
            clock, rate = drift.factors_at(c, k, state)
            clock = np.asarray(clock, np.float64)
            rate = np.asarray(rate, np.float64)
        c2r = tm.c2 / clock
        c1r = tm.c1 / rate
        c0r = tm.c0 / rate
        e_budget = drift.budget_at(c, k, state) if budgeted else None
        tau, d = solve_policy_row(
            scheme, c2r, c1r, c0r, prob, label=label.format(c), active=mask,
            e_budget=e_budget,
        )
        state = drift.state_update(c, state, jnp.asarray(tau), jnp.asarray(d))
        masks[c] = mask
        c2s[c], c1s[c], c0s[c] = c2r, c1r, c0r
        taus[c], ds[c] = tau, d
    return (c2s, c1s, c0s), (taus, ds), masks


def _weights_traced(tau, d, *, aggregation: str, gamma):
    """Traced twin of staleness_weights / fedavg_weights (f64 in, f32 out
    matches the eager numpy arithmetic followed by aggregate's cast)."""
    tau_f = tau.astype(jnp.float64)
    d_f = d.astype(jnp.float64)
    if aggregation == "staleness":
        w = d_f / (1.0 + gamma * (jnp.max(tau_f) - tau_f))
    else:
        w = d_f
    return (w / w.sum()).astype(jnp.float32)


@functools.partial(
    jax.jit,
    static_argnames=("d_cap", "loss_fn", "eval_fn", "policy",
                     "aggregation", "drift", "use_pallas", "interpret"),
    donate_argnums=(0,),
)
def _fused_realloc_cycles(params, state0, shards, c2b, c1b, c0b, T1, total1,
                          lo1, hi1, valid1, energy1, gamma, lr, eval_x,
                          eval_y, *, d_cap: int, loss_fn, eval_fn, policy,
                          aggregation: str, drift, use_pallas: bool,
                          interpret: bool):
    """One XLA program for C global cycles WITH per-cycle reallocation:
    scan(drift capacities at the traced cycle index/state -> policy-solve
    -> in-scan feasibility guard -> shard split by traced d -> dynamic
    local_train -> fed_agg).

    Arguments
    ---------
    params : model pytree (the scan carry; this buffer is donated)
    state0 : (K,) f32 drift state for a state-coupled ``drift``
        (``QueueDrift.state_init``), else a (0,) placeholder
    shards : a ``SampleIndex`` of (C, total) flat per-cycle draws into
        the device-resident training set
    c2b, c1b, c0b : (1, K) f64 BASE capacity rows — per-cycle drifted rows
        are generated INSIDE the scan by ``drift.factors_at`` on the
        traced cycle index (and, for a state-coupled drift, the carried
        state), so no host-precomputed coefficient path enters the
        program; ``drift=None`` runs the static rows as-is
    T1, total1 : (1,); lo1/hi1/valid1 : (1, K) — the policy problem args
    energy1 : None for an energy-blind ``policy``, else the (1, K) f64
        ``(e2, e1, e0, e_budget)`` operand the ``kkt_energy`` policy takes
        (None-ness is pytree structure, so the branch resolves at trace
        time)

    Feasibility is guarded IN-SCAN: a cycle whose capacity state cannot
    absorb the sample budget latches a ``dead`` flag; that cycle and every
    later one pass the params (and drift state) through untouched, so the
    scan never trains through a neutralized allocation. The per-cycle
    ``feas`` flags are returned for the host to raise on.

    Must run under ``jax.enable_x64`` so the allocation math stays f64 while
    training stays f32 (exogenous drift draws are f32-pinned either way,
    so the traced rows track ``CapacityDrift.coefficient_path`` to 1 f32
    ULP — and ``QueueDrift.rollout`` bitwise — and yield the same integer
    allocations).

    Returns ``((params, state, dead), (accs, taus, ds, feas))`` with
    per-cycle stacked outputs."""
    from repro.kernels import ops

    k = c2b.shape[1]
    state_coupled = is_state_coupled(drift)
    cycles, total = shards.idx.shape

    def one_cycle(carry, inp):
        p, qstate, dead = carry
        flat_idx, cyc = inp
        if drift is None:
            c2, c1, c0 = c2b, c1b, c0b
        else:
            if state_coupled:
                clock, rate = drift.factors_at(cyc, k, qstate)
            else:
                clock, rate = drift.factors_at(cyc, k)
            c2 = c2b / clock.astype(c2b.dtype)[None]
            c1 = c1b / rate.astype(c1b.dtype)[None]
            c0 = c0b / rate.astype(c0b.dtype)[None]
        energy = () if energy1 is None else (energy1,)
        with jax.named_scope("policy_solve"):
            tau_b, d_b, feas_b = policy(
                c2, c1, c0, T1, total1, lo1, hi1, valid1, *energy
            )
        tau, d, feas = tau_b[0], d_b[0], feas_b[0]
        ok = feas & jnp.logical_not(dead)

        def do_cycle(p):
            w = _weights_traced(tau, d, aggregation=aggregation, gamma=gamma)
            # split the flat draw into per-learner shards by the traced d —
            # identical contents to the eager path's contiguous slicing
            off = jnp.cumsum(d) - d
            j = jnp.arange(d_cap, dtype=d.dtype)
            gidx = off[:, None] + j[None, :]
            m = j[None, :] < d[:, None]
            safe = jnp.clip(gidx, 0, total - 1)
            rows = jnp.take(flat_idx, safe, axis=0)          # (K, d_cap)
            # every row is in range: clip only spares the bounds masks
            x = jnp.take(shards.x, rows, axis=0, mode="clip")  # (K, d_cap, F)
            y = jnp.take(shards.y, rows, axis=0, mode="clip")  # (K, d_cap)

            if use_pallas:
                # megakernel path: the in-kernel fori_loop bounds itself
                # by the traced max(tau), so no static max_tau is needed
                disp = jax.tree_util.tree_map(
                    lambda leaf: jnp.broadcast_to(leaf, (k,) + leaf.shape), p
                )
                new, _ = ops.train_agg_step(
                    disp, x, y, m.astype(jnp.float32), tau, w, lr,
                    loss_fn=loss_fn, use_pallas=True, interpret=interpret,
                )
            else:
                locals_ = _local_train_dynamic(
                    p, x, y, m.astype(jnp.float32), tau, lr, loss_fn=loss_fn,
                )
                new = jax.tree_util.tree_map(
                    lambda leaf: ops.fed_agg(leaf, w), locals_
                )
            with jax.named_scope("eval"):
                acc = (eval_fn(new, eval_x, eval_y).astype(jnp.float32)
                       if eval_fn is not None else jnp.float32(0))
            return new, acc

        def skip_cycle(p):
            return p, jnp.float32(0)

        p_new, acc = jax.lax.cond(ok, do_cycle, skip_cycle, p)
        if state_coupled:
            q_new = drift.state_update(cyc, qstate, tau, d)
            qstate = jnp.where(ok, q_new, qstate)
        return (p_new, qstate, dead | ~feas), (acc, tau, d, feas)

    carry0 = (params, state0, jnp.zeros((), bool))
    return jax.lax.scan(one_cycle, carry0, (shards.idx, jnp.arange(cycles)))


class Orchestrator:
    def __init__(
        self,
        mel: MELConfig,
        problem: AllocationProblem,
        loss_fn,
        init_params,
        *,
        seed: int = 0,
        drift: CapacityDrift | None = None,
    ):
        self.mel = mel
        self.problem = problem
        self.loss_fn = loss_fn
        self.params = init_params
        self.rng = np.random.default_rng(seed)
        if has_availability(drift):
            # the cycle-gated orchestrator has no offline semantics (every
            # learner participates in every barrier round by construction)
            raise TypeError(
                f"{type(drift).__name__} models client availability; the "
                "cycle-gated Orchestrator has no offline semantics — run "
                "churn scenarios through fed.async_engine.AsyncFedEngine"
            )
        self.drift = drift
        with span("allocate", k=problem.num_learners):
            self.allocation = SCHEMES[mel.scheme](problem)

    # -- time-varying capacities --------------------------------------------
    def _coefficient_path(self, cycles: int):
        """(C, K) f64 capacity rows — drifted when a CapacityDrift is
        attached, else the base coefficients tiled (static capacities)."""
        return coefficient_rows(self.problem, self.drift, cycles)

    def _policy_args(self):
        """Static (1,)/(1, K) f64 problem tensors shared by every per-cycle
        re-solve (eager and in-scan paths consume identical values)."""
        return policy_problem_args(self.problem)

    def _reallocate_cycle(self, coeff_path, c: int) -> Allocation:
        """Eager per-cycle re-solve on cycle c's capacity row (drifted or
        tiled-static), through the same traced policy the fused scan
        inlines (bitwise-identical tau/d between the two paths under
        x64)."""
        c2s, c1s, c0s = coeff_path
        tau, d = solve_policy_row(
            self.mel.scheme, c2s[c], c1s[c], c0s[c], self.problem,
            label=f"drifted capacities at cycle {c}",
        )
        return Allocation(tau=tau, d=d, method=f"{self.mel.scheme}_drift")

    # -- one global cycle ---------------------------------------------------
    def run_cycle(self, shards: list[Dataset]) -> dict:
        alloc = self.allocation
        tau = np.asarray(alloc.tau)
        d = np.asarray(alloc.d)
        d_max = int(d.max())
        feat = shards[0].x.shape[1]
        x, y, m = _stage_shards(shards, d_max, feat)

        max_tau = max(int(tau.max()), 1)
        locals_ = local_train(
            self.params, jnp.asarray(x), jnp.asarray(y), jnp.asarray(m),
            jnp.asarray(tau), jnp.asarray(self.mel.lr, jnp.float32),
            max_tau=max_tau, loss_fn=self.loss_fn,
        )
        if self.mel.aggregation == "staleness":
            w = staleness_weights(tau, d, gamma=self.mel.staleness_gamma)
        else:
            w = fedavg_weights(d)
        self.params = aggregate(locals_, jnp.asarray(w))
        return {
            "max_staleness": max_staleness(tau),
            "avg_staleness": avg_staleness(tau),
            "tau": tau.copy(),
            "d": d.copy(),
            "wall_clock_s": self.mel.T,
        }

    # -- full run -------------------------------------------------------------
    def run(
        self,
        train: Dataset,
        cycles: int,
        *,
        eval_fn=None,
        reallocate: bool = False,
        fused: bool = False,
        eval_batch=None,
        use_pallas: bool = False,
        interpret: bool = False,
    ) -> list[dict]:
        if fused:
            return self.run_fused(
                train, cycles, eval_fn=eval_fn, eval_batch=eval_batch,
                use_pallas=use_pallas, interpret=interpret,
                reallocate=reallocate,
            )
        if self.drift is not None and not reallocate:
            import warnings

            # a state-coupled drift cannot even be *simulated* statically
            # (its rows need the dispatched allocations) — same shared
            # rejection as coefficient_rows, not a silent base-capacity run
            require_standalone_rows(
                self.drift,
                remedy="run with reallocate=True so rows and allocations "
                "roll out together",
            )
            warnings.warn(
                "a CapacityDrift is attached but reallocate=False: the run "
                "simulates the BASE capacities and the drift is ignored "
                "(static-under-drift staleness analysis lives in "
                "fed.simulation.drift_staleness_sweep)", stacklevel=2,
            )
        part = FederatedPartitioner(train, seed=int(self.rng.integers(2**31)))
        # reallocate routes through the traced policy whenever the scheme
        # has one (same solver the fused scan inlines -> exact-match twin);
        # schemes without a policy (slsqp, sync) keep the legacy per-problem
        # re-solve, which only reacts to drift-free problem changes.
        coeff_path = None
        rollout = None
        if (reallocate and is_state_coupled(self.drift)
                and self.mel.scheme not in TRACED_POLICIES):
            # the legacy per-problem re-solve below cannot see drifted
            # capacities at all: silently simulating static capacities
            # would mislabel the run (the async engine and
            # coefficient_rows reject this configuration too)
            raise ValueError(
                "state-coupled drift needs a traced policy scheme "
                f"({' | '.join(TRACED_POLICIES)}); scheme "
                f"{self.mel.scheme!r} has none"
            )
        if reallocate and self.mel.scheme in TRACED_POLICIES:
            if is_state_coupled(self.drift):
                # rows depend on the allocations: roll both out together
                # (the host twin of the fused scan's coupled carry).
                # Lazy: each cycle solves right before it trains, so an
                # infeasible cycle raises AFTER the feasible prefix ran —
                # the same params-state contract as the fused in-scan
                # guard.
                rollout = solve_rows_state_coupled(
                    self.mel.scheme, self.drift, self.problem, cycles,
                    label="drifted capacities at cycle {}", lazy=True,
                )
            else:
                coeff_path = self._coefficient_path(cycles)
        history = []
        for c in range(cycles):
            if rollout is not None:
                _, _, _, tau_c, d_c = next(rollout)
                self.allocation = Allocation(
                    tau=tau_c, d=d_c, method=f"{self.mel.scheme}_drift",
                )
            elif coeff_path is not None:
                self.allocation = self._reallocate_cycle(coeff_path, c)
            elif reallocate and c:
                self.allocation = SCHEMES[self.mel.scheme](self.problem)
            shards = part.draw(self.allocation.d)
            rec = self.run_cycle(shards)
            rec["cycle"] = c
            rec["elapsed_s"] = (c + 1) * self.mel.T
            if eval_fn is not None:
                rec["accuracy"] = float(eval_fn(self.params))
            history.append(rec)
        return history

    # -- fused fast path ------------------------------------------------------
    def run_fused(
        self,
        train: Dataset,
        cycles: int,
        *,
        eval_fn=None,
        eval_batch=None,
        use_pallas: bool = False,
        interpret: bool = False,
        reallocate: bool = False,
    ) -> list[dict]:
        """Fused scan-over-cycles twin of ``run``: same shard draws, same
        allocation, one jitted lax.scan instead of C host round-trips.

        Parameters
        ----------
        train : Dataset to draw per-cycle shards from (identical rng
            consumption to the eager path for the same engine seed). It
            is copied to the device once per call; the host stages each
            cycle's sample indices and the program gathers the shards.
        cycles : number of global cycles C to scan over.
        eval_fn : optional jit-traceable ``(params, x, y) -> scalar``
            (e.g. ``mlp.accuracy``), evaluated inside the scan each cycle
            on ``eval_batch``; None skips per-cycle eval.
        eval_batch : ``(x, y)`` arrays; required with ``eval_fn``.
        use_pallas, interpret : route the whole per-cycle train+aggregate
            body through the ``ops.train_agg_step`` Pallas megakernel
            (``interpret=True`` emulates it on CPU); the default runs the
            unfused ``local_train_stacked`` + ``fed_agg`` composition.
        reallocate : re-solve the allocation INSIDE the scan each cycle on
            that cycle's capacity state via the traced
            ``batched_policy(mel.scheme)`` — still one XLA program, zero
            per-cycle host round-trips. With a ``CapacityDrift`` the rows
            are generated in-scan from ``factors_at`` on the traced cycle
            index; with a state-coupled ``QueueDrift`` additionally from
            the drift state carried through the scan (no host coefficient
            path enters the program in either case). The tau/d history and
            shard contents reproduce the eager ``run(reallocate=True)``
            path exactly for the same seed. Feasibility is guarded
            in-scan: an infeasible cycle stops all further updates and the
            call raises ValueError naming it, with ``self.params`` holding
            the state trained through the feasible prefix.

        Returns
        -------
        One history dict per cycle (tau, d, staleness metrics, elapsed
        virtual time, and ``accuracy`` when ``eval_fn`` is given) —
        the same rows the eager ``run`` produces.
        """
        if reallocate:
            return self._run_fused_realloc(
                train, cycles, eval_fn=eval_fn, eval_batch=eval_batch,
                use_pallas=use_pallas, interpret=interpret,
            )
        if self.drift is not None:
            import warnings

            require_standalone_rows(
                self.drift,
                remedy="run with reallocate=True so rows and allocations "
                "roll out together",
            )
            warnings.warn(
                "a CapacityDrift is attached but reallocate=False: the run "
                "simulates the BASE capacities and the drift is ignored "
                "(static-under-drift staleness analysis lives in "
                "fed.simulation.drift_staleness_sweep)", stacklevel=2,
            )
        alloc = self.allocation
        tau = np.asarray(alloc.tau)
        d = np.asarray(alloc.d)
        k = len(d)
        d_max = int(d.max())

        # identical shard sequence to the eager path (same rng consumption)
        part = FederatedPartitioner(train, seed=int(self.rng.integers(2**31)))
        # row-major fill of the valid slots is part.draw's split by d
        slots = np.arange(d_max) < d[:, None]
        with span("stage"):
            idx = np.zeros((cycles, k, d_max), np.int32)
        for c in range(cycles):
            with span("stage", bytes=idx[c].nbytes):
                with span("draw"):
                    idx[c][slots] = part.draw_indices(int(d.sum()))
        shards = SampleIndex(idx, *_device_table(train), d.astype(np.int32))

        if self.mel.aggregation == "staleness":
            w = staleness_weights(tau, d, gamma=self.mel.staleness_gamma)
        else:
            w = fedavg_weights(d)

        if eval_fn is not None and eval_batch is None:
            raise ValueError("run_fused needs eval_batch=(x, y) with eval_fn")
        ex, ey = eval_batch if eval_fn is not None else (None, None)
        shards_d, tau_d, w_d, lr_d, ex_d, ey_d = to_device(
            shards, tau, w, np.asarray(self.mel.lr, np.float32), ex, ey)

        max_tau = max(int(tau.max()), 1)
        with span("dispatch"):
            self.params, accs = _fused_cycles(
                self.params, shards_d, tau_d, w_d, lr_d, ex_d, ey_d,
                max_tau=max_tau, loss_fn=self.loss_fn, eval_fn=eval_fn,
                use_pallas=use_pallas, interpret=interpret,
            )
        with span("wait"):
            accs = np.asarray(accs)

        with span("history"):
            history = []
            for c in range(cycles):
                rec = {
                    "max_staleness": max_staleness(tau),
                    "avg_staleness": avg_staleness(tau),
                    "tau": tau.copy(),
                    "d": d.copy(),
                    "wall_clock_s": self.mel.T,
                    "cycle": c,
                    "elapsed_s": (c + 1) * self.mel.T,
                }
                if eval_fn is not None:
                    rec["accuracy"] = float(accs[c])
                history.append(rec)
        return history

    # -- fused fast path with in-scan reallocation ----------------------------
    def _run_fused_realloc(
        self,
        train: Dataset,
        cycles: int,
        *,
        eval_fn=None,
        eval_batch=None,
        use_pallas: bool = False,
        interpret: bool = False,
    ) -> list[dict]:
        prob = self.problem
        policy = batched_policy(self.mel.scheme)  # raises for slsqp/sync
        if self.mel.aggregation not in ("staleness", "fedavg"):
            raise ValueError(f"unknown aggregation {self.mel.aggregation!r}")
        total = prob.total_samples
        T1, total1, lo1, hi1, valid1 = self._policy_args()
        energy1 = (policy_energy_args(prob)
                   if self.mel.scheme in ENERGY_SCHEMES else None)
        tm = prob.time_model
        c2b = np.asarray(tm.c2[None], np.float64)
        c1b = np.asarray(tm.c1[None], np.float64)
        c0b = np.asarray(tm.c0[None], np.float64)

        # Feasibility is guarded IN-SCAN (see _fused_realloc_cycles): an
        # infeasible cycle latches the scan dead so no training runs on a
        # neutralized allocation, and the host raises from the returned
        # flags below. No host coefficient path enters the fused route at
        # all — the scan regenerates every row from ``factors_at`` on the
        # traced cycle index (and the carried state for a state-coupled
        # drift, which a host pre-check could not replay).

        # d_k <= d_upper bounds the shard split width (tau needs no static
        # bound: the dynamic trainer while-loops to each cycle's traced max)
        d_cap = int(prob.d_upper)

        # identical rng consumption to the eager path: one flat draw of the
        # (constant) per-cycle total; the split by d happens in the scan
        part = FederatedPartitioner(train, seed=int(self.rng.integers(2**31)))
        with span("stage"):
            idx = np.zeros((cycles, total), np.int32)
        for c in range(cycles):
            with span("stage", bytes=idx[c].nbytes):
                with span("draw"):
                    idx[c] = part.draw_indices(total)
        shards = SampleIndex(idx, *_device_table(train))

        if eval_fn is not None and eval_batch is None:
            raise ValueError("run_fused needs eval_batch=(x, y) with eval_fn")
        # the eval batch goes over before x64 is on, so it stays f32
        ex_d, ey_d = to_device(*(eval_batch if eval_fn is not None
                                 else (None, None)))

        state0 = (self.drift.state_init(len(tm.c2))
                  if is_state_coupled(self.drift)
                  else jnp.zeros((0,), jnp.float32))
        with jax.enable_x64(True):
            (shards_d, c2_d, c1_d, c0_d, T1_d, total1_d, lo1_d, hi1_d,
             valid1_d, gamma_d, lr_d, *energy_d) = to_device(
                shards, c2b, c1b, c0b, T1, total1, lo1, hi1, valid1,
                np.asarray(self.mel.staleness_gamma, np.float64),
                np.asarray(self.mel.lr, np.float32), *(energy1 or ()))
            with span("dispatch"):
                (params, _, _), (accs, taus, ds, feas) = _fused_realloc_cycles(
                    self.params, state0, shards_d, c2_d, c1_d, c0_d, T1_d,
                    total1_d, lo1_d, hi1_d, valid1_d,
                    tuple(energy_d) if energy1 is not None else None,
                    gamma_d, lr_d, ex_d, ey_d,
                    d_cap=d_cap, loss_fn=self.loss_fn,
                    eval_fn=eval_fn, policy=policy,
                    aggregation=self.mel.aggregation, drift=self.drift,
                    use_pallas=use_pallas, interpret=interpret,
                )
            # the input params buffer was donated: re-point at the scan
            # carry BEFORE any raise so the orchestrator stays usable (the
            # in-scan dead-latch guarantees it holds the params trained
            # through the feasible prefix only)
            self.params = params
            with span("wait"):
                accs, taus, ds, feas = (np.asarray(a)
                                        for a in (accs, taus, ds, feas))
        if not feas.all():
            bad = int(np.flatnonzero(~feas)[0])
            raise ValueError(
                "infeasible: even with tau=0 the deadline T cannot absorb "
                f"d samples (drifted capacities at cycle {bad})"
            )

        with span("history"):
            history = []
            for c in range(cycles):
                tau_c = taus[c].astype(np.int64)
                d_c = ds[c].astype(np.int64)
                rec = {
                    "max_staleness": max_staleness(tau_c),
                    "avg_staleness": avg_staleness(tau_c),
                    "tau": tau_c,
                    "d": d_c,
                    "wall_clock_s": self.mel.T,
                    "cycle": c,
                    "elapsed_s": (c + 1) * self.mel.T,
                }
                if eval_fn is not None:
                    rec["accuracy"] = float(accs[c])
                history.append(rec)
        self.allocation = Allocation(
            tau=history[-1]["tau"], d=history[-1]["d"],
            method=f"{self.mel.scheme}_drift",
        )
        return history
