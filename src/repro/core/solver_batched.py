"""Fleet-scale batched allocation engine: device-resident KKT water-filling
bisection + integer SAI repair for B allocation problems at once.

``solver_kkt`` solves one ``AllocationProblem`` with a NumPy bisection and a
Python greedy-repair loop — fine for a single fleet, hopeless when a
scheduling tick must re-solve (tau_k, d_k) for thousands of fleets (FedAST /
FedAsync-style servers re-allocate continuously as models return). This
module turns that O(B)-Python-solves path into **one XLA program**:

  * ``BatchedProblems`` — the shared (B, K) problem layout: coefficient
    tensors ``c2/c1/c0`` and per-learner bounds ``d_lo/d_hi`` of shape
    (B, K), per-fleet scalars ``T``/``total`` of shape (B,), and a
    ``valid`` mask so fleets of different sizes batch together (padded
    learner slots carry ``d_lo = d_hi = 0`` and never receive work).
  * ``solve_kkt_batched`` — lockstep bisection on the shared water level
    tau* across all B fleets (the inner residual
    ``sum_k clip((T - c0)/(c2 tau* + c1), d_l, d_u) - d`` is one
    ``kernels.ops.waterfill_residual`` call per step, with a Pallas TPU
    kernel behind ``use_pallas=True``), followed by a vmapped
    largest-remainder integerization and a vmapped SAI greedy repair, both
    as bounded ``lax.while_loop``s.
  * ``solve_eta_batched`` — the equal-task baseline in the same layout.
  * ``batched_max_staleness`` / ``batched_avg_staleness`` /
    ``batched_summary`` — (B,)-vectorized fleet metrics.

Numerical contract: with ``x64=True`` (default) every branch of the
bisection, the stable-sort tie-breaks of the largest-remainder rounding and
the greedy SAI moves replicate ``solver_kkt.solve`` decision-for-decision,
so per-problem outputs match the NumPy path exactly up to reduction-order
ULP noise in the residual sum (which can shift tau* within the bisection
tolerance and, extremely rarely, move one sample between two learners tied
at the same remainder — the documented tie-break tolerance).
``x64=False`` is the float32 device-resident fast path for hardware
without f64.
"""

from __future__ import annotations

import contextlib
import dataclasses
import functools

import numpy as np

import jax
import jax.numpy as jnp

from repro.core.allocation import Allocation, AllocationProblem
from repro.core.time_model import TimeModel

__all__ = [
    "BatchedProblems",
    "BatchedAllocation",
    "TRACED_POLICIES",
    "SPLIT_POLICIES",
    "batched_policy",
    "cross_model_weights",
    "cross_model_split",
    "multimodel_policy",
    "solve_kkt_batched",
    "solve_eta_batched",
    "solve_energy_batched",
    "batched_max_staleness",
    "batched_avg_staleness",
    "batched_summary",
    "apply_active_mask",
    "apply_energy_mask",
    "apply_sampling_mask",
]

_INT_SENTINEL = 2**31 - 1


# ---------------------------------------------------------------------------
# problem / solution containers
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class BatchedProblems:
    """B allocation problems in one (B, K) tensor layout (K = widest fleet).

    ``d_lo``/``d_hi`` are per-learner so heterogeneous fleets and padding
    share one code path; for real problems every valid learner of fleet b
    carries that problem's scalar (d_lower, d_upper).

    Mask semantics for padded slots (``valid[b, k] == False`` — learner k
    does not exist in fleet b): every solver in this module and
    ``solver_numeric.solve_pgd_batched`` honors the same contract —

      * padded slots carry ``d_lo = d_hi = 0`` so any bound clip pins them
        to zero work; ``from_problems`` builds them that way and hand-built
        structs must too (a padded slot with a non-zero box is undefined);
      * coefficients of padded slots are ignored (``from_problems`` writes
        c2 = c1 = 1, c0 = 0 so divides stay finite);
      * solver outputs carry ``tau = d = 0`` in padded slots, and padded
        slots never enter staleness objectives/metrics or the sum
        constraint (sum_k d_k = total ranges over valid slots only, which
        the zero box enforces).

    The optional energy rows ``e2/e1/e0`` + per-learner budgets
    ``e_budget`` (arXiv 2012.00143; see ``core/energy.py``) default to
    None — the energy-blind layout every pre-energy call site builds.
    ``energy_rows()`` materializes the zero-coefficient / infinite-budget
    rows in that case, under which ``kkt_energy`` is decision-identical
    to ``kkt_sai``.
    """

    c2: np.ndarray        # (B, K)
    c1: np.ndarray        # (B, K)
    c0: np.ndarray        # (B, K)
    T: np.ndarray         # (B,)
    total: np.ndarray     # (B,) int
    d_lo: np.ndarray      # (B, K)
    d_hi: np.ndarray      # (B, K)
    valid: np.ndarray     # (B, K) bool
    e2: np.ndarray | None = None        # (B, K) optional energy rows
    e1: np.ndarray | None = None        # (B, K)
    e0: np.ndarray | None = None        # (B, K)
    e_budget: np.ndarray | None = None  # (B, K) joules, +inf = unconstrained

    @property
    def num_problems(self) -> int:
        return int(self.c2.shape[0])

    @property
    def max_learners(self) -> int:
        return int(self.c2.shape[1])

    @property
    def has_energy(self) -> bool:
        return self.e2 is not None

    def energy_rows(self) -> tuple[np.ndarray, np.ndarray, np.ndarray, np.ndarray]:
        """(e2, e1, e0, e_budget) float64 rows; zero coefficients and +inf
        budgets when the struct carries no energy model (the regime where
        ``kkt_energy`` reproduces ``kkt_sai``)."""
        b, k = self.c2.shape
        if self.e2 is None:
            z = np.zeros((b, k))
            return z, z.copy(), z.copy(), np.full((b, k), np.inf)
        eb = (np.full((b, k), np.inf) if self.e_budget is None
              else np.asarray(self.e_budget, np.float64))
        return (np.asarray(self.e2, np.float64),
                np.asarray(self.e1, np.float64),
                np.asarray(self.e0, np.float64), eb)

    @staticmethod
    def from_problems(problems: "list[AllocationProblem]") -> "BatchedProblems":
        b = len(problems)
        k = max(p.num_learners for p in problems)
        c2 = np.ones((b, k)); c1 = np.ones((b, k)); c0 = np.zeros((b, k))
        d_lo = np.zeros((b, k)); d_hi = np.zeros((b, k))
        valid = np.zeros((b, k), bool)
        T = np.zeros(b); total = np.zeros(b, np.int64)
        any_energy = any(p.energy is not None for p in problems)
        if any_energy:
            # padded slots: zero cost, infinite budget (never binding)
            e2 = np.zeros((b, k)); e1 = np.zeros((b, k)); e0 = np.zeros((b, k))
            eb = np.full((b, k), np.inf)
        for i, p in enumerate(problems):
            n = p.num_learners
            tm = p.time_model
            c2[i, :n], c1[i, :n], c0[i, :n] = tm.c2, tm.c1, tm.c0
            d_lo[i, :n] = p.d_lower
            d_hi[i, :n] = p.d_upper
            valid[i, :n] = True
            T[i] = p.T
            total[i] = p.total_samples
            if any_energy and p.energy is not None:
                er2, er1, er0, erb = p.energy_rows()
                e2[i, :n], e1[i, :n], e0[i, :n] = er2, er1, er0
                eb[i, :n] = erb
        if not any_energy:
            return BatchedProblems(c2, c1, c0, T, total, d_lo, d_hi, valid)
        return BatchedProblems(c2, c1, c0, T, total, d_lo, d_hi, valid,
                               e2, e1, e0, eb)

    def problem(self, i: int) -> AllocationProblem:
        """Reconstruct the i-th (unpadded) AllocationProblem."""
        from repro.core.energy import EnergyModel

        v = self.valid[i]
        tm = TimeModel(c2=self.c2[i, v], c1=self.c1[i, v], c0=self.c0[i, v])
        energy = e_budget = None
        if self.has_energy:
            energy = EnergyModel(
                e2=self.e2[i, v], e1=self.e1[i, v], e0=self.e0[i, v]
            )
            if self.e_budget is not None:
                e_budget = self.e_budget[i, v]
        return AllocationProblem(
            time_model=tm,
            T=float(self.T[i]),
            total_samples=int(self.total[i]),
            d_lower=int(round(float(self.d_lo[i, v].min()))),
            d_upper=int(round(float(self.d_hi[i, v].max()))),
            energy=energy,
            e_budget=e_budget,
        )


@dataclasses.dataclass(frozen=True)
class BatchedAllocation:
    """Batched solver output; padded slots hold tau = d = 0."""

    tau: np.ndarray           # (B, K) int
    d: np.ndarray             # (B, K) int
    feasible: np.ndarray      # (B,) bool
    valid: np.ndarray         # (B, K) bool
    method: str = ""
    relaxed_tau: np.ndarray | None = None   # (B, K)
    relaxed_d: np.ndarray | None = None     # (B, K)
    tau_star: np.ndarray | None = None      # (B,)

    @property
    def num_problems(self) -> int:
        return int(self.tau.shape[0])

    def allocation(self, i: int) -> Allocation:
        """Per-problem Allocation (strips padding); raises on infeasible."""
        if not self.feasible[i]:
            raise ValueError(f"problem {i} infeasible: deadline cannot absorb d")
        v = self.valid[i]
        return Allocation(
            tau=self.tau[i, v].astype(np.int64),
            d=self.d[i, v].astype(np.int64),
            method=self.method,
            relaxed_tau=None if self.relaxed_tau is None else self.relaxed_tau[i, v],
            relaxed_d=None if self.relaxed_d is None else self.relaxed_d[i, v],
        )

    def summary(self, bp: BatchedProblems) -> dict:
        return batched_summary(bp, self.tau, self.d)


# ---------------------------------------------------------------------------
# batched metrics
# ---------------------------------------------------------------------------

def batched_max_staleness(tau: np.ndarray, valid: np.ndarray | None = None) -> np.ndarray:
    """(B,) max-pair staleness  max_k tau - min_k tau  over valid learners."""
    tau = np.asarray(tau)
    if valid is None:
        valid = np.ones(tau.shape, bool)
    tmax = np.where(valid, tau, -1).max(axis=1)
    tmin = np.where(valid, tau, _INT_SENTINEL).min(axis=1)
    n = valid.sum(axis=1)
    return np.where(n >= 2, tmax - tmin, 0).astype(np.int64)


def batched_avg_staleness(tau: np.ndarray, valid: np.ndarray | None = None) -> np.ndarray:
    """(B,) mean |tau_k - tau_l| over valid pairs k < l (paper Eq. 13)."""
    tau = np.asarray(tau, dtype=float)
    if valid is None:
        valid = np.ones(tau.shape, bool)
    k = tau.shape[1]
    diff = np.abs(tau[:, :, None] - tau[:, None, :])
    pair = (valid[:, :, None] & valid[:, None, :]) & np.triu(np.ones((k, k), bool), 1)
    n = valid.sum(axis=1)
    denom = n * (n - 1) / 2.0
    return np.where(denom > 0, (diff * pair).sum(axis=(1, 2)) / np.maximum(denom, 1.0), 0.0)


def batched_summary(bp: BatchedProblems, tau: np.ndarray, d: np.ndarray) -> dict:
    """Vectorized twin of ``Allocation.summary``: dict of (B,) arrays."""
    tau = np.asarray(tau); d = np.asarray(d)
    v = bp.valid
    t = bp.c2 * tau * d + bp.c1 * d + bp.c0
    n = np.maximum(v.sum(axis=1), 1)
    return {
        "max_staleness": batched_max_staleness(tau, v),
        "avg_staleness": batched_avg_staleness(tau, v),
        "total_updates": np.where(v, tau * d, 0).sum(axis=1).astype(np.int64),
        "min_tau": np.where(v, tau, _INT_SENTINEL).min(axis=1).astype(np.int64),
        "max_tau": np.where(v, tau, -1).max(axis=1).astype(np.int64),
        "utilization": np.where(v, t / bp.T[:, None], 0.0).sum(axis=1) / n,
    }


# ---------------------------------------------------------------------------
# jit building blocks (all shapes per problem unless noted)
# ---------------------------------------------------------------------------

def _max_tau_of_d(d, c2, c1, c0, T):
    """Largest integer tau with t_k <= T at integer d (TimeModel.max_tau)."""
    df = d.astype(c2.dtype)
    t = jnp.floor((T - c0 - c1 * df) / (c2 * df))
    t = jnp.where(d > 0, t, 0.0)
    return jnp.maximum(t, 0.0).astype(d.dtype)


#: "unbounded tau" sentinel of the energy cap. Finite (``floor(inf) ->
#: int`` is undefined) and exactly representable in float32 — 2**31 - 1
#: would round UP to 2**31 and overflow int32 on the f32 fast path. Far
#: above any deadline-feasible tau, so ``min(time_cap, _TAU_BIG)`` is the
#: time cap whenever the budget does not bind.
_TAU_BIG = 2**30


def _max_tau_energy(d, e2, e1, e0, eb):
    """Largest integer tau with E_k <= eb at integer d — the energy twin
    of ``_max_tau_of_d``. Unbounded where compute is free (e2 = 0) or the
    budget is infinite; 0 where even tau = 0 busts the budget (the
    affordability mask removes such learners before any solve)."""
    df = d.astype(e2.dtype)
    num = eb - e0 - e1 * df
    den = e2 * df
    raw = jnp.where(
        den > 0, num / jnp.where(den > 0, den, 1.0),
        jnp.where(num >= 0, jnp.inf, -1.0),
    )
    t = jnp.floor(raw)
    t = jnp.where(jnp.isfinite(t), t, float(_TAU_BIG))
    t = jnp.where(d > 0, t, 0.0)
    return jnp.maximum(t, 0.0).astype(d.dtype)


def _relaxed_batched(c2, c1, c0, T, total_f, d_lo, d_hi, *, tol, max_iter,
                     use_pallas, interpret):
    """Lockstep water-filling bisection over the (B,) batch. Mirrors
    ``solver_kkt.solve_relaxed`` branch-for-branch per problem."""
    from repro.kernels import ops

    def resid(tau_star):
        return ops.waterfill_residual(
            tau_star, c2, c1, c0, T, d_lo, d_hi, total_f,
            use_pallas=use_pallas, interpret=interpret,
        )

    b = c2.shape[0]
    zero = jnp.zeros((b,), c2.dtype)
    feasible = resid(zero) >= -1e-9

    # grow hi per problem until the absorbed data drops below total
    def gcond(state):
        _, it, r = state
        return jnp.any(r > 0) & (it < 200)

    def gbody(state):
        hi, it, r = state
        hi = jnp.where(r > 0, hi * 2.0, hi)
        return hi, it + 1, resid(hi)

    hi0 = jnp.ones((b,), c2.dtype)
    hi0, _, _ = jax.lax.while_loop(gcond, gbody, (hi0, 0, resid(hi0)))

    # bisection; per-problem convergence latches via `done`
    def bcond(state):
        lo, hi, steps, done = state
        return jnp.any(~done) & (steps < max_iter)

    def bbody(state):
        lo, hi, steps, done = state
        mid = 0.5 * (lo + hi)
        r = resid(mid)
        upd = ~done
        lo = jnp.where(upd & (r > 0), mid, lo)
        hi = jnp.where(upd & (r <= 0), mid, hi)
        done = done | (hi - lo < tol * jnp.maximum(1.0, hi))
        return lo, hi, steps + 1, done

    lo = jnp.zeros((b,), c2.dtype)
    lo, hi, steps, _ = jax.lax.while_loop(
        bcond, bbody, (lo, hi0, 0, jnp.zeros((b,), bool))
    )
    tau_star = 0.5 * (lo + hi)

    d = jnp.clip((T[:, None] - c0) / (c2 * tau_star[:, None] + c1), d_lo, d_hi)
    # spread the bisection's residual gap over unclamped learners
    free = (d > d_lo + 1e-9) & (d < d_hi - 1e-9)
    gap = total_f - d.sum(axis=-1)
    fsum = jnp.sum(jnp.where(free, d, 0.0), axis=-1)
    add = jnp.where(
        free & (fsum > 0)[:, None],
        gap[:, None] * d / jnp.where(fsum > 0, fsum, 1.0)[:, None],
        0.0,
    )
    d = jnp.clip(d + add, d_lo, d_hi)
    tau = jnp.where(
        d > 0, jnp.maximum((T[:, None] - c0 - c1 * d) / (c2 * d), 0.0), 0.0
    )
    return feasible, tau_star, tau, d, steps


def _relaxed_energy_batched(c2, c1, c0, T, e2, e1, e0, eb, total_f, d_lo,
                            d_hi, *, tol, max_iter, use_pallas=False,
                            interpret=False):
    """Energy-budgeted lockstep water-filling (arXiv 2012.00143): the same
    bisection as ``_relaxed_batched`` on the residual

        sum_k clip(min(d_time(tau*), d_energy(tau*)), d_lo, d_hi) - total

    where ``d_time = (T - c0)/(c2 tau* + c1)`` is the deadline hyperbola
    and ``d_energy = (eb - e0)/(e2 tau* + e1)`` the budget hyperbola — the
    most data each learner can absorb at water level tau* under BOTH
    constraints. The time branch replicates ``waterfill_residual_ref``'s
    op order exactly, and IEEE inf arithmetic makes ``min(d_time, inf)``
    select the time curve bitwise, so the whole stage degenerates to
    ``_relaxed_batched`` when no budget binds (eb = +inf). Each bisection
    step is one ``kernels.ops.waterfill_energy_residual`` call — the
    Pallas TPU kernel behind ``use_pallas=True`` (float32 only)."""
    from repro.kernels import ops

    def resid(tau_star):
        return ops.waterfill_energy_residual(
            tau_star, c2, c1, c0, T, e2, e1, e0, eb, d_lo, d_hi, total_f,
            use_pallas=use_pallas, interpret=interpret,
        )

    b = c2.shape[0]
    zero = jnp.zeros((b,), c2.dtype)
    feasible = resid(zero) >= -1e-9

    def gcond(state):
        _, it, r = state
        return jnp.any(r > 0) & (it < 200)

    def gbody(state):
        hi, it, r = state
        hi = jnp.where(r > 0, hi * 2.0, hi)
        return hi, it + 1, resid(hi)

    hi0 = jnp.ones((b,), c2.dtype)
    hi0, _, _ = jax.lax.while_loop(gcond, gbody, (hi0, 0, resid(hi0)))

    def bcond(state):
        lo, hi, steps, done = state
        return jnp.any(~done) & (steps < max_iter)

    def bbody(state):
        lo, hi, steps, done = state
        mid = 0.5 * (lo + hi)
        r = resid(mid)
        upd = ~done
        lo = jnp.where(upd & (r > 0), mid, lo)
        hi = jnp.where(upd & (r <= 0), mid, hi)
        done = done | (hi - lo < tol * jnp.maximum(1.0, hi))
        return lo, hi, steps + 1, done

    lo = jnp.zeros((b,), c2.dtype)
    lo, hi, steps, _ = jax.lax.while_loop(
        bcond, bbody, (lo, hi0, 0, jnp.zeros((b,), bool))
    )
    tau_star = 0.5 * (lo + hi)

    dt = (T[:, None] - c0) / (c2 * tau_star[:, None] + c1)
    de = (eb - e0) / (e2 * tau_star[:, None] + e1)
    d = jnp.clip(jnp.minimum(dt, de), d_lo, d_hi)
    free = (d > d_lo + 1e-9) & (d < d_hi - 1e-9)
    gap = total_f - d.sum(axis=-1)
    fsum = jnp.sum(jnp.where(free, d, 0.0), axis=-1)
    add = jnp.where(
        free & (fsum > 0)[:, None],
        gap[:, None] * d / jnp.where(fsum > 0, fsum, 1.0)[:, None],
        0.0,
    )
    d = jnp.clip(d + add, d_lo, d_hi)
    # tau is the tightest of the two per-learner caps at the final d
    tau_t = (T[:, None] - c0 - c1 * d) / (c2 * d)
    tau_e = (eb - e0 - e1 * d) / (e2 * d)
    tau = jnp.where(d > 0, jnp.maximum(jnp.minimum(tau_t, tau_e), 0.0), 0.0)
    return feasible, tau_star, tau, d, steps


def _integerize_one(d_real, total_i, lo_i, hi_i):
    """Largest-remainder rounding to exact sum within bounds — the
    ``solver_kkt._integerize_d`` loop as a bounded while_loop."""
    k = d_real.shape[0]
    base = jnp.clip(jnp.floor(d_real), lo_i.astype(d_real.dtype),
                    hi_i.astype(d_real.dtype)).astype(total_i.dtype)
    rema = d_real - jnp.floor(d_real)
    order_add = jnp.argsort(-rema, stable=True)
    order_sub = jnp.argsort(rema, stable=True)
    deficit0 = total_i - base.sum()
    pos = deficit0 > 0
    order = jnp.where(pos, order_add, order_sub)
    step = jnp.where(pos, 1, -1).astype(base.dtype)

    def cond(state):
        _, deficit, i = state
        return (deficit != 0) & (i < 10 * k + jnp.abs(total_i) + 1)

    def body(state):
        base, deficit, i = state
        kk = order[i % k]
        ok = jnp.where(pos, base[kk] < hi_i[kk], base[kk] > lo_i[kk])
        delta = jnp.where(ok, step, jnp.asarray(0, base.dtype))
        return base.at[kk].add(delta), deficit - delta, i + 1

    base, deficit, _ = jax.lax.while_loop(cond, body, (base, deficit0, 0))
    return base, deficit


def _sai_one(d0, c2, c1, c0, T, lo_i, hi_i, valid, *, max_rounds,
             energy=None):
    """Greedy suggest-and-improve repair (``solver_kkt.suggest_and_improve``)
    as a bounded while_loop: move samples from the min-tau learner to the
    highest-tau learner with headroom while staleness improves.

    With ``energy = (e2, e1, e0, eb)`` rows, every tau is additionally
    capped by the budget (``_max_tau_energy``), so any d within the
    energy-tightened box yields a budget-respecting (tau, d) by
    construction — SAI moves can never overspend."""

    int_dtype = d0.dtype
    neg_one = jnp.asarray(-1, int_dtype)
    sentinel = jnp.asarray(_INT_SENTINEL, int_dtype)

    def tau_of(d):
        t = _max_tau_of_d(d, c2, c1, c0, T)
        if energy is None:
            return t
        return jnp.minimum(t, _max_tau_energy(d, *energy))

    def stats(tau):
        tmax = jnp.max(jnp.where(valid, tau, neg_one))
        tmin = jnp.min(jnp.where(valid, tau, sentinel))
        return tmax, tmin

    def body(state):
        d, tau, rounds, _ = state
        tmax, tmin = stats(tau)
        s = tmax - tmin

        hi0 = jnp.argmax(jnp.where(valid, tau, neg_one))
        # min-tau learner freeing the most tau per sample removed (max c2)
        lo = jnp.argmax(jnp.where(valid & (tau == tmin), c2, -jnp.inf))
        give = d[lo] - lo_i[lo]
        room_k = jnp.minimum(hi_i - d, give)
        room0 = room_k[hi0]
        # fallback: next-highest-tau learner (above the min) with room
        elig = valid & (tau > tmin) & (room_k > 0)
        any_elig = jnp.any(elig)
        hi1 = jnp.argmax(jnp.where(elig, tau, neg_one))
        fallback = room0 <= 0
        hi = jnp.where(fallback, hi1, hi0)
        room = jnp.where(fallback, room_k[hi1], room0)
        has_target = jnp.where(fallback, any_elig, True)

        tau_sum = jnp.sum(jnp.where(valid, tau, 0))

        def try_move(m):
            d2 = d.at[hi].add(m).at[lo].add(-m)
            tau2 = tau_of(d2)
            tmax2, tmin2 = stats(tau2)
            s2 = tmax2 - tmin2
            better = (s2 < s) | (
                (s2 == s) & (jnp.sum(jnp.where(valid, tau2, 0)) > tau_sum)
            )
            return d2, tau2, better

        m_big = jnp.maximum(jnp.asarray(1, int_dtype), room // 8)
        d2a, tau2a, acc_a = try_move(m_big)
        d2b, tau2b, acc_b = try_move(jnp.asarray(1, int_dtype))
        retry = (~acc_a) & (m_big > 1) & acc_b

        do_move = (s > 0) & has_target & (acc_a | retry)
        d_new = jnp.where(do_move, jnp.where(acc_a, d2a, d2b), d)
        tau_new = jnp.where(do_move, jnp.where(acc_a, tau2a, tau2b), tau)
        return d_new, tau_new, rounds + 1, ~do_move

    def cond(state):
        return (~state[3]) & (state[2] < max_rounds)

    tau0 = tau_of(d0)
    d, tau, rounds, _ = jax.lax.while_loop(cond, body, (d0, tau0, 0, False))
    return tau, d, rounds


def _sai_one_energy(d0, c2, c1, c0, T, lo_i, hi_i, valid, e2, e1, e0, eb, *,
                    max_rounds):
    """``_sai_one`` with the energy rows as vmappable positional args."""
    return _sai_one(d0, c2, c1, c0, T, lo_i, hi_i, valid,
                    max_rounds=max_rounds, energy=(e2, e1, e0, eb))


def _integerize_and_repair(d_r, feasible, c2, c1, c0, T, total_i, d_lo, d_hi,
                           valid, *, max_rounds, energy=None):
    """Shared integer tail of every batched policy: largest-remainder
    rounding to the exact sum, then greedy SAI repair (both vmapped bounded
    while_loops). Returns (tau, d, feasible, sai_rounds). ``energy`` rows
    (if given) cap every tau the SAI stage assigns by the budget."""
    lo_i = jnp.round(d_lo).astype(total_i.dtype)
    hi_i = jnp.round(d_hi).astype(total_i.dtype)
    # neutralize infeasible rows so the integer repair loops terminate fast
    total_safe = jnp.where(feasible, total_i, lo_i.sum(axis=-1))
    d_r_safe = jnp.where(feasible[:, None], d_r, d_lo)

    d_int, leftover = jax.vmap(_integerize_one)(d_r_safe, total_safe, lo_i, hi_i)
    # repair that exhausted its bound without hitting the sum (possible only
    # for hand-built structs whose box is infeasible — AllocationProblem
    # rejects those up front) must not masquerade as a solution
    feasible = feasible & (leftover == 0)
    if energy is None:
        tau, d, rounds = jax.vmap(
            functools.partial(_sai_one, max_rounds=max_rounds)
        )(d_int, c2, c1, c0, T, lo_i, hi_i, valid)
    else:
        tau, d, rounds = jax.vmap(
            functools.partial(_sai_one_energy, max_rounds=max_rounds)
        )(d_int, c2, c1, c0, T, lo_i, hi_i, valid, *energy)
    return tau, d, feasible, rounds


def _kkt_batched_core(c2, c1, c0, T, total_i, d_lo, d_hi, valid, *,
                      tol, max_iter, max_rounds, use_pallas, interpret):
    """Traced KKT water-filling + SAI pipeline — callable from inside other
    traced programs (the orchestrator's in-scan reallocation) as well as
    from the jitted host entry point."""
    total_f = total_i.astype(c2.dtype)
    feasible, tau_star, tau_r, d_r, _ = _relaxed_batched(
        c2, c1, c0, T, total_f, d_lo, d_hi,
        tol=tol, max_iter=max_iter, use_pallas=use_pallas, interpret=interpret,
    )
    tau, d, feasible, rounds = _integerize_and_repair(
        d_r, feasible, c2, c1, c0, T, total_i, d_lo, d_hi, valid,
        max_rounds=max_rounds,
    )
    return dict(
        tau=tau, d=d, feasible=feasible,
        relaxed_tau=tau_r, relaxed_d=d_r, tau_star=tau_star, sai_rounds=rounds,
    )


@functools.partial(
    jax.jit,
    static_argnames=("tol", "max_iter", "max_rounds", "use_pallas", "interpret"),
)
def _solve_kkt_batched_impl(c2, c1, c0, T, total_i, d_lo, d_hi, valid, *,
                            tol, max_iter, max_rounds, use_pallas, interpret):
    return _kkt_batched_core(
        c2, c1, c0, T, total_i, d_lo, d_hi, valid,
        tol=tol, max_iter=max_iter, max_rounds=max_rounds,
        use_pallas=use_pallas, interpret=interpret,
    )


def apply_energy_mask(total_i, d_lo, d_hi, valid, energy):
    """Project a ``(B, K)`` policy problem onto its *affordable* sub-fleet.

    The budget at tau = 0 caps each learner's data at ``(eb - e0) / e1``
    samples; the upper bound is tightened to that cap, and a learner whose
    cap cannot even cover its ``d_lo`` is masked out entirely through
    ``apply_active_mask`` — the padded-slot semantics, exactly like an
    offline learner under churn. The per-fleet budget is clipped into the
    surviving fleet's box (feasible-or-degraded; an all-unaffordable
    fleet degrades to a zero budget rather than going infeasible).

    IEEE inf arithmetic makes an infinite budget a bitwise no-op: the cap
    is +inf, ``min(inf, d_hi) = d_hi``, every learner affordable. Only
    elementwise ``jnp``, so traced or host, like ``apply_active_mask``.

    Returns ``(total, d_lo, d_hi, valid)``.
    """
    e2, e1, e0, eb = energy
    lo = jnp.asarray(d_lo)
    hi = jnp.asarray(d_hi)
    room = eb - e0
    capf = jnp.where(
        e1 > 0, room / jnp.where(e1 > 0, e1, 1.0),
        jnp.where(room >= 0, jnp.inf, -1.0),
    )
    hi_e = jnp.clip(jnp.minimum(jnp.floor(capf), hi), 0.0, hi)
    affordable = hi_e >= lo
    return apply_active_mask(total_i, lo, hi_e, valid, affordable)


def _kkt_energy_core(c2, c1, c0, T, total_i, d_lo, d_hi, valid, energy, *,
                     tol, max_iter, max_rounds, use_pallas=False,
                     interpret=False):
    """Traced energy-budgeted KKT pipeline (``scheme="kkt_energy"``):
    affordability mask -> budgeted water-filling -> integerize -> SAI with
    energy-capped taus. Every stage keeps ``E_k(tau, d) <= eb_k`` by
    construction (integer d never exceeds the tau=0 cap, integer tau never
    exceeds the energy cap at that d), so solutions carry ZERO budget
    violations — the property the energy tests pin."""
    e2, e1, e0, eb = (jnp.asarray(x) for x in energy)
    energy = (e2, e1, e0, eb)
    total_i, d_lo, d_hi, valid = apply_energy_mask(
        total_i, d_lo, d_hi, valid, energy
    )
    total_f = total_i.astype(c2.dtype)
    feasible, tau_star, tau_r, d_r, _ = _relaxed_energy_batched(
        c2, c1, c0, T, e2, e1, e0, eb, total_f, d_lo, d_hi,
        tol=tol, max_iter=max_iter, use_pallas=use_pallas,
        interpret=interpret,
    )
    tau, d, feasible, rounds = _integerize_and_repair(
        d_r, feasible, c2, c1, c0, T, total_i, d_lo, d_hi, valid,
        max_rounds=max_rounds, energy=energy,
    )
    return dict(
        tau=tau, d=d, feasible=feasible,
        relaxed_tau=tau_r, relaxed_d=d_r, tau_star=tau_star, sai_rounds=rounds,
    )


@functools.partial(
    jax.jit,
    static_argnames=("tol", "max_iter", "max_rounds", "use_pallas", "interpret"),
)
def _solve_energy_batched_impl(c2, c1, c0, T, total_i, d_lo, d_hi, valid,
                               energy, *, tol, max_iter, max_rounds,
                               use_pallas=False, interpret=False):
    return _kkt_energy_core(
        c2, c1, c0, T, total_i, d_lo, d_hi, valid, energy,
        tol=tol, max_iter=max_iter, max_rounds=max_rounds,
        use_pallas=use_pallas, interpret=interpret,
    )


# ---------------------------------------------------------------------------
# host entry points
# ---------------------------------------------------------------------------

def _as_batched(problems) -> BatchedProblems:
    if isinstance(problems, BatchedProblems):
        return problems
    return BatchedProblems.from_problems(list(problems))


def solve_kkt_batched(
    problems,
    *,
    x64: bool = True,
    use_pallas: bool = False,
    interpret: bool = False,
    tol: float = 1e-10,
    max_iter: int = 200,
    max_rounds: int = 10_000,
) -> BatchedAllocation:
    """Solve B problems (list[AllocationProblem] or BatchedProblems) with the
    paper's KKT water-filling + SAI pipeline as one jitted XLA program.

    ``x64=True`` reproduces ``solve_kkt_sai`` per problem exactly (modulo
    the documented remainder-tie tolerance); ``x64=False`` runs float32 for
    device-resident scheduling. ``use_pallas=True`` routes every bisection
    residual through the Pallas TPU kernel (``interpret=True`` on CPU); the
    kernel computes in float32, so it requires ``x64=False``.
    """
    if use_pallas and x64:
        raise ValueError("use_pallas=True computes residuals in float32; "
                         "pass x64=False (the exact-equivalence path is "
                         "jnp-reference only)")
    bp = _as_batched(problems)
    fdt = np.float64 if x64 else np.float32
    idt = np.int64 if x64 else np.int32
    ctx = jax.enable_x64(True) if x64 else contextlib.nullcontext()
    with ctx:
        out = _solve_kkt_batched_impl(
            jnp.asarray(bp.c2, fdt), jnp.asarray(bp.c1, fdt),
            jnp.asarray(bp.c0, fdt), jnp.asarray(bp.T, fdt),
            jnp.asarray(bp.total, idt),
            jnp.asarray(bp.d_lo, fdt), jnp.asarray(bp.d_hi, fdt),
            jnp.asarray(bp.valid),
            tol=tol, max_iter=max_iter, max_rounds=max_rounds,
            use_pallas=use_pallas, interpret=interpret,
        )
        out = {k: np.asarray(v) for k, v in out.items()}
    return BatchedAllocation(
        tau=out["tau"].astype(np.int64),
        d=out["d"].astype(np.int64),
        feasible=out["feasible"],
        valid=np.asarray(bp.valid, bool),
        method="kkt_sai_batched",
        relaxed_tau=out["relaxed_tau"],
        relaxed_d=out["relaxed_d"],
        tau_star=out["tau_star"],
    )


def _eta_one(total_i, lo_i, hi_i, valid, c2, c1, c0, T):
    k = lo_i.shape[0]
    n_valid = jnp.maximum(valid.sum(), 1)
    base = total_i // n_valid
    rem = total_i - base * n_valid
    rank = jnp.cumsum(valid.astype(total_i.dtype)) - 1
    d = jnp.where(valid, base + (rank < rem).astype(total_i.dtype), 0)
    d = jnp.clip(d, lo_i, hi_i)
    order = jnp.argsort(-d, stable=True)

    def cond(state):
        _, gap, i = state
        return (gap != 0) & (i < 100 * k + jnp.abs(total_i) + 1)

    def body(state):
        d, gap, i = state
        kk = order[i % k]
        delta = jnp.where(
            (gap > 0) & (d[kk] < hi_i[kk]), 1,
            jnp.where((gap < 0) & (d[kk] > lo_i[kk]), -1, 0),
        ).astype(d.dtype)
        return d.at[kk].add(delta), gap - delta, i + 1

    d, gap, _ = jax.lax.while_loop(cond, body, (d, total_i - d.sum(), 0))
    tau = _max_tau_of_d(d, c2, c1, c0, T)
    return tau, d, gap == 0


@jax.jit
def _solve_eta_batched_impl(c2, c1, c0, T, total_i, lo_i, hi_i, valid):
    return jax.vmap(_eta_one)(total_i, lo_i, hi_i, valid, c2, c1, c0, T)


# ---------------------------------------------------------------------------
# traced allocation policies (the orchestrator's in-scan reallocation API)
# ---------------------------------------------------------------------------

def _kkt_policy(c2, c1, c0, T, total_i, d_lo, d_hi, valid, *, tol, max_iter,
                max_rounds, use_pallas, interpret):
    out = _kkt_batched_core(
        c2, c1, c0, T, total_i, d_lo, d_hi, valid,
        tol=tol, max_iter=max_iter, max_rounds=max_rounds,
        use_pallas=use_pallas, interpret=interpret,
    )
    return out["tau"], out["d"], out["feasible"]


def _kkt_energy_policy(c2, c1, c0, T, total_i, d_lo, d_hi, valid, energy, *,
                       tol, max_iter, max_rounds, use_pallas, interpret):
    """The ``kkt_energy`` traced policy: the standard 8-arg policy
    signature plus a 9th traced argument — the ``(e2, e1, e0, eb)`` tuple
    of (B, K) energy rows (traced data, NOT baked into the closure, so
    one cached callable serves every budget)."""
    out = _kkt_energy_core(
        c2, c1, c0, T, total_i, d_lo, d_hi, valid, energy,
        tol=tol, max_iter=max_iter, max_rounds=max_rounds,
        use_pallas=use_pallas, interpret=interpret,
    )
    return out["tau"], out["d"], out["feasible"]


def _eta_policy(c2, c1, c0, T, total_i, d_lo, d_hi, valid):
    lo_i = jnp.round(d_lo).astype(total_i.dtype)
    hi_i = jnp.round(d_hi).astype(total_i.dtype)
    tau, d, ok = jax.vmap(_eta_one)(total_i, lo_i, hi_i, valid, c2, c1, c0, T)
    return tau, d, ok


def _pgd_policy(c2, c1, c0, T, total_i, d_lo, d_hi, valid, energy=None, *,
                steps, max_rounds):
    """The ``pgd`` traced policy. The optional 9th argument mirrors
    ``kkt_energy``'s: ``(e2, e1, e0, eb)`` rows project the problem onto
    the energy-budget box (affordability mask) before the gradient stage
    and cap every SAI tau by the budget — with ``eb = +inf`` all of it is
    decision-inert and the energy-blind path is reproduced exactly."""
    from repro.core import solver_numeric
    from repro.kernels import ops

    if energy is not None:
        energy = tuple(jnp.asarray(x) for x in energy)
        total_i, d_lo, d_hi, valid = apply_energy_mask(
            total_i, d_lo, d_hi, valid, energy
        )
    total_f = total_i.astype(c2.dtype)
    if energy is None:
        feasible = ops.waterfill_residual(
            jnp.zeros_like(T), c2, c1, c0, T, d_lo, d_hi, total_f
        ) >= -1e-9
    else:
        feasible = ops.waterfill_energy_residual(
            jnp.zeros_like(T), c2, c1, c0, T, *energy, d_lo, d_hi, total_f
        ) >= -1e-9
    n_valid = jnp.maximum(valid.sum(axis=-1, keepdims=True), 1)
    d0 = jnp.clip(
        jnp.where(valid, total_f[:, None] / n_valid, 0.0), d_lo, d_hi
    )
    tau_r, d_r = jax.vmap(
        lambda d0_, c2_, c1_, c0_, T_, lo_, hi_, tot_, v_:
            solver_numeric._pgd_run(d0_, c2_, c1_, c0_, T_, lo_, hi_, tot_,
                                    steps, v_)
    )(d0, c2, c1, c0, T, d_lo, d_hi, total_f, valid)
    tau, d, feasible, _ = _integerize_and_repair(
        d_r, feasible, c2, c1, c0, T, total_i, d_lo, d_hi, valid,
        max_rounds=max_rounds, energy=energy,
    )
    return tau, d, feasible


#: schemes with a traced in-scan policy (see ``batched_policy``)
TRACED_POLICIES = ("kkt_sai", "eta", "pgd", "kkt_energy")


@functools.lru_cache(maxsize=None)
def batched_policy(
    name: str,
    *,
    tol: float = 1e-10,
    max_iter: int = 200,
    max_rounds: int = 10_000,
    use_pallas: bool = False,
    interpret: bool = False,
    pgd_steps: int = 600,
):
    """A traced allocation policy — the in-scan re-solve hook of the fused
    orchestrator and the per-(re)dispatch solve of the async engine.

    Parameters
    ----------
    name : one of ``TRACED_POLICIES``: ``"kkt_sai"`` (the paper's
        water-filling + SAI pipeline), ``"eta"`` (equal-task baseline),
        ``"pgd"`` (relaxed projected-gradient + the same integerize/SAI
        tail) or ``"kkt_energy"`` (the budgeted pipeline of arXiv
        2012.00143 — same signature plus a 9th traced argument, the
        ``(e2, e1, e0, e_budget)`` tuple of (B, K) energy rows; with
        ``e_budget = +inf`` it reproduces ``kkt_sai`` decision for
        decision).
    tol, max_iter : bisection stop criteria (kkt_sai).
    max_rounds : SAI repair bound (kkt_sai, pgd).
    use_pallas, interpret : route bisection residuals through the Pallas
        TPU kernel (float32 only; ``interpret=True`` emulates on CPU).
    pgd_steps : inner gradient steps (pgd).

    Returns
    -------
    A pure traced callable ``fn(c2, c1, c0, T, total_i, d_lo, d_hi, valid)
    -> (tau, d, feasible)`` safe to call inside ``jit``/``scan``/``vmap``:

    * inputs — ``c2/c1/c0/d_lo/d_hi``: (B, K) float capacity rows and box
      bounds; ``T``: (B,) float deadlines; ``total_i``: (B,) int sample
      budgets; ``valid``: (B, K) bool fleet mask (``BatchedProblems``
      padding semantics: padded slots carry ``d_lo = d_hi = 0``);
    * outputs — ``tau, d``: (B, K) int allocations (0 in padded slots);
      ``feasible``: (B,) bool, False where even tau = 0 cannot absorb the
      budget (outputs in such rows are neutralized, not meaningful).

    Run under ``jax.enable_x64`` with f64 inputs to reproduce the NumPy
    solvers decision-for-decision; f32 inputs give the device-resident
    fast path. The returned callable is cached per option set so jit
    caches keyed on it stay warm."""
    if name == "kkt_sai":
        return functools.partial(
            _kkt_policy, tol=tol, max_iter=max_iter, max_rounds=max_rounds,
            use_pallas=use_pallas, interpret=interpret,
        )
    if name == "eta":
        return _eta_policy
    if name == "pgd":
        return functools.partial(
            _pgd_policy, steps=pgd_steps, max_rounds=max_rounds,
        )
    if name == "kkt_energy":
        return functools.partial(
            _kkt_energy_policy, tol=tol, max_iter=max_iter,
            max_rounds=max_rounds, use_pallas=use_pallas,
            interpret=interpret,
        )
    raise ValueError(
        f"no batched/traced policy for scheme {name!r}; "
        f"choose from {' | '.join(TRACED_POLICIES)}"
    )


def solve_eta_batched(problems, *, x64: bool = True) -> BatchedAllocation:
    """Equal-task-allocation baseline (``baselines.solve_eta``) over a batch:
    d_k = d/K spread by index, bound-clipped, integer-sum repaired, then
    tau_k maximal per learner."""
    bp = _as_batched(problems)
    fdt = np.float64 if x64 else np.float32
    idt = np.int64 if x64 else np.int32
    ctx = jax.enable_x64(True) if x64 else contextlib.nullcontext()
    with ctx:
        tau, d, ok = _solve_eta_batched_impl(
            jnp.asarray(bp.c2, fdt), jnp.asarray(bp.c1, fdt),
            jnp.asarray(bp.c0, fdt), jnp.asarray(bp.T, fdt),
            jnp.asarray(bp.total, idt),
            jnp.asarray(np.round(bp.d_lo), idt), jnp.asarray(np.round(bp.d_hi), idt),
            jnp.asarray(bp.valid),
        )
        tau, d, ok = np.asarray(tau), np.asarray(d), np.asarray(ok)
    return BatchedAllocation(
        tau=tau.astype(np.int64), d=d.astype(np.int64), feasible=ok,
        valid=np.asarray(bp.valid, bool), method="eta_batched",
    )


def solve_energy_batched(
    problems,
    *,
    x64: bool = True,
    use_pallas: bool = False,
    interpret: bool = False,
    tol: float = 1e-10,
    max_iter: int = 200,
    max_rounds: int = 10_000,
) -> BatchedAllocation:
    """Solve B energy-budgeted problems (arXiv 2012.00143) with the
    ``kkt_energy`` pipeline as one jitted XLA program. Problems without an
    energy model get zero-coefficient rows and infinite budgets, under
    which the decisions coincide with ``solve_kkt_batched``; with budgets,
    every returned allocation satisfies ``E_k(tau, d) <= e_budget_k`` by
    construction (learners whose budget cannot cover ``d_lower`` are
    degraded to the padded-slot semantics, like offline learners).
    ``use_pallas=True`` routes every budgeted bisection residual through
    the Pallas TPU kernel (float32 only — requires ``x64=False``;
    ``interpret=True`` emulates on CPU)."""
    if use_pallas and x64:
        raise ValueError("use_pallas=True computes residuals in float32; "
                         "pass x64=False (the exact-equivalence path is "
                         "jnp-reference only)")
    bp = _as_batched(problems)
    e2, e1, e0, eb = bp.energy_rows()
    fdt = np.float64 if x64 else np.float32
    idt = np.int64 if x64 else np.int32
    ctx = jax.enable_x64(True) if x64 else contextlib.nullcontext()
    with ctx:
        out = _solve_energy_batched_impl(
            jnp.asarray(bp.c2, fdt), jnp.asarray(bp.c1, fdt),
            jnp.asarray(bp.c0, fdt), jnp.asarray(bp.T, fdt),
            jnp.asarray(bp.total, idt),
            jnp.asarray(bp.d_lo, fdt), jnp.asarray(bp.d_hi, fdt),
            jnp.asarray(bp.valid),
            (jnp.asarray(e2, fdt), jnp.asarray(e1, fdt),
             jnp.asarray(e0, fdt), jnp.asarray(eb, fdt)),
            tol=tol, max_iter=max_iter, max_rounds=max_rounds,
            use_pallas=use_pallas, interpret=interpret,
        )
        out = {k: np.asarray(v) for k, v in out.items()}
    return BatchedAllocation(
        tau=out["tau"].astype(np.int64),
        d=out["d"].astype(np.int64),
        feasible=out["feasible"],
        valid=np.asarray(bp.valid, bool),
        method="kkt_energy_batched",
        relaxed_tau=out["relaxed_tau"],
        relaxed_d=out["relaxed_d"],
        tau_star=out["tau_star"],
    )


def apply_active_mask(total_i, d_lo, d_hi, valid, active):
    """Project a ``(B, K)`` policy problem onto its online sub-fleet.

    Offline slots get the padded-slot semantics of ``BatchedProblems``
    (``d_lo = d_hi = 0``, ``valid=False``) so the policies skip them,
    and the per-fleet sample budget is clipped into the live fleet's box
    ``[sum d_lo, sum d_hi]`` — a thinned fleet serves what it can absorb
    instead of going infeasible; an all-offline fleet degrades to a zero
    budget.  Elementwise ``jnp`` only, so it is usable traced or on host
    (run under ``jax.enable_x64`` when exact integer budgets matter).

    Returns ``(total, d_lo, d_hi, valid)`` with the same shapes/dtypes
    as the inputs.
    """
    act = jnp.asarray(active, bool)
    lo = jnp.where(act, d_lo, jnp.zeros((), jnp.asarray(d_lo).dtype))
    hi = jnp.where(act, d_hi, jnp.zeros((), jnp.asarray(d_hi).dtype))
    v = jnp.asarray(valid, bool) & act
    total = jnp.asarray(total_i)
    tot = jnp.clip(
        total.astype(lo.dtype), jnp.sum(lo, axis=-1), jnp.sum(hi, axis=-1)
    )
    return tot.astype(total.dtype), lo, hi, v


def apply_sampling_mask(total_i, d_lo, d_hi, valid, sampled):
    """Project a fleet-axis policy problem onto the round's sampled fleets.

    ``sampled`` is a per-fleet ``(B,)`` bool mask (FedAST-style partial
    participation: only a subset of fleets is served each round). A
    sampled-out fleet is treated exactly like an all-offline fleet, which
    in turn is exactly a row of ``BatchedProblems`` padded slots: zero
    boxes, ``valid=False`` everywhere, budget degraded to zero — so the
    policies solve tau = d = 0 for it without going infeasible. This is
    ``apply_active_mask`` with the mask broadcast over the learner axis;
    the equivalence of the three maskings is pinned by the fleet property
    tests. Traced or host, same as ``apply_active_mask``.
    """
    act = jnp.asarray(sampled, bool)[..., None] & jnp.asarray(valid, bool)
    return apply_active_mask(total_i, d_lo, d_hi, valid, act)


# ---------------------------------------------------------------------------
# cross-model allocation layer (FedAST-style multi-tenant split)
# ---------------------------------------------------------------------------

#: cross-model budget-split policies (see ``cross_model_weights``)
SPLIT_POLICIES = ("deficit", "equal")

#: split weights are floored onto this binary grid so their exact sum is a
#: representable float <= 1.0 — the budget-conservation guarantee cannot be
#: eaten by rounding in the normalization divides.
_SPLIT_GRID = float(2**20)


def cross_model_weights(deficits, *, policy: str = "deficit",
                        share_floor: float = 0.0):
    """Per-model budget-split weights ``w`` of shape (S,) from a (S,)
    progress-deficit signal (FedAST-style behind-ness: how far each tenant
    model trails its round target — model-value-free, so the schedule stays
    bit-reproducible).

    ``policy="deficit"`` splits proportionally to ``max(deficits, 0)``
    (equal split when all deficits are zero); ``policy="equal"`` is the
    uniform 1/S baseline. ``share_floor`` mixes a uniform floor in
    (``w = (1 - S*floor) p + floor``) so no tenant is fully starved;
    requires ``share_floor * S <= 1``.

    Guarantees, pinned by the multimodel property tests:

    * weights are floored onto a 2^-20 binary grid, so ``w.sum()`` is an
      EXACTLY-representable float ``<= 1.0`` — per-learner budgets split
      as ``w_s * T_k`` can never over-commit the pool by more than one
      product-rounding ULP per model;
    * S = 1 returns exactly 1.0 (statically — no grid, no arithmetic), so
      ``w * T == T`` bitwise: the single-tenant engine is a fixed point;
    * permutation-equivariant across models, and each model's weight is
      monotone non-decreasing in its own deficit (elementwise normalize +
      monotone floor).
    """
    if policy not in SPLIT_POLICIES:
        raise ValueError(
            f"no cross-model split policy {policy!r}; "
            f"choose from {' | '.join(SPLIT_POLICIES)}"
        )
    deficits = jnp.asarray(deficits)
    s = int(deficits.shape[0])
    dtype = (deficits.dtype if jnp.issubdtype(deficits.dtype, jnp.floating)
             else jnp.result_type(float))
    if s == 1:
        return jnp.ones((1,), dtype)
    if share_floor < 0 or share_floor * s > 1.0:
        raise ValueError(f"share_floor={share_floor} must satisfy "
                         f"0 <= share_floor * S <= 1 (S={s})")
    if policy == "equal":
        p = jnp.full((s,), 1.0 / s, dtype)
    else:
        c = jnp.maximum(deficits.astype(dtype), 0.0)
        tot = c.sum()
        p = jnp.where(tot > 0, c / jnp.where(tot > 0, tot, 1.0), 1.0 / s)
    if share_floor > 0.0:
        p = (1.0 - s * share_floor) * p + share_floor
    return jnp.floor(p * _SPLIT_GRID) / _SPLIT_GRID


def cross_model_split(deficits, T, e_budget=None, *, policy: str = "deficit",
                      share_floor: float = 0.0):
    """Split shared budgets across S tenant models: ``(w, T_split,
    eb_split)`` where ``T_split = w * T`` ((S,) per-model deadlines from a
    scalar or (S,) shared deadline) and ``eb_split = w[:, None] *
    e_budget`` ((S, K) per-model per-learner joule budgets; infinite
    budgets stay infinite rather than going 0 * inf = nan). With
    ``w.sum() <= 1.0`` exact (see ``cross_model_weights``), each learner's
    summed time/energy commitment across tenants stays within its single-
    tenant budget."""
    w = cross_model_weights(deficits, policy=policy, share_floor=share_floor)
    T = jnp.asarray(T)
    w = w.astype(T.dtype)
    T_split = w * T
    eb_split = None
    if e_budget is not None:
        eb = jnp.asarray(e_budget)
        eb_split = jnp.where(jnp.isinf(eb), eb, w[:, None] * eb)
    return w, T_split, eb_split


def multimodel_policy(name: str, *, split: str = "deficit",
                      share_floor: float = 0.0, **policy_kwargs):
    """The cross-model allocation layer: a traced policy over the (S, K)
    multi-tenant problem tensor (S models sharing one K-learner pool).

    Every (re)dispatch first splits each learner's deadline ``T`` (and
    per-learner energy budgets, for ``name="kkt_energy"``) across models
    with ``cross_model_split`` on the progress-deficit signal, scales each
    model's per-round sample budget by its share, degrades (model,
    learner) cells whose share cannot even cover ``d_lo`` at tau = 0 to
    the padded-slot semantics (``apply_active_mask`` — feasible-or-
    degraded, like offline learners under churn), then solves all S
    per-model (tau, d) rows with ONE ``batched_policy(name)`` call on the
    (S, K) batch.

    Returns a traced callable

        fn(deficits, c2, c1, c0, T, total_i, d_lo, d_hi, valid[, energy])
        -> (tau, d, feasible, w)

    with ``deficits``: (S,); ``c2/c1/c0/d_lo/d_hi/valid``: (S, K);
    ``T``: (S,) per-model full deadlines (normally all equal to the shared
    learner deadline); ``total_i``: (S,) per-model sample budgets;
    ``energy``: optional (e2, e1, e0, eb) rows of shape (S, K).

    Exactness anchor: S = 1 is a STATIC pass-through — the unit split
    leaves every input untouched (no mask, no scaling), so the underlying
    ``batched_policy`` sees bitwise-identical operands and the multi-
    tenant engine reproduces the single-tenant one record-for-record."""
    base = batched_policy(name, **policy_kwargs)

    def fn(deficits, c2, c1, c0, T, total_i, d_lo, d_hi, valid, energy=None):
        s = int(c2.shape[0])
        if s == 1:
            w = jnp.ones((1,), jnp.asarray(T).dtype)
            if energy is None:
                tau, d, ok = base(c2, c1, c0, T, total_i, d_lo, d_hi, valid)
            else:
                tau, d, ok = base(c2, c1, c0, T, total_i, d_lo, d_hi, valid,
                                  energy)
            return tau, d, ok, w
        eb = energy[3] if energy is not None else None
        w, T_s, eb_s = cross_model_split(
            deficits, T, eb, policy=split, share_floor=share_floor
        )
        total_s = jnp.round(w * total_i.astype(c2.dtype)).astype(total_i.dtype)
        active = jnp.asarray(valid, bool) & (T_s[:, None] >= c0 + c1 * d_lo)
        total_s, lo, hi, v = apply_active_mask(
            total_s, d_lo, d_hi, valid, active
        )
        if energy is None:
            tau, d, ok = base(c2, c1, c0, T_s, total_s, lo, hi, v)
        else:
            e2, e1, e0, _ = energy
            tau, d, ok = base(c2, c1, c0, T_s, total_s, lo, hi, v,
                              (e2, e1, e0, eb_s))
        return tau, d, ok, w

    return fn
