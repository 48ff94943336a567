"""Event-driven asynchronous federation engine (FedAsync / FedBuff style).

The paper's scheme is asynchronous only *within* a global cycle: every
learner's work is gated to the same wall-clock budget ``T`` (constraint 7b)
and the server aggregates once per cycle. This module drops the cycle gate:
a **virtual-clock event queue** lets every learner upload the moment it
finishes, and the server reacts per upload in the style of FedAsync (Xie et
al., arXiv:1903.03934) and FedBuff/FedAST (arXiv:2106.06639 / 2406.00302):

  * each learner's task completion time follows the paper's own per-learner
    wall-clock model (Eq. 5: download + tau_k * compute + upload =
    ``C2 tau_k d_k + C1 d_k + C0`` under the capacities of the drift block
    it was dispatched in);
  * ``mode="fedasync"`` — on every arrival the server mixes immediately,
    ``w <- (1 - alpha * s(v)) * w + alpha * s(v) * w_k`` with **version
    staleness** ``v = server_version - dispatch_version`` and the
    constant / hinge / polynomial discount ``s`` of the FedAsync paper
    (``core.staleness.staleness_factor``);
  * ``mode="buffered"`` — arrivals accumulate in a size-``M`` buffer; a
    full buffer is flushed as one staleness-weighted aggregation (the
    intra-buffer tau weighting of ``core.aggregation.staleness_weights``
    times the version-staleness discount) and bumps the server version
    once. With ``M = K`` and ``barrier=True`` the engine degenerates to
    the paper's cycle-gated scheme and reproduces ``Orchestrator.run``
    exactly (pinned by tests);
  * at every (re)dispatch the learner's ``(tau_k, d_k)`` comes from the
    fleet-level allocation re-solved through the existing traced
    ``core.solver_batched.batched_policy`` on the capacities of the current
    drift block — adaptive allocation composes with true asynchrony.

Two execution paths share one host-side **schedule**. The key structural
property is that the event timeline is *model-independent*: completion
times, versions, staleness, shard draws and aggregation coefficients depend
only on allocations and capacities, never on parameter values. The
scheduler therefore simulates the whole event system once on the host
(cheap scalar math, identical rng consumption for both paths) and the
device work is pure tensor compute:

  * ``run`` — eager: walk the schedule, train each arrival's dispatched
    model (one ``local_train`` call per event), mix/flush per event. One
    host round-trip per event.
  * ``run_events`` — device-resident fast path (**event-indexed / jagged
    bucketing**): the scheduler already fixes the full event timeline, so
    arrivals are grouped by their *flush structure* — one ``lax.scan``
    step per flush group (a fedasync arrival, or a buffered group split
    wherever a learner repeats) — instead of per time bucket. Each step
    trains the fleet's carried dispatch models (masked, to the
    schedule-wide max tau), folds the step's arrivals into a weighted
    accumulator and applies the flush as masked ``kernels.ops.fed_agg``
    contractions, with the (server, dispatched, accumulator) params carry
    donated — the whole campaign is ONE XLA program, like
    ``Orchestrator.run_fused``. Because grouping is by event index, not
    arrival time, the replay is **exact for every schedule** — including
    the near-tie and exactly-tying completion times a KKT allocator
    produces by design, which no fixed time grid can resolve. Memory cost:
    the pre-staged shard tensor is (S, K, d_cap, F) with S = number of
    scan steps (≈ number of aggregated arrivals), independent of how
    close the arrival times are.
  * ``run_bucketed`` — the legacy fixed-grid fast path: completion times
    quantized onto a ``num_buckets`` uniform grid, same scan body. Exact
    only when the grid resolves every arrival into its own bucket (the
    required bucket count blows up as 1/min-gap on near-tie schedules);
    ``strict=False`` merges colliding fedasync arrivals via
    sequentially-composed weights (aggregation exact, mid-bucket
    redispatch approximated), and buffered flushes that straddle a
    bucket boundary raise. Kept for grid-vs-jagged benchmarking; new
    callers should use ``run_events``.

Capacity drift composes with both paths through the schedule: exogenous
``CapacityDrift`` rows are materialized per block, and a state-coupled
``QueueDrift`` (capacities degraded by the backlog the dispatched
allocations themselves build up) is rolled out block-by-block jointly
with the per-block re-solves (``reallocate=True`` required).
"""

from __future__ import annotations

import dataclasses
import functools
import hashlib
import heapq

import numpy as np

import jax
import jax.numpy as jnp

from repro.core import (
    AllocationProblem,
    CapacityDrift,
    aggregate,
    fedavg_weights,
    is_state_coupled,
    staleness_weights,
)
from repro.core.staleness import (
    STALENESS_FNS,
    avg_staleness,
    max_staleness,
    staleness_factor,
    version_staleness_profile,
)
from repro.core.availability import (
    availability_masks,
    capacity_state_coupled,
    has_availability,
)
from repro.data.pipeline import Dataset, FederatedPartitioner
from repro.fed.orchestrator import (
    SCHEMES,
    _stage_shards,
    coefficient_rows,
    local_train,
    local_train_stacked,
    solve_policy_row,
    solve_rows_availability,
    solve_rows_state_coupled,
)
from repro.fed.tracing import span, to_device

__all__ = [
    "AsyncConfig",
    "AsyncFedEngine",
    "FAULT_COUNTERS",
    "summarize_async_history",
]

# NOTE: the multi-model scheduler (``fed.multimodel``) replays its per-model
# schedules through the SAME module-level executors below
# (``_replay_eager_schedule`` / ``_run_group_program``) — the S = 1
# record-for-record equivalence is literal code sharing, not re-derivation.


@dataclasses.dataclass(frozen=True)
class AsyncConfig:
    """Server behaviour of the event-driven engine.

    ``buffer_size = 0`` means "fleet size K" (resolved at engine init).
    ``barrier=True`` (buffered only, requires M = K) gates every round on
    the slowest learner and redispatches the whole fleet at the cycle
    boundary — the paper's scheme as a point in this family.

    Fault injection (all off by default; any event mode, virtual-clock
    seconds; see ``docs/robustness.md``): ``drop_rate`` loses uploads in
    transit, ``delay_rate``/``delay_mean`` adds exponential transit
    delay, ``straggler_rate``/``straggler_factor`` slows a dispatch's
    whole computation, ``deadline`` bounds each dispatch server-side with
    ``retry_backoff``-capped-exponential redispatch on a miss, and
    ``quorum``/``flush_timeout`` lets a buffered server flush an
    incomplete group (>= quorum arrivals at the timeout; below quorum it
    extends once, then degrades and flushes whatever arrived rather than
    stalling). ``barrier=True`` rejects every fault knob: the barrier is
    the fault-free paper regime.
    """

    mode: str = "fedasync"             # fedasync | buffered
    alpha: float = 0.6                 # FedAsync server mixing rate
    staleness_fn: str = "poly"         # constant | hinge | poly
    staleness_a: float = 0.5           # discount exponent / slope
    staleness_b: float = 4.0           # hinge knee
    buffer_size: int = 0               # M (buffered); 0 -> K
    barrier: bool = False              # cycle barrier (paper scheme at M=K)
    aggregation: str = "staleness"     # intra-buffer weighting: staleness|fedavg
    staleness_gamma: float = 1.0
    lr: float = 0.1
    scheme: str = "kkt_sai"            # allocation policy at (re)dispatch
    reallocate: bool = False           # re-solve per drift block
    # -- fault / churn injection (virtual-clock seconds) --------------------
    drop_rate: float = 0.0             # P(an upload is lost in transit)
    delay_rate: float = 0.0            # P(an upload is delayed in transit)
    delay_mean: float = 1.0            # mean exponential transit delay (s)
    straggler_rate: float = 0.0        # P(a dispatch straggles)
    straggler_factor: float = 4.0      # straggler slowdown (>= 1)
    deadline: float = 0.0              # per-dispatch deadline (s); 0 = off
    retry_backoff: float = 1.0         # first redispatch backoff (s)
    retry_backoff_cap: float = 8.0     # exponential backoff ceiling (s)
    quorum: int = 0                    # buffered: min arrivals at timeout
    flush_timeout: float = 0.0         # buffered: group deadline (s)

    @property
    def has_faults(self) -> bool:
        """Whether any fault/churn knob is active (fault rng is only
        drawn — and fault events only scheduled — when this is True, so
        fault-free schedules consume the historical rng stream)."""
        return (self.drop_rate > 0 or self.delay_rate > 0
                or self.straggler_rate > 0 or self.deadline > 0
                or self.quorum > 0)

    def __post_init__(self):
        if self.mode not in ("fedasync", "buffered"):
            raise ValueError(f"unknown mode {self.mode!r}: fedasync | buffered")
        if self.staleness_fn not in STALENESS_FNS:
            raise ValueError(
                f"unknown staleness fn {self.staleness_fn!r}: "
                + " | ".join(STALENESS_FNS)
            )
        if self.aggregation not in ("staleness", "fedavg"):
            raise ValueError(f"unknown aggregation {self.aggregation!r}")
        if not (0.0 < self.alpha <= 1.0):
            raise ValueError("alpha must be in (0, 1]")
        if self.barrier and self.mode != "buffered":
            raise ValueError("barrier=True is the buffered (M=K) regime; "
                             "fedasync has no cycle gate")
        for name in ("drop_rate", "delay_rate", "straggler_rate"):
            if not (0.0 <= getattr(self, name) <= 1.0):
                raise ValueError(f"{name} must be a probability in [0, 1]")
        if self.straggler_factor < 1.0:
            raise ValueError("straggler_factor must be >= 1 (a straggler "
                             "is slower, never faster)")
        if self.delay_rate > 0 and self.delay_mean <= 0:
            raise ValueError("delay_rate > 0 needs delay_mean > 0")
        if self.deadline < 0:
            raise ValueError("deadline must be >= 0 (0 disables it)")
        if self.deadline > 0 and self.retry_backoff <= 0:
            raise ValueError("deadline retries need retry_backoff > 0")
        if self.retry_backoff_cap < self.retry_backoff:
            raise ValueError("retry_backoff_cap must be >= retry_backoff")
        if self.quorum < 0:
            raise ValueError("quorum must be >= 0 (0 disables timer flushes)")
        if self.quorum > 0:
            if self.mode != "buffered":
                raise ValueError("quorum applies to buffered flushes only; "
                                 "fedasync flushes every arrival already")
            if self.flush_timeout <= 0:
                raise ValueError("quorum > 0 needs flush_timeout > 0 (the "
                                 "group deadline that triggers the quorum "
                                 "check)")
        elif self.flush_timeout > 0:
            raise ValueError("flush_timeout without quorum has no effect; "
                             "set quorum >= 1")
        if self.barrier and self.has_faults:
            raise ValueError(
                "barrier=True is the fault-free paper regime (every round "
                "gates on the full fleet); fault injection needs the "
                "event-driven modes"
            )


# ---------------------------------------------------------------------------
# host-side schedule (model-independent event timeline)
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class _Arrival:
    """One upload event. Aggregation coefficients are filled retroactively
    when the event's flush group closes (the schedule is fully simulated
    before any training runs, so this is always possible)."""

    seq: int                 # chronological arrival index
    learner: int
    t: float                 # completion (= arrival) time
    tau: int
    d: int
    idx: np.ndarray          # shard sample indices drawn at dispatch
    dispatch_t: float
    dispatch_version: int
    staleness: int           # server_version - dispatch_version at arrival
    energy: float = 0.0      # joules the dispatch cost (0 without a model)
    version_after: int = 0
    flush: bool = False      # this arrival closes a flush
    timer_flush: bool = False  # the flush fired on a quorum timer, AFTER
    #                          this arrival redispatched (pre-flush server)
    flush_t: float = 0.0     # virtual time the flush applied (= t unless
    #                          a quorum timer closed the group later)
    keep: float = 1.0        # server self-weight at the flush
    weight: float = 0.0      # this local model's coefficient in its flush
    flush_id: int = -1
    group_weights: np.ndarray | None = None   # on flush arrivals only


@dataclasses.dataclass
class _Schedule:
    arrivals: list
    n_flushes: int
    d_cap: int               # max d over arrivals (>= 1)
    max_tau: int             # max tau over arrivals (>= 1)
    counters: dict = dataclasses.field(default_factory=dict)
    # per-learner joules spent over ALL dispatches (including dropped /
    # deadline-cancelled ones — the device burned the energy either way)
    energy_spent: np.ndarray | None = None
    energy_violations: int = 0   # dispatches costing more than e_budget


FAULT_COUNTERS = (
    "dispatches", "drops", "delays", "stragglers", "deadline_misses",
    "retries", "late_discards", "quorum_flushes", "quorum_extensions",
    "quorum_degradations", "offline_deferrals", "offline_churned",
)


def _zero_fault_counters() -> dict:
    return {key: 0 for key in FAULT_COUNTERS}


_EV_ARRIVE, _EV_DEADLINE, _EV_QUORUM = 0, 1, 2   # heap tie-break priority


def _event_segments(arrivals: "list[_Arrival]") -> "list[list[_Arrival]]":
    """Partition the flush-ordered arrival sequence into **event-indexed
    (jagged) segments** — the scan steps of ``run_events``.

    Invariants (what makes one segment representable as one step of the
    bucketed scan body, and the whole partition an *exact* replay):

      * at most one arrival per learner per segment (the scan holds one
        carried dispatch model per learner slot);
      * at most one flush per segment, always the segment's LAST arrival
        (so the post-step server is the post-flush server and every
        mid-segment redispatch sees an unchanged server — which is exactly
        what the eager loop dispatches, since buffered arrivals before a
        flush redispatch with the untouched server);
      * fedasync arrivals each close their own flush, so their segments
        have exactly one arrival — no weight composition, no mid-step
        redispatch approximation, regardless of how closely (or exactly)
        the arrival times tie;
      * never-flushed trailing arrivals (``flush_id < 0``) are dropped —
        their local models are unobservable (same rule as the grid path).

    Buffered flush groups are split greedily at learner repeats; the split
    prefixes become accumulate-only segments (no flush, server untouched).
    """
    segments: list[list[_Arrival]] = []
    cur: list[_Arrival] = []
    seen: set[int] = set()
    for a in arrivals:
        if a.flush_id < 0:
            continue
        if a.learner in seen:
            segments.append(cur)
            cur, seen = [], set()
        cur.append(a)
        seen.add(a.learner)
        if a.flush:
            segments.append(cur)
            cur, seen = [], set()
    # every kept arrival belongs to a flush group that closes within the
    # horizon, so the walk always ends on a flush boundary
    assert not cur
    return segments


def _flush_row(ev: _Arrival, group: "list[_Arrival]", mode: str) -> dict:
    """One history record per server aggregation — shared by every replay
    path (and by the multi-model engine's per-model histories)."""
    ss = [g.staleness for g in group]
    return {
        "event": ev.flush_id,
        "t": ev.flush_t,
        "mode": mode,
        "server_version": ev.version_after,
        "learners": [g.learner for g in group],
        "tau": np.array([g.tau for g in group], np.int64),
        "d": np.array([g.d for g in group], np.int64),
        "staleness_list": list(map(int, ss)),
        "version_staleness_max": int(max(ss)),
        "version_staleness_mean": float(np.mean(ss)),
        "weights": np.asarray(ev.group_weights, np.float64),
        "keep": ev.keep,
        "energy": np.array([g.energy for g in group], np.float64),
    }


def _replay_eager_schedule(params, sched: _Schedule, train: Dataset, *,
                           mode: str, lr: float, num_learners: int, loss_fn,
                           evalj, ex, ey):
    """The eager event walk over ONE model's schedule: train each arrival's
    dispatched model, mix/flush per event. Returns ``(params, history)``.
    Extracted from ``AsyncFedEngine.run`` so the multi-model engine replays
    each of its per-model schedules through the IDENTICAL executor (their
    S = 1 record-for-record equivalence is this code sharing)."""
    feat = train.x.shape[1]
    dispatch_params = [params] * num_learners
    pending: list = []          # trained locals of the open buffer group
    group: list[_Arrival] = []
    history: list[dict] = []
    lrj = jnp.asarray(lr, jnp.float32)

    for ev in sched.arrivals:
        if ev.flush_id < 0:
            # trailing buffered arrival whose group never flushes
            # within the horizon: its local model is unobservable, so
            # skip the training (the redispatch model is the unchanged
            # server either way)
            dispatch_params[ev.learner] = params
            continue
        # pad to the schedule-wide (d_cap, max_tau) so every event hits
        # ONE local_train compilation (and the same masked-scan numerics
        # as the bucketed path)
        x = np.zeros((1, sched.d_cap, feat), np.float32)
        y = np.zeros((1, sched.d_cap), np.int32)
        msk = np.zeros((1, sched.d_cap), np.float32)
        x[0, : ev.d] = train.x[ev.idx]
        y[0, : ev.d] = train.y[ev.idx]
        msk[0, : ev.d] = 1.0
        out = local_train(
            dispatch_params[ev.learner], jnp.asarray(x), jnp.asarray(y),
            jnp.asarray(msk), jnp.asarray([ev.tau], jnp.int32), lrj,
            max_tau=sched.max_tau, loss_fn=loss_fn,
        )
        pending.append(jax.tree_util.tree_map(lambda l: l[0], out))
        group.append(ev)
        if ev.flush:
            if ev.timer_flush:
                # a quorum timer closed this group AFTER its last
                # arrival redispatched: the schedule gave that dispatch
                # the PRE-flush server, so hand it out before flushing
                dispatch_params[ev.learner] = params
            models = [params] + pending
            stacked = jax.tree_util.tree_map(
                lambda *ls: jnp.stack(ls), *models
            )
            wvec = np.concatenate([[ev.keep], ev.group_weights])
            params = aggregate(stacked, jnp.asarray(wvec, jnp.float32))
            rec = _flush_row(ev, group, mode)
            if evalj is not None:
                rec["accuracy"] = float(evalj(params, ex, ey))
            history.append(rec)
            pending, group = [], []
            if not ev.timer_flush:
                dispatch_params[ev.learner] = params
        else:
            dispatch_params[ev.learner] = params
    return params, history


class AsyncFedEngine:
    """Virtual-clock asynchronous federation over one fleet.

    Parameters mirror ``Orchestrator``: the ``AllocationProblem`` supplies
    the per-learner wall-clock model, ``drift`` (optional) the per-block
    capacity evolution (block length = ``problem.T``, the paper's
    capacities-constant-per-cycle block model; task cost is evaluated under
    the block of its dispatch time).
    """

    def __init__(
        self,
        cfg: AsyncConfig,
        problem: AllocationProblem,
        loss_fn,
        init_params,
        *,
        seed: int = 0,
        drift: CapacityDrift | None = None,
    ):
        self.cfg = cfg
        self.problem = problem
        self.loss_fn = loss_fn
        self.params = init_params
        self.rng = np.random.default_rng(seed)
        self.drift = drift
        k = problem.num_learners
        self.buffer_size = cfg.buffer_size or k
        if not (1 <= self.buffer_size <= k):
            raise ValueError(f"buffer_size must be in [1, K={k}]")
        if cfg.barrier and self.buffer_size != k:
            raise ValueError(
                "the cycle barrier gates on the whole fleet: it requires "
                f"buffer_size == K (= {k}); M < K is the event-driven "
                "buffered regime"
            )
        if cfg.quorum > self.buffer_size:
            raise ValueError(
                f"quorum (= {cfg.quorum}) must be <= buffer_size "
                f"(= {self.buffer_size}): a full buffer flushes on its own"
            )
        if has_availability(drift):
            if cfg.barrier:
                raise ValueError(
                    "availability churn has no barrier regime (one offline "
                    "learner would gate every round forever); use the "
                    "event-driven modes, or the Orchestrator for the "
                    "fault-free paper scheme"
                )
            coupled = capacity_state_coupled(drift)
        else:
            coupled = is_state_coupled(drift)
        if coupled and not cfg.reallocate:
            raise ValueError(
                "state-coupled drift ties capacities to the dispatched "
                "allocations; the async engine supports it only with "
                "reallocate=True (per-block re-solves drive the state)"
            )
        # the paper-scheme allocation on the base capacities (used by the
        # barrier path so it matches Orchestrator.run bitwise); event-mode
        # dispatches go through the traced batched_policy instead.
        with span("allocate", k=k):
            self.allocation = SCHEMES[cfg.scheme](problem)
        self._alloc_cache: dict[int, tuple[np.ndarray, np.ndarray]] = {}
        self._static_alloc: tuple[np.ndarray, np.ndarray] | None = None
        self._block_masks: np.ndarray | None = None
        # fault/churn tallies of the LAST schedule built by a run method
        self.fault_counters: dict = _zero_fault_counters()
        # per-learner joule ledger of the LAST run (all-zero without an
        # EnergyModel on the problem): total joules spent per learner and
        # the count of dispatches that overran their e_budget — zero by
        # construction under scheme="kkt_energy"
        self.energy_ledger: dict = {
            "per_learner": np.zeros(k), "violations": 0,
        }

    # -- capacities & allocation --------------------------------------------
    def _block_rows(self, nblocks: int):
        """(C, K) f64 capacity rows per drift block — the SAME row source
        as ``Orchestrator._coefficient_path`` so barrier runs replay the
        orchestrator's exact re-solves. A state-coupled drift has no
        standalone row path (its rows depend on the allocations), so rows
        and per-block solves are rolled out jointly and the allocation
        cache prefilled. An availability process additionally yields the
        per-block online masks (``self._block_masks``) that gate
        dispatching: adaptive runs solve each block masked
        (``solve_rows_availability``); frozen runs dispatch the static
        base allocation whenever a learner is online, with the masks
        rolled out under that frozen allocation."""
        drift = self.drift
        self._block_masks = None
        if has_availability(drift):
            if self.cfg.reallocate:
                rows, (taus, ds), masks = solve_rows_availability(
                    self.cfg.scheme, drift, self.problem, nblocks,
                    label="capacities at drift block {}",
                )
                for b in range(nblocks):
                    self._alloc_cache[b] = (taus[b], ds[b])
                self._block_masks = masks
                return rows
            tau0, d0 = self._alloc_base()
            self._block_masks = availability_masks(
                drift, self.problem.num_learners, nblocks, tau=tau0, d=d0,
            )
            return coefficient_rows(self.problem, drift.base, nblocks)
        if is_state_coupled(drift):
            rows, (taus, ds) = solve_rows_state_coupled(
                self.cfg.scheme, drift, self.problem, nblocks,
                label="capacities at drift block {}",
            )
            for b in range(nblocks):
                self._alloc_cache[b] = (taus[b], ds[b])
            return rows
        return coefficient_rows(self.problem, drift, nblocks)

    def _solve_row(self, c2r, c1r, c0r, *, label) -> tuple[np.ndarray, np.ndarray]:
        """Fleet allocation (tau, d) on one (K,) capacity row, through the
        SAME traced-policy solve the orchestrator's re-solves use (the
        barrier-equivalence guarantee depends on sharing it)."""
        return solve_policy_row(
            self.cfg.scheme, c2r, c1r, c0r, self.problem, label=label
        )

    def _alloc_for_block(self, block: int, rows) -> tuple[np.ndarray, np.ndarray]:
        """Per-block adaptive allocation (cached per drift block)."""
        hit = self._alloc_cache.get(block)
        if hit is None:
            c2s, c1s, c0s = rows
            hit = self._solve_row(
                c2s[block], c1s[block], c0s[block],
                label=f"capacities at drift block {block}",
            )
            self._alloc_cache[block] = hit
        return hit

    def _alloc_base(self) -> tuple[np.ndarray, np.ndarray]:
        """Static allocation: solved ONCE on the base (undrifted)
        capacities — the frozen-scheduler regime a drifting run is compared
        against."""
        if self._static_alloc is None:
            tm = self.problem.time_model
            self._static_alloc = self._solve_row(
                tm.c2.astype(np.float64), tm.c1.astype(np.float64),
                tm.c0.astype(np.float64), label="base capacities",
            )
        return self._static_alloc

    # -- schedule ------------------------------------------------------------
    def _build_schedule(
        self, part: FederatedPartitioner, horizon: float, max_events: int
    ) -> _Schedule:
        """Simulate the full event system WITHOUT touching model values:
        completion times, version bookkeeping, per-dispatch shard draws and
        all aggregation coefficients. Both executors consume this verbatim,
        so their rng streams and event orders agree by construction —
        including every fault event: drops, transit delays, stragglers,
        deadline-retry redispatches and quorum timer flushes are all
        decided here, so eager and jagged replays of a faulty schedule
        stay exactly equivalent for free.

        The heap carries typed events ``(t, kind, seq, payload)`` with
        kind priority arrival < deadline < quorum, so an upload landing
        exactly at its deadline counts as arrived and an upload landing
        exactly at a quorum timeout joins the group before the check.
        Fault randomness comes from a dedicated generator seeded off the
        engine rng ONLY when ``cfg.has_faults`` — fault-free schedules
        consume the historical stream bit-for-bit (the barrier/orchestrator
        equivalence depends on this)."""
        cfg, prob = self.cfg, self.problem
        k_fleet, T = prob.num_learners, prob.T
        m = self.buffer_size
        nblocks = max(int(np.ceil(horizon / T)) + 1, 1)
        rows = self._block_rows(nblocks)
        masks = self._block_masks           # (nblocks, K) bool under churn
        # without drift every block row is the tiled base row: re-solving
        # per block would just repeat the static solve
        realloc = cfg.reallocate and self.drift is not None
        frng = (np.random.default_rng(int(self.rng.integers(2**31)))
                if cfg.has_faults else None)
        counters = _zero_fault_counters()
        # energy accounting: joules are charged at DISPATCH (the device
        # burns them whether or not the upload survives transit), against
        # the problem's static per-learner budget rows
        e_rows = prob.energy_rows()
        energy_spent = np.zeros(k_fleet)
        energy_violations = 0
        heap: list = []
        seq = 0
        server_version = 0
        arrivals: list[_Arrival] = []
        group: list[_Arrival] = []
        flush_id = 0
        next_did = 0                    # dispatch id
        dstate: dict[int, str] = {}     # did -> pending | arrived | cancelled
        open_gid = -1                   # quorum timer id of the open group
        gid_counter = 0

        def push(t: float, kind: int, payload) -> None:
            nonlocal seq
            heapq.heappush(heap, (t, kind, seq, payload))
            seq += 1

        def dispatch(k: int, t: float, attempt: int = 0) -> None:
            nonlocal next_did, energy_violations
            block = min(int(t // T), nblocks - 1)
            if masks is not None:
                # an offline learner cannot accept a task: defer the
                # dispatch to the start of its next online block (or churn
                # it out of the run if none remains within the horizon)
                b = block
                while b < nblocks and not masks[b][k]:
                    b += 1
                if b >= nblocks or b * T > horizon:
                    counters["offline_churned"] += 1
                    return
                if b != block:
                    counters["offline_deferrals"] += 1
                    block, t = b, b * T
            if realloc:
                tau_a, d_a = self._alloc_for_block(block, rows)
            else:
                tau_a, d_a = self._alloc_base()
            tau_k, d_k = int(tau_a[k]), int(d_a[k])
            if masks is not None and d_k == 0:
                # the masked solve starved this (online) learner — the
                # budget fit inside the rest of the fleet; try next block
                if (block + 1) * T <= horizon and block + 1 < nblocks:
                    dispatch(k, (block + 1) * T, attempt)
                else:
                    counters["offline_churned"] += 1
                return
            idx = part.draw_indices(d_k)
            c2, c1, c0 = (r[block, k] for r in rows)
            cost = float(c2 * tau_k * d_k + c1 * d_k + c0)
            counters["dispatches"] += 1
            energy_j = 0.0
            if e_rows is not None:
                e2k, e1k, e0k, ebk = (row[k] for row in e_rows)
                energy_j = float(e2k * tau_k * d_k + e1k * d_k + e0k)
                energy_spent[k] += energy_j
                if energy_j > ebk * (1 + 1e-9):
                    energy_violations += 1
            dropped = False
            if frng is not None:
                # fixed per-dispatch draw order: straggle -> delay -> drop
                if (cfg.straggler_rate > 0
                        and frng.random() < cfg.straggler_rate):
                    counters["stragglers"] += 1
                    cost *= cfg.straggler_factor
                if cfg.delay_rate > 0 and frng.random() < cfg.delay_rate:
                    counters["delays"] += 1
                    cost += float(frng.exponential(cfg.delay_mean))
                dropped = cfg.drop_rate > 0 and frng.random() < cfg.drop_rate
            did = next_did
            next_did += 1
            dstate[did] = "pending"
            if dropped:
                # the upload is lost in transit: no arrival event — only a
                # deadline (if armed) ever hears from this dispatch again
                counters["drops"] += 1
            else:
                push(t + cost, _EV_ARRIVE,
                     (did, k, t, server_version, tau_k, d_k, idx, attempt,
                      energy_j))
            if cfg.deadline > 0:
                push(t + cfg.deadline, _EV_DEADLINE, (did, k, attempt))

        def close_group(t_flush: float, timer: bool) -> None:
            """Flush the open buffered group (arrival-triggered at M, or a
            quorum timer firing at ``t_flush`` after the last arrival)."""
            nonlocal server_version, flush_id, group, open_gid
            taus = np.array([g.tau for g in group], float)
            ds = np.array([g.d for g in group], float)
            phi = staleness_factor(
                np.array([g.staleness for g in group], float),
                kind=cfg.staleness_fn, a=cfg.staleness_a, b=cfg.staleness_b,
            )
            # the paper's intra-buffer weighting (shared with the
            # barrier/cycle server), version-discounted by phi;
            # the renormalization absorbs staleness_weights' own
            base = (fedavg_weights(ds)
                    if cfg.aggregation == "fedavg" else
                    staleness_weights(taus, ds, gamma=cfg.staleness_gamma))
            w = base * phi
            w = w / w.sum()
            for g, wg in zip(group, w):
                g.weight = float(wg)
                g.flush_id = flush_id
            closer = group[-1]
            closer.flush = True
            closer.timer_flush = timer
            closer.flush_t = t_flush
            closer.keep = 0.0
            closer.group_weights = np.asarray(w, np.float64)
            server_version += 1
            closer.version_after = server_version
            flush_id += 1
            group = []
            open_gid = -1

        for k in range(k_fleet):
            dispatch(k, 0.0)

        while heap and len(arrivals) < max_events:
            t_e, kind, _, payload = heapq.heappop(heap)
            if t_e > horizon:
                break
            if kind == _EV_DEADLINE:
                did, k, attempt = payload
                if dstate.get(did) != "pending":
                    continue   # arrived in time (or already cancelled)
                dstate[did] = "cancelled"
                counters["deadline_misses"] += 1
                counters["retries"] += 1
                backoff = min(cfg.retry_backoff * (2.0 ** attempt),
                              cfg.retry_backoff_cap)
                dispatch(k, t_e + backoff, attempt + 1)
                continue
            if kind == _EV_QUORUM:
                gid, extended = payload
                if gid != open_gid or not group:
                    continue   # the group already flushed at M
                if len(group) >= cfg.quorum:
                    counters["quorum_flushes"] += 1
                    close_group(t_e, timer=True)
                elif not extended:
                    # below quorum: extend the deadline once before degrading
                    counters["quorum_extensions"] += 1
                    push(t_e + cfg.flush_timeout, _EV_QUORUM, (gid, True))
                else:
                    # still below quorum after the extension: flush whatever
                    # arrived instead of stalling the server forever
                    counters["quorum_degradations"] += 1
                    close_group(t_e, timer=True)
                continue
            did, k, t_disp, v_disp, tau_k, d_k, idx, attempt, e_j = payload
            if dstate.get(did) == "cancelled":
                counters["late_discards"] += 1
                continue   # its deadline already fired and retried
            dstate[did] = "arrived"
            a = _Arrival(
                seq=len(arrivals), learner=k, t=t_e, tau=tau_k, d=d_k,
                idx=idx, dispatch_t=t_disp, dispatch_version=v_disp,
                staleness=server_version - v_disp, energy=e_j,
            )
            group.append(a)
            arrivals.append(a)
            if cfg.mode == "fedasync":
                phi = staleness_factor(
                    np.array([a.staleness], float),
                    kind=cfg.staleness_fn, a=cfg.staleness_a,
                    b=cfg.staleness_b,
                )
                w = np.array([cfg.alpha]) * phi
                a.weight = float(w[0])
                a.flush_id = flush_id
                a.flush = True
                a.flush_t = t_e
                a.keep = 1.0 - float(w[0])
                a.group_weights = np.asarray(w, np.float64)
                server_version += 1
                a.version_after = server_version
                flush_id += 1
                group = []
            elif len(group) == m:
                close_group(t_e, timer=False)
            else:
                if cfg.quorum > 0 and len(group) == 1:
                    gid_counter += 1
                    open_gid = gid_counter
                    push(t_e + cfg.flush_timeout, _EV_QUORUM,
                         (open_gid, False))
                a.version_after = server_version
            dispatch(k, t_e)   # immediate redispatch with the current server

        return _Schedule(
            arrivals=arrivals, n_flushes=flush_id,
            d_cap=max([a.d for a in arrivals], default=1),
            max_tau=max([a.tau for a in arrivals] + [1]),
            counters=counters,
            energy_spent=energy_spent, energy_violations=energy_violations,
        )

    # -- shared pieces -------------------------------------------------------
    def _eval_pair(self, eval_fn, eval_batch):
        if eval_fn is None:
            return None, None, None
        if eval_batch is None:
            raise ValueError("eval_fn needs eval_batch=(x, y)")
        return (jax.jit(eval_fn), jnp.asarray(eval_batch[0]),
                jnp.asarray(eval_batch[1]))

    def _flush_row(self, ev: _Arrival, group: list[_Arrival]) -> dict:
        return _flush_row(ev, group, self.cfg.mode)

    # -- eager event loop ----------------------------------------------------
    def run(
        self,
        train: Dataset,
        horizon: float | None = None,
        *,
        cycles: int | None = None,
        eval_fn=None,
        eval_batch=None,
        max_events: int = 100_000,
    ) -> list[dict]:
        """Simulate to virtual time ``horizon`` (seconds). Returns one
        history row per server aggregation (per arrival in fedasync mode,
        per buffer flush in buffered mode). ``eval_fn`` must be
        jit-traceable with signature ``(params, x, y) -> scalar`` and is
        evaluated on ``eval_batch`` after every aggregation.

        With ``cfg.barrier=True`` the run is round-gated instead (pass
        ``cycles``, or ``horizon`` as a multiple of T) and reproduces
        ``Orchestrator.run`` exactly for the same seed.
        """
        if self.cfg.barrier:
            return self._run_barrier(
                train, horizon=horizon, cycles=cycles,
                eval_fn=eval_fn, eval_batch=eval_batch,
            )
        if horizon is None:
            raise ValueError("event mode needs a virtual-time horizon")
        # counters describe THIS run only: reset before building, so a
        # schedule build that raises cannot leave the previous run's tallies
        self.fault_counters = _zero_fault_counters()
        part = FederatedPartitioner(train, seed=int(self.rng.integers(2**31)))
        sched = self._build_schedule(part, horizon, max_events)
        self.fault_counters = sched.counters
        self.energy_ledger = {
            "per_learner": sched.energy_spent,
            "violations": sched.energy_violations,
        }
        evalj, ex, ey = self._eval_pair(eval_fn, eval_batch)
        self.params, history = _replay_eager_schedule(
            self.params, sched, train, mode=self.cfg.mode, lr=self.cfg.lr,
            num_learners=self.problem.num_learners, loss_fn=self.loss_fn,
            evalj=evalj, ex=ex, ey=ey,
        )
        return history

    # -- barrier (paper-scheme) rounds --------------------------------------
    def _run_barrier(self, train, *, horizon, cycles, eval_fn, eval_batch):
        prob, cfg = self.problem, self.cfg
        if cycles is None:
            if horizon is None:
                raise ValueError("barrier mode needs cycles or horizon")
            cycles = int(np.floor(horizon / prob.T + 1e-9))
        part = FederatedPartitioner(train, seed=int(self.rng.integers(2**31)))
        self.fault_counters = _zero_fault_counters()   # barrier is fault-free
        e_rows = prob.energy_rows()
        energy_spent = np.zeros(prob.num_learners)
        energy_violations = 0
        evalj, ex, ey = self._eval_pair(eval_fn, eval_batch)
        # without drift, per-cycle re-solves would repeat the static solve
        rows = (self._block_rows(cycles)
                if cfg.reallocate and self.drift is not None else None)
        feat = train.x.shape[1]
        history = []
        for c in range(cycles):
            if rows is not None:
                tau, d = self._alloc_for_block(c, rows)
            else:
                tau = np.asarray(self.allocation.tau)
                d = np.asarray(self.allocation.d)
            shards = part.draw(d)
            x, y, msk = _stage_shards(shards, int(d.max()), feat)
            locals_ = local_train(
                self.params, jnp.asarray(x), jnp.asarray(y), jnp.asarray(msk),
                jnp.asarray(tau), jnp.asarray(cfg.lr, jnp.float32),
                max_tau=max(int(tau.max()), 1), loss_fn=self.loss_fn,
            )
            if cfg.aggregation == "staleness":
                w = staleness_weights(tau, d, gamma=cfg.staleness_gamma)
            else:
                w = fedavg_weights(d)
            # all versions are equal under the barrier, so the version
            # discount is exactly 1.0 for every learner and the weights
            # reduce to the orchestrator's (bitwise — no factor applied)
            self.params = aggregate(locals_, jnp.asarray(w))
            if e_rows is not None:
                e2r, e1r, e0r, ebr = e_rows
                e_c = np.where(d > 0, e2r * tau * d + e1r * d + e0r, 0.0)
                energy_spent += e_c
                energy_violations += int(np.sum(e_c > ebr * (1 + 1e-9)))
            else:
                e_c = np.zeros(prob.num_learners)
            rec = {
                "event": c,
                "t": (c + 1) * prob.T,
                "mode": "cycle",
                "server_version": c + 1,
                "learners": list(range(prob.num_learners)),
                "tau": tau.copy(),
                "d": d.copy(),
                "staleness_list": [0] * prob.num_learners,
                "version_staleness_max": 0,
                "version_staleness_mean": 0.0,
                "weights": np.asarray(w, np.float64),
                "keep": 0.0,
                "energy": e_c,
                "max_staleness": max_staleness(tau),
                "avg_staleness": avg_staleness(tau),
                "cycle": c,
                "elapsed_s": (c + 1) * prob.T,
                "wall_clock_s": prob.T,
            }
            if evalj is not None:
                rec["accuracy"] = float(evalj(self.params, ex, ey))
            history.append(rec)
        self.energy_ledger = {
            "per_learner": energy_spent, "violations": energy_violations,
        }
        return history

    # -- shared one-XLA-program execution over event groups -------------------
    def _run_groups(self, groups, sched: _Schedule, train: Dataset, *,
                    eval_fn, eval_batch, use_pallas: bool,
                    interpret: bool, seg_batch=None) -> list[dict]:
        self.params, history = _run_group_program(
            self.params, groups, sched, train, mode=self.cfg.mode,
            lr=self.cfg.lr, num_learners=self.problem.num_learners,
            loss_fn=self.loss_fn, eval_fn=eval_fn, eval_batch=eval_batch,
            use_pallas=use_pallas, interpret=interpret, seg_batch=seg_batch,
        )
        return history


    # -- event-indexed (jagged) device-resident fast path ---------------------
    def run_events(
        self,
        train: Dataset,
        horizon: float,
        *,
        eval_fn=None,
        eval_batch=None,
        use_pallas: bool = False,
        interpret: bool = False,
        seg_batch=None,
        max_events: int = 100_000,
    ) -> list[dict]:
        """The eager event loop as ONE jitted ``lax.scan`` over
        **event-indexed (jagged) segments** — the exact device-resident
        fast path.

        Arrivals are grouped by flush structure (``_event_segments``), not
        onto a time grid: one scan step per fedasync arrival / buffered
        flush group (split at learner repeats). Exactness needs no grid
        resolution, so near-tie and exactly-tying completion times — the
        norm under the paper's KKT allocator, which equalizes finish times
        — replay exactly, where ``run_bucketed`` needed an exploding
        ``num_buckets`` or lossy ``strict=False`` merging.

        Parameters
        ----------
        train : Dataset the shard draws index into (same rng discipline as
            ``run`` — the two paths share one host schedule).
        horizon : float — virtual-time horizon in seconds.
        eval_fn : optional jit-traceable ``(params, x, y) -> scalar``,
            evaluated inside the scan after every flush on ``eval_batch``.
        eval_batch : ``(x, y)`` arrays; required with ``eval_fn``.
        use_pallas, interpret : route each scan step's whole
            train+accumulate+flush body through the ``ops.train_agg_step``
            Pallas megakernel (``interpret=True`` emulates it on CPU).
        seg_batch : optional int — sub-batch each jagged segment into
            chunks of at most this many arrivals, staged COMPACTLY over
            arrival slots (``(S', seg_batch, d_cap, F)`` with a
            slot-to-learner gather) instead of densely over all K
            learners. Buffered runs with large flush quorum M keep the
            per-step working set at ``seg_batch`` learner rows rather
            than paying widest-segment padding on every step; prefix
            chunks are accumulate-only, the closing chunk carries the
            flush. Same history rows; params match the dense staging to
            float tolerance (the accumulate folds in chunks).
        max_events : schedule-length safety cap.

        Returns
        -------
        One history row per server aggregation, identical to ``run``'s for
        the same seed: versions, staleness lists and weights bitwise (they
        come from the shared schedule); aggregated params match to float
        tolerance (the scan composes the same contractions in a different
        reduction order).
        """
        if self.cfg.barrier:
            raise ValueError(
                "the barrier (cycle-gated) regime is already one XLA "
                "program via Orchestrator.run_fused; run_events is the "
                "event-driven fast path"
            )
        self.fault_counters = _zero_fault_counters()   # this run's tallies only
        part = FederatedPartitioner(train, seed=int(self.rng.integers(2**31)))
        with span("schedule") as sp:
            sched = self._build_schedule(part, horizon, max_events)
            segments = _event_segments(sched.arrivals)
            sp.set_metadata(arrivals=len(sched.arrivals))
        self.fault_counters = sched.counters
        self.energy_ledger = {
            "per_learner": sched.energy_spent,
            "violations": sched.energy_violations,
        }
        if not segments:
            return []
        return self._run_groups(
            segments, sched, train, eval_fn=eval_fn, eval_batch=eval_batch,
            use_pallas=use_pallas, interpret=interpret, seg_batch=seg_batch,
        )

    # -- bucketed device-resident fast path (legacy fixed grid) ---------------
    def run_bucketed(
        self,
        train: Dataset,
        horizon: float,
        num_buckets: int,
        *,
        eval_fn=None,
        eval_batch=None,
        strict: bool = True,
        use_pallas: bool = False,
        interpret: bool = False,
        max_events: int = 100_000,
    ) -> list[dict]:
        """LEGACY fixed-grid twin of ``run_events``: the eager event loop
        as ONE jitted ``lax.scan`` over a ``num_buckets`` uniform time
        grid (see module docstring). History rows are identical to
        ``run``'s for the same seed (same host schedule); the aggregation
        sequence matches to float tolerance whenever each bucket holds at
        most one arrival — the guards below raise (with a remedy) for
        grids too coarse to be faithful at all.

        Prefer ``run_events``: it groups by event index instead of time,
        so it is exact on the near-tie/tied schedules this grid cannot
        represent, needs no ``num_buckets``/``strict`` tuning, and stages
        a smaller tensor (S segments vs H >= S buckets). This path is
        kept for grid-vs-jagged benchmarking (``benchmarks/async_bench``).

        Parameters mirror ``run_events`` plus ``num_buckets`` (grid size)
        and ``strict`` (raise on multi-arrival buckets vs merge fedasync
        collisions into composed weights — exact aggregation, approximated
        mid-bucket redispatch)."""
        if self.cfg.barrier:
            raise ValueError(
                "the barrier (cycle-gated) regime is already one XLA "
                "program via Orchestrator.run_fused; run_bucketed is the "
                "event-driven fast path"
            )
        if num_buckets < 1:
            raise ValueError("num_buckets must be >= 1")
        self.fault_counters = _zero_fault_counters()   # this run's tallies only
        part = FederatedPartitioner(train, seed=int(self.rng.integers(2**31)))
        sched = self._build_schedule(part, horizon, max_events)
        self.fault_counters = sched.counters
        self.energy_ledger = {
            "per_learner": sched.energy_spent,
            "violations": sched.energy_violations,
        }

        h = num_buckets
        width = horizon / h
        buckets: list[list[_Arrival]] = [[] for _ in range(h)]
        for a in sched.arrivals:
            if a.flush_id < 0:
                continue   # never-flushed trailing buffer: unobservable
            buckets[min(int(a.t / width), h - 1)].append(a)

        # guards: configurations the grid cannot represent at all
        for b, evs in enumerate(buckets):
            learners = [a.learner for a in evs]
            if len(set(learners)) < len(learners):
                raise ValueError(
                    f"bucket {b} holds two arrivals of the same learner — "
                    "its second task would need training before the bucket "
                    "ends; increase num_buckets"
                )
            if strict and len(evs) > 1:
                raise ValueError(
                    f"bucket {b} holds {len(evs)} arrivals; increase "
                    "num_buckets for an exact replay, pass strict=False "
                    "to merge them (exact aggregation via composed weights; "
                    "mid-bucket redispatches then see the bucket-end "
                    "server), or use run_events (exact without a grid)"
                )
            if self.cfg.mode == "buffered":
                # fedasync flushes per arrival and merges exactly via the
                # composed weights below; buffered groups cannot straddle a
                # bucket boundary mid-bucket
                tie = len({a.t for a in evs}) < len(evs)
                remedy = (
                    "arrival times tie exactly, so NO grid separates them "
                    "— use run_events (event-indexed segments replay tied "
                    "buffered schedules exactly)"
                    if tie else "increase num_buckets (or use run_events)"
                )
                nflush = sum(a.flush for a in evs)
                if nflush > 1:
                    raise ValueError(
                        f"bucket {b} holds {nflush} buffer flushes; {remedy}"
                    )
                if nflush == 1 and not evs[-1].flush:
                    raise ValueError(
                        f"a buffer flush splits bucket {b} (arrivals of "
                        f"the next group share it); {remedy}"
                    )

        return self._run_groups(
            buckets, sched, train, eval_fn=eval_fn, eval_batch=eval_batch,
            use_pallas=use_pallas, interpret=interpret,
        )


def _compose_group_row(evs, mode: str):
    """Per-group flush coefficients: the composed keep factor, the flush
    flag, and one contraction weight per arrival (arrival order).
    fedasync groups compose their sequential mixes into one contraction
    server' = prod(1-b_i) * server + sum_i b_i prod_{j>i}(1-b_j) w_i —
    for single-arrival groups (always, on the jagged path) bitwise the
    schedule's own per-arrival coefficients."""
    if mode == "fedasync":
        betas = np.array([a.weight for a in evs])
        suffix = np.cumprod((1.0 - betas)[::-1])[::-1]
        comp = betas * np.concatenate([suffix[1:], [1.0]])
        return float(suffix[0]), 1.0, comp
    comp = np.array([a.weight for a in evs])
    if evs[-1].flush:
        return float(evs[-1].keep), 1.0, comp
    return 1.0, 0.0, comp


def _stage_groups_dense(groups, train: Dataset, *, mode: str, k_fleet: int,
                        d_cap: int, feat: int):
    """Stage one scan step per event group over the full (n, K, d_cap, F)
    learner grid (``_bucketed_events`` layout)."""
    n = len(groups)
    xs = np.zeros((n, k_fleet, d_cap, feat), np.float32)
    ys = np.zeros((n, k_fleet, d_cap), np.int32)
    ms = np.zeros((n, k_fleet, d_cap), np.float32)
    tau_g = np.zeros((n, k_fleet), np.int32)
    wc = np.zeros((n, k_fleet), np.float32)
    keepv = np.ones(n, np.float32)
    fflag = np.zeros(n, np.float32)
    rmask = np.zeros((n, k_fleet), bool)
    pmask = np.zeros((n, k_fleet), bool)
    for i, evs in enumerate(groups):
        if not evs:
            continue
        keepv[i], fflag[i], comp = _compose_group_row(evs, mode)
        for a, w_i in zip(evs, comp):
            wc[i, a.learner] = w_i
        for a in evs:
            k = a.learner
            rmask[i, k] = True
            # a timer-flush closer redispatched BEFORE the timer fired,
            # so it takes the pre-flush server like any accumulate
            # upload; only arrival-triggered closers see the post-flush
            pmask[i, k] = a.flush and not a.timer_flush
            tau_g[i, k] = a.tau
            xs[i, k, : a.d] = train.x[a.idx]
            ys[i, k, : a.d] = train.y[a.idx]
            ms[i, k, : a.d] = 1.0
    return xs, ys, ms, tau_g, wc, keepv, fflag, rmask, pmask


def _stage_groups_compact(groups, train: Dataset, *, mode: str, slots: int,
                          d_cap: int, feat: int):
    """Stage over ARRIVAL SLOTS instead of learner rows: (n, slots,
    d_cap, F) plus a slot-to-learner ``ids`` map — the sub-batched
    ``_bucketed_events_compact`` layout. Padding slots point at learner 0
    with tau = 0, weight 0, mask 0 (exact no-ops)."""
    n = len(groups)
    xs = np.zeros((n, slots, d_cap, feat), np.float32)
    ys = np.zeros((n, slots, d_cap), np.int32)
    ms = np.zeros((n, slots, d_cap), np.float32)
    tau_g = np.zeros((n, slots), np.int32)
    wc = np.zeros((n, slots), np.float32)
    keepv = np.ones(n, np.float32)
    fflag = np.zeros(n, np.float32)
    ids = np.zeros((n, slots), np.int32)
    rms = np.zeros((n, slots), bool)
    pms = np.zeros((n, slots), bool)
    for i, evs in enumerate(groups):
        if not evs:
            continue
        keepv[i], fflag[i], comp = _compose_group_row(evs, mode)
        wc[i, : len(evs)] = comp
        for j, a in enumerate(evs):
            ids[i, j] = a.learner
            rms[i, j] = True
            pms[i, j] = a.flush and not a.timer_flush
            tau_g[i, j] = a.tau
            xs[i, j, : a.d] = train.x[a.idx]
            ys[i, j, : a.d] = train.y[a.idx]
            ms[i, j, : a.d] = 1.0
    return xs, ys, ms, tau_g, wc, keepv, fflag, ids, rms, pms


_STAGING_CACHE: "dict[tuple, tuple]" = {}
_STAGING_STATS = {"stages": 0, "hits": 0}
_STAGING_CACHE_MAX = 4


def staging_cache_stats() -> dict:
    """Copy of the group-staging cache counters (tests/diagnostics)."""
    return dict(_STAGING_STATS)


def clear_staging_cache() -> None:
    _STAGING_CACHE.clear()
    _STAGING_STATS["stages"] = 0
    _STAGING_STATS["hits"] = 0


def _schedule_digest(groups, *, mode: str, k_fleet: int, d_cap: int,
                     feat: int, seg_batch) -> str:
    """Digest of everything the staged tensors depend on besides the
    dataset contents: the staging geometry and, per arrival, the fields
    the staging loops read (learner, tau, d, weight, flush structure,
    sample indices)."""
    h = hashlib.sha1()
    h.update(repr((mode, k_fleet, d_cap, feat, seg_batch)).encode())
    for i, evs in enumerate(groups):
        h.update(b"|g%d" % i)
        for a in evs:
            h.update(repr((a.learner, int(a.tau), int(a.d), float(a.weight),
                           bool(a.flush), bool(a.timer_flush),
                           float(a.keep))).encode())
            h.update(np.ascontiguousarray(a.idx).tobytes())
    return h.hexdigest()


def _staged_group_arrays(groups, train: Dataset, *, mode: str, k_fleet: int,
                         d_cap: int, feat: int, seg_batch):
    """The host-staging front of the group program, cached keyed on
    (dataset identity, schedule digest): repeated replays of one schedule
    — parameter sweeps, golden-trace replays, the multi-model engine's
    per-model reruns — skip re-staging the full (S, K, d_cap, F) tensor
    and pay it once per distinct schedule. Runs under a ``fed.stage``
    span whose stats are the bytes staged (0 on a hit) and ``hit``."""
    with span("stage") as sp:
        key = (id(train), _schedule_digest(
            groups, mode=mode, k_fleet=k_fleet, d_cap=d_cap, feat=feat,
            seg_batch=seg_batch,
        ))
        hit = _STAGING_CACHE.get(key)
        # the entry pins the dataset object, so its id cannot be recycled
        # while the entry lives — an identity check makes that explicit
        if hit is not None and hit[0] is train:
            _STAGING_STATS["hits"] += 1
            sp.set_metadata(bytes=0, hit=1)
            return hit[1]
        _STAGING_STATS["stages"] += 1
        if seg_batch is None:
            staged = _stage_groups_dense(
                groups, train, mode=mode, k_fleet=k_fleet, d_cap=d_cap,
                feat=feat,
            )
        else:
            staged = _stage_groups_compact(
                groups, train, mode=mode, slots=seg_batch, d_cap=d_cap,
                feat=feat,
            )
        while len(_STAGING_CACHE) >= _STAGING_CACHE_MAX:
            _STAGING_CACHE.pop(next(iter(_STAGING_CACHE)))
        _STAGING_CACHE[key] = (train, staged)
        sp.set_metadata(bytes=sum(a.nbytes for a in staged), hit=0)
        return staged


def _run_group_program(params, groups, sched: _Schedule, train: Dataset, *,
                       mode: str, lr: float, num_learners: int, loss_fn,
                       eval_fn, eval_batch, use_pallas: bool,
                       interpret: bool, seg_batch=None):
    """Stage one scan step per event group, run the whole campaign as
    ONE jitted program (``_bucketed_events``), and replay the history
    rows — THE shared back half of ``run_events`` (jagged segments)
    and ``run_bucketed`` (grid buckets), so the two scan paths cannot
    diverge in staging semantics. Module-level so the multi-model engine's
    per-model replays run the identical program. Returns
    ``(params, history)``.

    Empty groups are allowed (empty grid buckets; runtime-skipped scan
    steps). fedasync groups may hold several arrivals (grid
    ``strict=False`` merging): their sequential mixes are composed
    into one contraction — for single-arrival groups (always, on the
    jagged path) the composition degenerates to the schedule's own
    per-arrival coefficients bitwise. The post-step accuracy is
    attributed to the group's LAST flush row (earlier merged flushes
    have no mid-step eval point).

    ``seg_batch`` sub-batches each group into chunks of at most that many
    arrivals and runs the slot-compact program
    (``_bucketed_events_compact``): prefix chunks are accumulate-only,
    the closing chunk carries the group's flush, so a buffered run's
    per-step working set is ``seg_batch`` learner rows instead of the
    widest segment padded over all K."""
    if eval_fn is not None and eval_batch is None:
        raise ValueError("eval_fn needs eval_batch=(x, y)")
    if seg_batch is not None:
        if seg_batch < 1:
            raise ValueError("seg_batch must be >= 1")
        groups = [evs[j: j + seg_batch]
                  for evs in groups
                  for j in range(0, max(len(evs), 1), seg_batch)]
    k_fleet = num_learners
    feat = train.x.shape[1]
    d_cap, max_tau = sched.d_cap, sched.max_tau
    staged = _staged_group_arrays(
        groups, train, mode=mode, k_fleet=k_fleet, d_cap=d_cap, feat=feat,
        seg_batch=seg_batch,
    )

    disp0 = jax.tree_util.tree_map(
        lambda p: jnp.broadcast_to(p, (k_fleet,) + p.shape),
        params,
    )
    accum0 = jax.tree_util.tree_map(jnp.zeros_like, params)
    ex, ey = eval_batch if eval_fn is not None else (None, None)
    # the staged blocks in the program's argument order, then lr and eval
    staged_d = to_device(*staged, np.asarray(lr, np.float32), ex, ey)
    program = _bucketed_events if seg_batch is None else _bucketed_events_compact
    with span("dispatch"):
        params, accs = program(
            params, disp0, accum0, *staged_d,
            max_tau=max_tau, loss_fn=loss_fn, eval_fn=eval_fn,
            use_pallas=use_pallas, interpret=interpret,
        )
    with span("wait"):
        accs = np.asarray(accs)

    with span("history"):
        history: list[dict] = []
        group: list[_Arrival] = []
        for i, evs in enumerate(groups):
            flushes = [a for a in evs if a.flush]
            for a in evs:
                group.append(a)
                if a.flush:
                    rec = _flush_row(a, group, mode)
                    if eval_fn is not None and a is flushes[-1]:
                        rec["accuracy"] = float(accs[i])
                    history.append(rec)
                    group = []
    return params, history


@functools.partial(
    jax.jit,
    static_argnames=("max_tau", "loss_fn", "eval_fn", "use_pallas", "interpret"),
)
def _bucketed_events(server, disp, accum, xs, ys, ms, taus, wcs, keeps, fs,
                     rmask, pmask, lr, eval_x, eval_y, *, max_tau: int,
                     loss_fn, eval_fn, use_pallas: bool, interpret: bool):
    """One XLA program for H scan steps (time buckets of ``run_bucketed``
    or jagged event segments of ``run_events``) of the async event system:
    scan(train carried dispatch models -> fold arrivals into the weighted
    accumulator -> masked flush into the server -> masked redispatch). The
    initial server buffer is NOT donated on purpose: engines may share the
    caller's init_params object (the scan carry is double-buffered by XLA
    either way).

    xs: (H, K, d_cap, F); ys/ms: (H, K, d_cap); taus/wcs: (H, K);
    keeps/fs: (H,); rmask/pmask: (H, K) bool. Per step the whole
    train+accumulate+flush body is one ``ops.train_agg_step`` call
    (= ``local_train_stacked`` then server' = fed_agg([server, A'],
    [keep, f]) with A' = fed_agg([A, locals], [1, w_c]); the Pallas
    megakernel under ``use_pallas=True``) — f = 0 steps leave the server
    untouched, f = 1 steps apply a flush whose coefficients the host
    composed to be exactly the eager loop's sequential mixes.

    Redispatch is mask-split to mirror the eager loop's timing exactly:
    arrivals in ``pmask`` (flush arrivals — all of fedasync, the buffer
    closer in buffered mode) redispatch with the POST-flush server; the
    remaining ``rmask`` arrivals (buffered accumulate uploads, which the
    eager loop redispatches before any flush touches the server)
    redispatch with the step's incoming PRE-flush server."""
    from repro.kernels import ops

    def one_bucket(carry, inp):
        x, y, m, tau, w, keep, f, rm, pm = inp

        def process(op):
            server, dp, acc = op
            server1, acc2 = ops.train_agg_step(
                dp, x, y, m, tau, w, lr, loss_fn=loss_fn, max_tau=max_tau,
                server=server, acc=acc, keep=keep, flush=f,
                use_pallas=use_pallas, interpret=interpret,
            )
            pre = rm & jnp.logical_not(pm)
            dp1 = jax.tree_util.tree_map(
                lambda old, new_post, new_pre: jnp.where(
                    pm.reshape((-1,) + (1,) * new_post.ndim),
                    new_post[None],
                    jnp.where(
                        pre.reshape((-1,) + (1,) * new_pre.ndim),
                        new_pre[None], old,
                    ),
                ),
                dp, server1, server,
            )
            # only flush buckets' accuracies are ever read back (buffered
            # accumulation buckets would be dead eval compute)
            with jax.named_scope("eval"):
                a_out = (
                    jax.lax.cond(
                        f > 0,
                        lambda s: eval_fn(s, eval_x, eval_y).astype(jnp.float32),
                        lambda s: jnp.float32(0),
                        server1,
                    )
                    if eval_fn is not None else jnp.float32(0)
                )
            return (server1, dp1, acc2), a_out

        def skip(op):
            return op, jnp.float32(0)

        # empty buckets skip training entirely at RUNTIME (scan-level cond
        # is real branching, not a select) — a fine exact grid costs only
        # its active buckets
        return jax.lax.cond(jnp.any(rm), process, skip, carry)

    (server, disp, accum), accs = jax.lax.scan(
        one_bucket, (server, disp, accum), (xs, ys, ms, taus, wcs, keeps, fs,
                                            rmask, pmask)
    )
    return server, accs


@functools.partial(
    jax.jit,
    static_argnames=("max_tau", "loss_fn", "eval_fn", "use_pallas", "interpret"),
)
def _bucketed_events_compact(server, disp, accum, xs, ys, ms, taus, wcs,
                             keeps, fs, ids, rms, pms, lr, eval_x, eval_y, *,
                             max_tau: int, loss_fn, eval_fn,
                             use_pallas: bool, interpret: bool):
    """Slot-compact twin of ``_bucketed_events`` for sub-batched jagged
    segments: each scan step trains only its <= seg_batch arrival SLOTS —
    ``ids`` gathers the slots' dispatch models out of the (K, ...) carry
    and the redispatch decisions scatter back — so the per-step working
    set is bounded by the slot count however wide the fleet or the widest
    flush group is. Padding slots carry tau = 0, weight 0, mask 0 (exact
    no-ops on a gathered copy of learner 0). xs: (H, B, d_cap, F);
    ys/ms: (H, B, d_cap); taus/wcs/ids: (H, B); rms/pms: (H, B) bool;
    keeps/fs: (H,). Flush/redispatch semantics match ``_bucketed_events``
    row for row; only the accumulate fold is chunked, so params agree to
    float tolerance."""
    from repro.kernels import ops

    k_fleet = jax.tree_util.tree_leaves(disp)[0].shape[0]

    def one_bucket(carry, inp):
        x, y, m, tau, w, keep, f, idr, rm, pm = inp

        def process(op):
            server, dp, acc = op
            sub = jax.tree_util.tree_map(
                lambda leaf: jnp.take(leaf, idr, axis=0), dp
            )
            server1, acc2 = ops.train_agg_step(
                sub, x, y, m, tau, w, lr, loss_fn=loss_fn, max_tau=max_tau,
                server=server, acc=acc, keep=keep, flush=f,
                use_pallas=use_pallas, interpret=interpret,
            )
            # scatter the slots' redispatch decisions to learner rows
            # (<= 1 arrival per learner per chunk, so add == or)
            post_k = jnp.zeros((k_fleet,), jnp.int32).at[idr].add(
                pm.astype(jnp.int32)) > 0
            pre_k = jnp.zeros((k_fleet,), jnp.int32).at[idr].add(
                (rm & jnp.logical_not(pm)).astype(jnp.int32)) > 0
            dp1 = jax.tree_util.tree_map(
                lambda old, new_post, new_pre: jnp.where(
                    post_k.reshape((-1,) + (1,) * new_post.ndim),
                    new_post[None],
                    jnp.where(
                        pre_k.reshape((-1,) + (1,) * new_pre.ndim),
                        new_pre[None], old,
                    ),
                ),
                dp, server1, server,
            )
            with jax.named_scope("eval"):
                a_out = (
                    jax.lax.cond(
                        f > 0,
                        lambda s: eval_fn(s, eval_x, eval_y).astype(jnp.float32),
                        lambda s: jnp.float32(0),
                        server1,
                    )
                    if eval_fn is not None else jnp.float32(0)
                )
            return (server1, dp1, acc2), a_out

        def skip(op):
            return op, jnp.float32(0)

        return jax.lax.cond(jnp.any(rm), process, skip, carry)

    (server, disp, accum), accs = jax.lax.scan(
        one_bucket, (server, disp, accum),
        (xs, ys, ms, taus, wcs, keeps, fs, ids, rms, pms),
    )
    return server, accs


def summarize_async_history(history: list[dict], *,
                            counters: dict | None = None,
                            energy: dict | None = None) -> dict:
    """Fleet-level summary of an async run: the version-staleness profile
    (mean/max AND p50/p90/p99 quantiles) over all aggregated uploads,
    aggregation counts, the virtual time span, and — under ``counters``
    (pass ``engine.fault_counters``) — the fault tallies of the schedule
    (drops, retries, deadline misses, quorum degradations, ...). The
    ``faults`` dict always carries every ``FAULT_COUNTERS`` key so
    consumers need no presence checks; without ``counters`` it is all
    zeros. Barrier (cycle) rows carry zero version staleness by
    construction.

    The ``energy`` section summarizes the joule ledger: per-upload
    dispatch energies from the history rows (total, p50/p99), plus —
    under ``energy`` (pass ``engine.energy_ledger``) — the engine's
    per-learner joule totals over ALL dispatches (aggregated or not) and
    the count of dispatches that overran their ``e_budget``. The
    violation count is zero by construction under ``scheme="kkt_energy"``
    (the policy caps every (tau, d) inside the budget); an energy-blind
    scheme under finite budgets reports its overruns here. All keys are
    always present (zeros without an ``EnergyModel``)."""
    stal: list[int] = []
    joules: list[float] = []
    for rec in history:
        stal.extend(rec.get("staleness_list", [0] * len(rec["learners"])))
        joules.extend(np.atleast_1d(rec.get("energy", [])).tolist())
    jarr = np.asarray(joules, np.float64)
    ledger = energy or {}
    per_learner = ledger.get("per_learner")
    return {
        "aggregations": len(history),
        "uploads": int(sum(len(r["learners"]) for r in history)),
        "virtual_time": float(history[-1]["t"]) if history else 0.0,
        "staleness": version_staleness_profile(np.asarray(stal)),
        "final_accuracy": history[-1].get("accuracy") if history else None,
        "faults": {**_zero_fault_counters(), **(counters or {})},
        "energy": {
            "joules_total": float(jarr.sum()) if jarr.size else 0.0,
            "joules_p50": float(np.percentile(jarr, 50)) if jarr.size else 0.0,
            "joules_p99": float(np.percentile(jarr, 99)) if jarr.size else 0.0,
            "per_learner": (np.asarray(per_learner, np.float64)
                            if per_learner is not None else None),
            "violations": int(ledger.get("violations", 0)),
        },
    }
