"""Two-tier fleet-of-fleets federation over the device mesh.

Everything below ``fed/fleet.py`` simulates ONE K<=10 edge fleet. This
module is the population-scale layer: F fleets x K learners live as sharded
fleet tensors — an ``(F, K)`` ``BatchedProblems`` struct for the allocation
problems/capacities, ``(F, K, d_cap, feat)`` staged sample tensors, and a
params-per-fleet pytree with a leading F axis — laid out over a mesh from
``launch.mesh`` with the ``sharding.rules.FLEET_RULES`` logical axis. Each
global round is ONE jitted XLA program wrapped in ``jax.shard_map``:

  1. every fleet runs its paper-scheme cycle — masked ``local_train`` to
     the fleet-wide max tau, vmapped over the local fleet shard, then the
     fleet server's staleness-weighted aggregation (``aggregate``'s exact
     contraction, vmapped);
  2. the global server merges the round's SAMPLED fleets (FedAST-style
     partial participation, arxiv 2406.00302): each sampled fleet's model
     is weighted by its data volume times the version-staleness discount
     ``staleness_factor(g - pull_version)`` — fleets that trained on an
     old pull are trusted less on arrival — normalized by a ``psum`` over
     the mesh axes the fleet dim is split over, and mixed into the global
     model at ``server_mix``;
  3. the next dispatch is solved for the sampled fleets with ONE
     ``batched_policy`` call on the sampling-masked ``(F, K)`` problem
     tensors (``apply_sampling_mask``: a sampled-out fleet is exactly an
     all-offline fleet is exactly a row of padded slots), while unsampled
     fleets keep training on their stale dispatch.

Exactness discipline (pinned by ``tests/test_fleet.py``): with F = 1, full
participation, and a 1-device mesh, every stage above degenerates bitwise
to the single-fleet path — the vmap has one slice, the merge weight is
exactly 1.0, ``server_mix=1`` selects the merged model unblended — so the
fleet engine reproduces ``Orchestrator.run`` results exactly, record for
record. Fleet f's partitioner seed is drawn from the engine rng in fleet
order, so fleet 0 consumes the same ``(seed, draw-index)``-keyed shard
draws the orchestrator's partitioner does.

Scale: ``host_mesh()`` spans every device: the (2, 4) mesh under
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` (the fleet-scale CI
step), (2, 2) on a four-chip TPU host, one device elsewhere;
``benchmarks/fleet_scale`` drives 10^4 trained and 10^6 solved learners per
virtual-time unit through the same two programs.
"""

from __future__ import annotations

import dataclasses
import functools

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core.solver_batched import (
    BatchedProblems,
    TRACED_POLICIES,
    apply_active_mask,
    apply_sampling_mask,
    batched_avg_staleness,
    batched_max_staleness,
    batched_policy,
    cross_model_weights,
)
from repro.core.staleness import STALENESS_FNS, staleness_factor
from repro.data.pipeline import Dataset, FederatedPartitioner
from repro.fed.orchestrator import ENERGY_SCHEMES, _weights_traced, local_train
from repro.launch.mesh import host_mesh
from repro.sharding.rules import fleet_partition_axes

__all__ = ["FleetConfig", "FleetEngine", "build_fleet_problems"]

# fold-in stream tag for the per-round fleet-sampling keys (disjoint from
# partitioner draw keys, which live under per-fleet seeds)
_SAMPLE_STREAM = 0x5AB5


@dataclasses.dataclass(frozen=True)
class FleetConfig:
    """Knobs of the two-tier engine (per-fleet problem knobs live in the
    ``BatchedProblems`` struct passed to ``FleetEngine``)."""

    lr: float = 0.1
    scheme: str = "kkt_sai"            # traced policy for the fleet solves
    aggregation: str = "staleness"     # intra-fleet: staleness | fedavg
    staleness_gamma: float = 1.0
    participation: float = 1.0         # fraction of fleets sampled per round
    server_mix: float = 1.0            # global-server mixing rate (1 = replace)
    staleness_fn: str = "poly"         # cross-tier discount on stale fleets
    staleness_a: float = 0.5
    staleness_b: float = 4.0

    def __post_init__(self):
        if self.scheme not in TRACED_POLICIES:
            raise ValueError(
                f"the fleet engine solves through batched_policy; scheme "
                f"{self.scheme!r} has none ({' | '.join(TRACED_POLICIES)})"
            )
        if self.aggregation not in ("staleness", "fedavg"):
            raise ValueError(f"unknown aggregation {self.aggregation!r}")
        if not (0.0 < self.participation <= 1.0):
            raise ValueError("participation must be in (0, 1]")
        if not (0.0 < self.server_mix <= 1.0):
            raise ValueError("server_mix must be in (0, 1]")
        if self.staleness_fn not in STALENESS_FNS:
            raise ValueError(
                f"unknown staleness fn {self.staleness_fn!r}: "
                + " | ".join(STALENESS_FNS)
            )


def build_fleet_problems(
    f: int,
    k: int = 8,
    *,
    T: float = 6.0,
    total_samples: int = 60,
    seed: int = 0,
    jitter: float = 0.25,
) -> BatchedProblems:
    """An (F, K) fleet population around the hand-tuned spread coefficients:
    every draw comes from one generator keyed by ``seed`` drawing whole
    (F, K) tensors at once (no per-fleet iteration-order dependence), so
    the population is reproducible across processes."""
    base_c2 = np.array([0.050, 0.031, 0.022, 0.045, 0.027, 0.038, 0.019, 0.042])
    base_c1 = np.array([0.004, 0.006, 0.003, 0.005, 0.002, 0.004, 0.006, 0.003])
    base_c0 = np.array([0.40, 0.55, 0.30, 0.25, 0.45, 0.35, 0.50, 0.28])
    if k > base_c2.size:
        reps = -(-k // base_c2.size)
        base_c2, base_c1, base_c0 = (
            np.tile(a, reps) for a in (base_c2, base_c1, base_c0)
        )
    rng = np.random.default_rng(np.random.SeedSequence((seed, f, k)))
    scale = np.exp(jitter * rng.standard_normal((3, f, k)))
    c2 = base_c2[:k][None] * scale[0]
    c1 = base_c1[:k][None] * scale[1]
    c0 = base_c0[:k][None] * scale[2]
    return BatchedProblems(
        c2=c2, c1=c1, c0=c0,
        T=np.full(f, float(T)),
        total=np.full(f, int(total_samples), np.int64),
        d_lo=np.full((f, k), float(max(1, total_samples // (2 * k)))),
        d_hi=np.full((f, k), float(min(total_samples, 2 * total_samples // k))),
        valid=np.ones((f, k), bool),
    )


def _fleet_spec(axes: tuple[str, ...], extra: int = 0) -> P:
    """PartitionSpec for a tensor whose LEADING dim is the fleet axis and
    whose remaining ``extra`` dims are per-fleet payload (unsharded)."""
    if not axes:
        lead = None
    elif len(axes) == 1:
        lead = axes[0]
    else:
        lead = axes
    return P(lead, *([None] * extra))


def _tree_fleet_specs(tree, axes):
    return jax.tree_util.tree_map(
        lambda leaf: _fleet_spec(axes, extra=leaf.ndim - 1), tree
    )


def _psum(x, axes):
    return jax.lax.psum(x, axes) if axes else x


def _wsum(leaf, w):
    """``core.aggregation.aggregate``'s exact weighted contraction over the
    leading axis (bitwise-shared so the fleet server matches the eager
    orchestrator's aggregation)."""
    ww = w.reshape((-1,) + (1,) * (leaf.ndim - 1)).astype(leaf.dtype)
    return (leaf * ww).sum(axis=0)


@functools.partial(
    jax.jit, static_argnames=("scheme", "mesh", "fleet_axes"),
)
def _fleet_solve(c2, c1, c0, T, total, d_lo, d_hi, valid, sampled, *en,
                 scheme: str, mesh, fleet_axes):
    """ONE ``batched_policy`` call for every fleet's (tau, d), sharded over
    the fleet axis under ``shard_map``; sampled-out fleets get the padded
    -slot projection and solve to zeros. Run under ``jax.enable_x64`` with f64
    rows for exact integer allocations. Energy-aware schemes take four
    trailing (F, K) rows — ``(e2, e1, e0, e_budget)`` — sharded like the
    other per-learner tensors."""
    policy = batched_policy(scheme)

    def body(c2, c1, c0, T, total, d_lo, d_hi, valid, sampled, *en):
        tot_m, lo_m, hi_m, valid_m = apply_sampling_mask(
            total, d_lo, d_hi, valid, sampled
        )
        if en:
            return policy(c2, c1, c0, T, tot_m, lo_m, hi_m, valid_m, en)
        return policy(c2, c1, c0, T, tot_m, lo_m, hi_m, valid_m)

    row = _fleet_spec(fleet_axes, extra=1)
    vec = _fleet_spec(fleet_axes)
    return jax.shard_map(
        body, mesh=mesh,
        in_specs=(row, row, row, vec, vec, row, row, row, vec)
        + (row,) * len(en),
        out_specs=(row, row, vec), check_vma=False,
    )(c2, c1, c0, T, total, d_lo, d_hi, valid, sampled, *en)


@functools.partial(
    jax.jit,
    static_argnames=("max_tau", "loss_fn", "eval_fn", "aggregation",
                     "scheme", "mesh", "fleet_axes", "use_pallas",
                     "interpret"),
)
def _fleet_round(g, fleet_params, x, y, m, tau, d, base_w, sampled, mix, lr,
                 gamma, c2, c1, c0, T, total, d_lo, d_hi, valid, ex, ey, *en,
                 max_tau: int, loss_fn, eval_fn, aggregation: str,
                 scheme: str, mesh, fleet_axes, use_pallas: bool = False,
                 interpret: bool = False):
    """One global round as one XLA program (see module docstring): vmapped
    per-fleet train+aggregate, psum-normalized two-tier merge of the
    sampled fleets, and the next dispatch's sampling-masked policy solve.
    Must run under ``jax.enable_x64`` (f64 solve/weight math, f32 training).

    Returns ``(new_global, new_fleet_params, tau', d', feasible, acc)``.
    """
    policy = batched_policy(scheme)
    row = _fleet_spec(fleet_axes, extra=1)
    vec = _fleet_spec(fleet_axes)
    rep = P()

    def body(g, fleet_params, x, y, m, tau, d, base_w, sampled,
             mix, lr, gamma, c2, c1, c0, T, total, d_lo, d_hi, valid,
             ex, ey, *en):
        # -- tier 1: each fleet trains its K learners and aggregates ------
        def fleet_step(fp, xf, yf, mf, tf, df):
            # a padded fleet (d = 0 everywhere) has no weights to normalize
            # (0/0): it keeps its model instead of turning it into NaN,
            # which the merge's zero weight would still carry (0 * NaN)
            idle = df.sum() == 0
            keep = lambda new: jax.tree_util.tree_map(
                lambda n, o: jnp.where(idle, o, n), new, fp)
            w = _weights_traced(tf, df, aggregation=aggregation, gamma=gamma)
            if use_pallas:
                from repro.kernels import ops

                kf = xf.shape[0]
                disp = jax.tree_util.tree_map(
                    lambda leaf: jnp.broadcast_to(leaf, (kf,) + leaf.shape),
                    fp,
                )
                new, _ = ops.train_agg_step(
                    disp, xf, yf, mf, tf, w, lr, loss_fn=loss_fn,
                    max_tau=max_tau, use_pallas=True, interpret=interpret,
                )
                return keep(new)
            locals_ = local_train(
                fp, xf, yf, mf, tf, lr, max_tau=max_tau, loss_fn=loss_fn
            )
            return keep(jax.tree_util.tree_map(
                functools.partial(_wsum, w=w), locals_
            ))

        fleet_new = jax.vmap(fleet_step)(fleet_params, x, y, m, tau, d)

        # -- tier 2: staleness-discounted merge of the sampled fleets -----
        bw = jnp.where(sampled, base_w, 0.0)
        norm = _psum(bw.sum(), fleet_axes)
        any_sampled = norm > 0.0
        wg = (bw / jnp.where(any_sampled, norm, 1.0)).astype(jnp.float32)
        merged = jax.tree_util.tree_map(
            lambda leaf: _psum(_wsum(leaf, wg), fleet_axes), fleet_new
        )
        # server_mix == 1 SELECTS the merged model (no 0*g + 1*m blend:
        # that would flip signed zeros and break the F=1 bitwise contract)
        full = (mix == jnp.ones((), mix.dtype)) & any_sampled

        def mix_leaf(mleaf, gleaf):
            blend = ((1.0 - mix) * gleaf + mix * mleaf).astype(gleaf.dtype)
            out = jnp.where(full, mleaf, blend)
            return jnp.where(any_sampled, out, gleaf)

        new_g = jax.tree_util.tree_map(mix_leaf, merged, g)

        # -- next dispatch: ONE masked policy solve for sampled fleets ----
        tot_m, lo_m, hi_m, valid_m = apply_sampling_mask(
            total, d_lo, d_hi, valid, sampled
        )
        if en:
            tau_n, d_n, feas = policy(
                c2, c1, c0, T, tot_m, lo_m, hi_m, valid_m, en
            )
        else:
            tau_n, d_n, feas = policy(c2, c1, c0, T, tot_m, lo_m, hi_m, valid_m)
        tau_out = jnp.where(sampled[:, None], tau_n, tau)
        d_out = jnp.where(sampled[:, None], d_n, d)

        # sampled fleets pull the new global; the rest keep training stale
        def pull_leaf(fn_leaf, g_leaf):
            keep = sampled.reshape((-1,) + (1,) * g_leaf.ndim)
            return jnp.where(keep, g_leaf[None], fn_leaf)

        fleet_out = jax.tree_util.tree_map(pull_leaf, fleet_new, new_g)

        acc = (eval_fn(new_g, ex, ey).astype(jnp.float32)
               if eval_fn is not None else jnp.float32(0))
        return new_g, fleet_out, tau_out, d_out, feas, acc

    g_specs = jax.tree_util.tree_map(lambda _: rep, g)
    fp_specs = _tree_fleet_specs(fleet_params, fleet_axes)
    in_specs = (
        g_specs, fp_specs,
        _fleet_spec(fleet_axes, 3), _fleet_spec(fleet_axes, 2),
        _fleet_spec(fleet_axes, 2),                       # x, y, m
        row, row, vec, vec,                               # tau, d, base_w, sampled
        rep, rep, rep,                                    # mix, lr, gamma
        row, row, row, vec, vec, row, row, row,           # problem tensors
        rep, rep,                                         # eval batch
    ) + (row,) * len(en)                                  # energy rows
    out_specs = (g_specs, fp_specs, row, row, vec, rep)
    return jax.shard_map(
        body, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
        check_vma=False,
    )(g, fleet_params, x, y, m, tau, d, base_w, sampled, mix, lr, gamma,
      c2, c1, c0, T, total, d_lo, d_hi, valid, ex, ey, *en)


class FleetEngine:
    """F fleets x K learners, two-tier servers, one XLA program per round.

    ``problems`` is the (F, K) ``BatchedProblems`` population (build one
    with ``build_fleet_problems``). ``mesh`` defaults to ``host_mesh()``
    — a data×model mesh over every device the process has. F is padded up
    to a multiple of the mesh device count with all-invalid fleets (never
    sampled, zero weight, zero work: the ``BatchedProblems`` padded-slot
    semantics lifted one axis up) so the fleet dim always splits evenly."""

    def __init__(self, cfg: FleetConfig, problems: BatchedProblems, loss_fn,
                 init_params, *, seed: int = 0, mesh=None):
        self.cfg = cfg
        self.loss_fn = loss_fn
        self.global_params = init_params
        self.seed = int(seed)
        self.rng = np.random.default_rng(seed)
        self.mesh = host_mesh() if mesh is None else mesh
        n_dev = int(np.prod(list(self.mesh.shape.values())))

        self.num_fleets = problems.num_problems
        f_pad = -(-self.num_fleets // n_dev) * n_dev
        self.problems = self._pad_problems(problems, f_pad)
        self.fleet_axes = fleet_partition_axes(f_pad, self.mesh)
        self._real = np.zeros(f_pad, bool)
        self._real[: self.num_fleets] = True

        self.global_version = 0
        self.pull_version = np.zeros(f_pad, np.int64)
        self.rounds_run = 0
        self.tau, self.d = self._solve(self._real)
        self._check_feasible(self._real, self._last_feasible, "initial dispatch")
        # every fleet starts from the global model (version-0 dispatch)
        self.fleet_params = jax.tree_util.tree_map(
            lambda p: jnp.broadcast_to(p[None], (f_pad,) + p.shape),
            init_params,
        )

    @staticmethod
    def _pad_problems(bp: BatchedProblems, f_pad: int) -> BatchedProblems:
        f = bp.num_problems
        if f == f_pad:
            return bp
        pad = lambda a, fill: np.concatenate(
            [np.asarray(a),
             np.full((f_pad - f,) + np.asarray(a).shape[1:], fill,
                     np.asarray(a).dtype)]
        )
        energy = {}
        if bp.has_energy:
            # padded fleets are free: zero coefficients, infinite budget
            k = np.asarray(bp.c2).shape[1]
            e2, e1, e0, eb = bp.energy_rows()
            energy = dict(
                e2=pad(e2, 0.0), e1=pad(e1, 0.0), e0=pad(e0, 0.0),
                e_budget=pad(np.broadcast_to(eb, (f, k)), np.inf),
            )
        return BatchedProblems(
            c2=pad(bp.c2, 1.0), c1=pad(bp.c1, 1.0), c0=pad(bp.c0, 0.0),
            T=pad(bp.T, 1.0), total=pad(bp.total, 0),
            d_lo=pad(bp.d_lo, 0.0), d_hi=pad(bp.d_hi, 0.0),
            valid=pad(bp.valid, False), **energy,
        )

    # -- allocation ---------------------------------------------------------
    def _solve_args(self):
        bp = self.problems
        return (
            jnp.asarray(bp.c2, jnp.float64), jnp.asarray(bp.c1, jnp.float64),
            jnp.asarray(bp.c0, jnp.float64), jnp.asarray(bp.T, jnp.float64),
            jnp.asarray(bp.total, jnp.int64),
            jnp.asarray(bp.d_lo, jnp.float64),
            jnp.asarray(bp.d_hi, jnp.float64),
            jnp.asarray(bp.valid),
        )

    def _energy_args(self) -> tuple:
        """Trailing ``(e2, e1, e0, e_budget)`` policy rows — only for
        energy-aware schemes (problems without an energy model get zero
        coefficients and infinite budgets, reproducing ``kkt_sai``)."""
        if self.cfg.scheme not in ENERGY_SCHEMES:
            return ()
        f_pad, k = np.asarray(self.problems.c2).shape
        rows = self.problems.energy_rows()
        e2, e1, e0, eb = (np.broadcast_to(r, (f_pad, k)) for r in rows)
        return tuple(jnp.asarray(r, jnp.float64) for r in (e2, e1, e0, eb))

    def _solve(self, sampled: np.ndarray):
        """(tau, d) int64 host arrays for the sampled fleets (zeros in the
        rest) — one sharded batched_policy call."""
        with jax.enable_x64(True):
            tau, d, feas = _fleet_solve(
                *self._solve_args(), jnp.asarray(sampled, bool),
                *self._energy_args(),
                scheme=self.cfg.scheme, mesh=self.mesh,
                fleet_axes=self.fleet_axes,
            )
            tau = np.asarray(tau, np.int64)
            d = np.asarray(d, np.int64)
            self._last_feasible = np.asarray(feas, bool)
        return tau, d

    def solve_multimodel(self, deficits, *, split: str = "deficit",
                         share_floor: float = 0.0, sampled=None):
        """(tau, d, w) for S tenant models time-sharing the whole (F, K)
        population — the fleet-scale face of the cross-model allocation
        layer (``core.solver_batched.multimodel_policy``).

        ``deficits`` is the (S,) global progress-deficit signal (one per
        tenant's GLOBAL server); ``cross_model_weights`` turns it into
        shares ``w`` splitting every fleet's deadline ``T_f`` (and joule
        budgets, for energy-aware schemes), per-model sample budgets are
        scaled by ``round(w_s * total_f)``, and cells whose share cannot
        cover ``d_lo`` at tau = 0 degrade to padded slots — semantics
        mirroring ``multimodel_policy`` exactly, lifted one axis up. The
        S x F problems are flattened model-major to ``(S * F_pad, K)``
        and solved with ONE sharded ``_fleet_solve`` call
        (``fleet_partition_axes`` falls back to replication when the
        flattened dim does not divide the mesh).

        Returns ``(tau, d, w)`` with tau/d ``(S, F_pad, K)`` int64.
        S = 1 short-circuits to ``_solve`` — the SAME call the
        single-tenant rounds make, bitwise."""
        sampled = self._real if sampled is None else np.asarray(sampled, bool)
        deficits = np.asarray(deficits, np.float64)
        s = int(deficits.shape[0])
        if s == 1:
            tau, d = self._solve(sampled)
            return tau[None], d[None], np.ones(1)
        f_pad, k = np.asarray(self.problems.c2).shape
        axes = fleet_partition_axes(s * f_pad, self.mesh)
        en = self._energy_args()
        with jax.enable_x64(True):
            w = cross_model_weights(
                jnp.asarray(deficits), policy=split, share_floor=share_floor
            )
            c2, c1, c0, T, total, lo, hi, valid = self._solve_args()
            tile = lambda a: jnp.tile(a, (s,) + (1,) * (a.ndim - 1))
            w_f = jnp.repeat(w.astype(T.dtype), f_pad)        # (S*F_pad,)
            T_s = w_f * tile(T)
            total_s = jnp.round(
                w_f * tile(total).astype(T.dtype)
            ).astype(total.dtype)
            c2_t, c1_t, c0_t = tile(c2), tile(c1), tile(c0)
            lo_t, hi_t, valid_t = tile(lo), tile(hi), tile(valid)
            active = valid_t & (T_s[:, None] >= c0_t + c1_t * lo_t)
            total_s, lo_t, hi_t, valid_t = apply_active_mask(
                total_s, lo_t, hi_t, valid_t, active
            )
            if en:
                e2, e1, e0, eb = (tile(e) for e in en)
                eb = jnp.where(jnp.isinf(eb), eb, w_f[:, None] * eb)
                en = (e2, e1, e0, eb)
            tau, d, feas = _fleet_solve(
                c2_t, c1_t, c0_t, T_s, total_s, lo_t, hi_t, valid_t,
                jnp.asarray(np.tile(sampled, s)), *en,
                scheme=self.cfg.scheme, mesh=self.mesh, fleet_axes=axes,
            )
            tau = np.asarray(tau, np.int64).reshape(s, f_pad, k)
            d = np.asarray(d, np.int64).reshape(s, f_pad, k)
            feas = np.asarray(feas, bool).reshape(s, f_pad)
            w = np.asarray(w, np.float64)
        for si in range(s):
            self._check_feasible(sampled, feas[si],
                                 f"multimodel solve, model {si}")
        return tau, d, w

    def _check_feasible(self, sampled, feas, label: str):
        bad = self._real & np.asarray(sampled, bool) & ~np.asarray(feas, bool)
        if bad.any():
            raise ValueError(
                "infeasible: even with tau=0 the deadline T cannot absorb "
                f"d samples (fleet {int(np.argmax(bad))} at {label})"
            )

    # -- per-round host staging --------------------------------------------
    def _sample_mask(self, r: int) -> np.ndarray:
        f = self.num_fleets
        mask = np.zeros(self._real.size, bool)
        if self.cfg.participation >= 1.0:
            mask[:f] = True
            return mask
        n = max(1, int(round(self.cfg.participation * f)))
        rng = np.random.default_rng(
            np.random.SeedSequence((self.seed, _SAMPLE_STREAM, r))
        )
        mask[rng.choice(f, size=n, replace=False)] = True
        return mask

    def _stage(self, parts, train: Dataset, d_cap: int):
        f_pad, k = self.problems.c2.shape
        feat = train.x.shape[1]
        x = np.zeros((f_pad, k, d_cap, feat), np.float32)
        y = np.zeros((f_pad, k, d_cap), np.int32)
        m = np.zeros((f_pad, k, d_cap), np.float32)
        for f in range(self.num_fleets):
            row = self.d[f]
            idx = parts[f].draw_indices(int(row.sum()))
            off = 0
            for kk in range(k):
                dk = int(row[kk])
                if dk:
                    sl = idx[off:off + dk]
                    x[f, kk, :dk] = train.x[sl]
                    y[f, kk, :dk] = train.y[sl]
                    m[f, kk, :dk] = 1.0
                    off += dk
        return x, y, m

    # -- full run -----------------------------------------------------------
    def run(self, train: Dataset, rounds: int, *, eval_fn=None,
            eval_batch=None, use_pallas: bool = False,
            interpret: bool = False) -> list[dict]:
        """Run ``rounds`` global rounds; returns one history record per
        round. ``eval_fn`` must be jit-traceable ``(params, x, y) ->
        scalar`` (e.g. ``mlp.accuracy``) evaluated on ``eval_batch`` inside
        the round program. ``use_pallas`` routes each fleet's vmapped
        train+aggregate step through the ``ops.train_agg_step`` megakernel
        (``interpret=True`` emulates it on CPU); the default keeps the
        unfused ``local_train`` + ``_wsum`` tier-1 body. Repeated calls
        continue from the current state (fresh partitioners, like
        ``Orchestrator.run``)."""
        if eval_fn is not None and eval_batch is None:
            raise ValueError("eval_fn needs eval_batch=(x, y)")
        cfg = self.cfg
        parts = [
            FederatedPartitioner(train, seed=int(self.rng.integers(2**31)))
            for _ in range(self.num_fleets)
        ]
        ex, ey = ((jnp.asarray(eval_batch[0]), jnp.asarray(eval_batch[1]))
                  if eval_fn is not None
                  else (jnp.zeros((1, train.x.shape[1]), jnp.float32),
                        jnp.zeros((1,), jnp.int32)))
        t_round = float(self.problems.T[self._real].max())
        history: list[dict] = []
        for r in range(self.rounds_run, self.rounds_run + rounds):
            sampled = self._sample_mask(r)
            d_cap = max(1, int(self.d[self._real].max()))
            max_tau = max(1, int(self.tau[self._real].max()))
            x, y, m = self._stage(parts, train, d_cap)
            stale = np.maximum(self.global_version - self.pull_version, 0)
            phi = staleness_factor(
                stale, kind=cfg.staleness_fn, a=cfg.staleness_a,
                b=cfg.staleness_b,
            )
            n_f = self.d.sum(axis=1).astype(np.float64)
            base_w = np.where(self._real, n_f * phi, 0.0)
            with jax.enable_x64(True):
                (g, fp, tau_n, d_n, feas, acc) = _fleet_round(
                    self.global_params, self.fleet_params,
                    jnp.asarray(x), jnp.asarray(y), jnp.asarray(m),
                    jnp.asarray(self.tau), jnp.asarray(self.d),
                    jnp.asarray(base_w, jnp.float64),
                    jnp.asarray(sampled),
                    jnp.asarray(cfg.server_mix, jnp.float32),
                    jnp.asarray(cfg.lr, jnp.float32),
                    jnp.asarray(cfg.staleness_gamma, jnp.float64),
                    *self._solve_args(), ex, ey, *self._energy_args(),
                    max_tau=max_tau, loss_fn=self.loss_fn, eval_fn=eval_fn,
                    aggregation=cfg.aggregation, scheme=cfg.scheme,
                    mesh=self.mesh, fleet_axes=self.fleet_axes,
                    use_pallas=use_pallas, interpret=interpret,
                )
                feas_h = np.asarray(feas, bool)
            self._check_feasible(sampled, feas_h, f"round {r}")
            self.global_params, self.fleet_params = g, fp
            tau_h = np.asarray(tau_n, np.int64)
            d_h = np.asarray(d_n, np.int64)
            merged = sampled & self._real
            rec = {
                "round": r,
                "cycle": r,
                "elapsed_s": (r + 1) * t_round,
                "wall_clock_s": t_round,
                "fleets": int(self.num_fleets),
                "sampled_fleets": int(merged.sum()),
                "tau": self.tau[self._real].copy(),
                "d": self.d[self._real].copy(),
                "max_staleness": batched_max_staleness(
                    self.tau[self._real], self.problems.valid[self._real]
                ),
                "avg_staleness": batched_avg_staleness(
                    self.tau[self._real], self.problems.valid[self._real]
                ),
                "fleet_staleness_max": int(stale[merged].max()) if merged.any() else 0,
                "fleet_staleness_mean": float(stale[merged].mean()) if merged.any() else 0.0,
            }
            if eval_fn is not None:
                rec["accuracy"] = float(acc)
            history.append(rec)
            # bookkeeping: merge bumps the global version; sampled fleets
            # pulled it and re-dispatched with the freshly solved (tau, d)
            self.global_version += 1
            self.pull_version[merged] = self.global_version
            self.tau, self.d = tau_h, d_h
        self.rounds_run += rounds
        return history
