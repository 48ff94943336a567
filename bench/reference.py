"""Plain reference of a federated campaign, written from the paper.

It recomputes, from the cell's inputs alone, what a campaign of the
program should return: the allocation of every cycle or arrival, and the
trained server model with its accuracy after every aggregation. The
schedule of each campaign kind (for asynchronous kinds: versions,
staleness, mixing weights) is built from these pieces by the kind's
``schedule`` in ``bench/kinds/``. Nothing here imports the program.

* Allocation (paper Sec. IV): the relaxed problem's KKT water-filling in
  tau* by bisection, largest-remainder rounding to integer d, then the
  suggest-and-improve (SAI) moves that shrink the staleness spread
  max(tau) - min(tau).
* Capacity drift: per cycle a uniform clock jitter and a clipped
  lognormal fading, drawn from ``fold_in(key(seed), cycle)``.
* Shards: the partitioner's draw ``i`` is ``choice(N, total,
  replace=False)`` from ``SeedSequence((partition_seed, i))``; the
  partition seed is the first ``integers(2**31)`` of the engine's
  ``default_rng(seed)``.
* Training: full-batch gradient descent on each learner's own shard for
  tau_k steps, on the learner model family's plain ``loss``
  (``bench/models/``), then the staleness-weighted average (barrier) or
  the FedAsync mix ``(1 - a s(v)) w + a s(v) w_k`` with s(v) = (1 + v)^-0.5.
  Where the K learners of an aggregation fit in ``work.memory_budget()``
  at three copies of the params each (start, carry, gradient), they train
  in one ``vmap``; otherwise in groups of the most that fit, at least one,
  whose trained params are summed into one accumulator as each group ends.

``dtype`` float32 runs every matmul at HIGHEST precision (the
reference); bfloat16 runs the same arithmetic in bfloat16 (the control,
which the comparison must reject).
"""

from __future__ import annotations

import functools

import numpy as np

import jax
import jax.numpy as jnp

from bench import work

# ---------------------------------------------------------------------------
# allocation: KKT water-filling + SAI
# ---------------------------------------------------------------------------

BISECT_TOL = 1e-10
BISECT_ITERS = 200
SAI_ROUNDS = 10_000


def max_tau(c2, c1, c0, T, d):
    """Largest integer tau_k with c2 tau d + c1 d + c0 <= T."""
    d = np.asarray(d, float)
    with np.errstate(divide="ignore", invalid="ignore"):
        t = np.floor((T - c0 - c1 * d) / (c2 * d))
    return np.maximum(np.where(d > 0, t, 0.0), 0.0).astype(np.int64)


def _water_level(c2, c1, c0, T, total, lo, hi):
    def d_of(ts):
        with np.errstate(over="ignore", invalid="ignore"):
            return np.clip((T - c0) / (c2 * ts + c1), lo, hi)

    if d_of(0.0).sum() < total - 1e-9:
        raise ValueError("infeasible: the deadline cannot absorb the samples")
    a, b = 0.0, 1.0
    n = 0
    while d_of(b).sum() > total and n < 200:
        b *= 2.0
        n += 1
    for _ in range(BISECT_ITERS):
        mid = 0.5 * (a + b)
        if d_of(mid).sum() > total:
            a = mid
        else:
            b = mid
        if b - a < BISECT_TOL * max(1.0, b):
            break
    d = d_of(0.5 * (a + b))
    free = (d > lo + 1e-9) & (d < hi - 1e-9)
    if free.any():
        d[free] += (total - d.sum()) * (d[free] / d[free].sum())
    return np.clip(d, lo, hi)


def _round_to_total(d_real, total, lo, hi):
    d = np.clip(np.floor(d_real).astype(np.int64), lo, hi)
    rem = d_real - np.floor(d_real)
    short = total - int(d.sum())
    order = np.argsort(-rem if short > 0 else rem, kind="stable")
    i = 0
    while short != 0:
        k = order[i % len(order)]
        if short > 0 and d[k] < hi:
            d[k] += 1
            short -= 1
        elif short < 0 and d[k] > lo:
            d[k] -= 1
            short += 1
        i += 1
    return d


def allocate(c2, c1, c0, T, total, lo, hi):
    """(tau, d) integer allocation of one fleet on one capacity row."""
    d = _round_to_total(_water_level(c2, c1, c0, T, total, lo, hi),
                        total, lo, hi)
    tau = max_tau(c2, c1, c0, T, d)
    for _ in range(SAI_ROUNDS):
        spread = int(tau.max() - tau.min())
        if spread == 0:
            break
        up = int(np.argmax(tau))
        lows = np.flatnonzero(tau == tau.min())
        down = int(lows[np.argmax(c2[lows])])
        give = int(d[down]) - lo
        room = min(hi - int(d[up]), give)
        if room <= 0:
            up = -1
            for cand in np.argsort(-tau, kind="stable"):
                if tau[cand] == tau.min():
                    break
                if min(hi - int(d[cand]), give) > 0:
                    up, room = int(cand), min(hi - int(d[cand]), give)
                    break
            if up < 0:
                break
        moved = False
        for m in dict.fromkeys((max(1, room // 8), 1)):
            d2 = d.copy()
            d2[up] += m
            d2[down] -= m
            tau2 = max_tau(c2, c1, c0, T, d2)
            s2 = int(tau2.max() - tau2.min())
            if s2 < spread or (s2 == spread and tau2.sum() > tau.sum()):
                d, tau, moved = d2, tau2, True
                break
        if not moved:
            break
    return tau, d


def drift_factors(seed: int, cycles: int, k: int, *, clock_jitter: float,
                  fading_sigma_db: float, fading_clip_db: float):
    """(C, K) clock and rate factors of the exogenous capacity drift."""
    with jax.enable_x64(True):
        def one(c):
            kc, kf = jax.random.split(jax.random.fold_in(jax.random.key(seed), c))
            clock = 1.0 + clock_jitter * (
                2.0 * jax.random.uniform(kc, (k,), jnp.float32) - 1.0)
            db = jnp.clip(fading_sigma_db * jax.random.normal(kf, (k,), jnp.float32),
                          -fading_clip_db, fading_clip_db)
            rate = jnp.power(jnp.float64(10.0), db.astype(jnp.float64) / 10.0)
            return clock, rate.astype(jnp.float32)

        clock, rate = jax.vmap(one)(jnp.arange(cycles))
        return np.asarray(clock, np.float64), np.asarray(rate, np.float64)


# ---------------------------------------------------------------------------
# shards (the schedules of each campaign kind are in ``bench/kinds/``)
# ---------------------------------------------------------------------------

class Draws:
    """The partitioner's sequence of sample draws for one engine seed."""

    def __init__(self, engine_seed: int, n: int):
        self.seed = int(np.random.default_rng(engine_seed).integers(2**31))
        self.n, self.i = n, 0

    def __call__(self, size: int) -> np.ndarray:
        rng = np.random.default_rng(np.random.SeedSequence((self.seed, self.i)))
        self.i += 1
        return rng.choice(self.n, size=int(size), replace=False)


# ---------------------------------------------------------------------------
# training
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("loss", "dtype"))
def train_learners(start, x, y, m, tau, lr, *, loss, dtype):
    """tau_k full-batch GD steps of each learner from its own start
    params (leading learner axis) on the family's ``loss``, on rows where
    m = 1. A scalar ``tau`` is one learner, with no learner axis on any
    operand: traced without ``vmap``, so that a family's ``lax.cond``
    stays a branch. Floating inputs are cast to ``dtype``; integer ones
    (token ids) are not."""
    cast = lambda t: jax.tree_util.tree_map(lambda a: a.astype(dtype), t)
    start = cast(start)
    if jnp.issubdtype(x.dtype, jnp.floating):
        x = x.astype(dtype)
    m, lr = m.astype(dtype), lr.astype(dtype)

    def one(p, xk, yk, mk, tk):
        g = jax.grad(loss)

        def step(i, p):
            gi = g(p, xk, yk, mk)
            return jax.tree_util.tree_map(
                lambda a, b: jnp.where(i < tk, a - lr * b, a), p, gi)

        return jax.lax.fori_loop(0, jnp.max(tau), step, p)

    if jnp.ndim(tau) == 0:
        return one(start, x, y, m, tau)
    return jax.vmap(one)(start, x, y, m, tau)


@functools.partial(jax.jit, static_argnames=("dtype",))
def mix(server, locals_, keep, w, *, dtype):
    """keep * server + sum_k w_k local_k; a scalar ``w`` weighs one local
    with no learner axis."""
    return jax.tree_util.tree_map(
        lambda s, l: (keep.astype(dtype) * s.astype(dtype)
                      + jnp.tensordot(w.astype(dtype), l.astype(dtype), w.ndim)),
        server, locals_)


@functools.partial(jax.jit, static_argnames=("accuracy",))
def evaluate(params, x, y, *, accuracy):
    """The family's ``accuracy`` of ``params``, read in float32."""
    return accuracy(jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), params), x, y)


def _stack(trees):
    return jax.tree_util.tree_map(lambda *a: jnp.stack(a), *trees)


def _pad_rows(d_max: int) -> int:
    return max(128, -(-int(d_max) // 128) * 128)


def run_campaign(schedule, init_params, train_x, train_y, eval_x, eval_y, *,
                 model, lr: float, gamma: float, dtype=jnp.float32,
                 train_override=None):
    """Train one campaign along ``schedule`` with the loss and accuracy of
    the family ``model`` (``bench/models/``); returns ``(params, accs)``.
    ``train_override`` replaces ``train_learners`` (fault readings), for
    each group of learners."""
    train = train_override or train_learners
    k_all = max(int(np.max(e["learners"])) for e in schedule) + 1
    server = jax.tree_util.tree_map(lambda a: jnp.asarray(a, dtype), init_params)
    dispatched = [server] * k_all
    rows = _pad_rows(max(int(np.max(e["d"])) for e in schedule))
    lr_j = jnp.float32(lr)
    # learners whose start params, carries and gradients fit at once
    fit = work.memory_budget() / (3 * work.tree_bytes(server))
    accs = []
    for e in schedule:
        ks = e["learners"]
        x = np.zeros((len(ks), rows, *train_x.shape[1:]), train_x.dtype)
        y = np.zeros((len(ks), rows, *train_y.shape[1:]), train_y.dtype)
        m = np.zeros((len(ks), rows), np.float32)
        for j, idx in enumerate(e["idx"]):
            x[j, :len(idx)], y[j, :len(idx)], m[j, :len(idx)] = (
                train_x[idx], train_y[idx], 1.0)
        if "keep" in e:             # FedAsync: one upload mixed into the server
            keep, w = e["keep"], e["weights"]
        else:                       # barrier: staleness-weighted average
            t = np.asarray(e["tau"], float)
            w = np.asarray(e["d"], float) / (1.0 + gamma * (t.max() - t))
            keep, w = 0.0, w / w.sum()
        if len(ks) <= fit:          # one vmap over every learner
            start = _stack([dispatched[k] for k in ks])
            tau = jnp.asarray(e["tau"], jnp.int32)
            locals_ = train(start, jnp.asarray(x), jnp.asarray(y), jnp.asarray(m),
                            tau, lr_j, loss=model.loss, dtype=dtype)
            server = mix(server, locals_, jnp.float32(keep),
                         jnp.asarray(w, jnp.float32), dtype=dtype)
        else:                       # groups of g, summed as each ends
            g, keep_j, tau = max(1, int(fit)), jnp.float32(keep), np.asarray(e["tau"])
            for lo in range(0, len(ks), g):
                if len(ks) - lo == 1 or g == 1:     # one learner: no learner axis
                    part, start = lo, dispatched[ks[lo]]
                else:
                    part = slice(lo, lo + g)
                    start = _stack([dispatched[k] for k in ks[part]])
                local = train(start, jnp.asarray(x[part]), jnp.asarray(y[part]),
                              jnp.asarray(m[part]), jnp.asarray(tau[part], jnp.int32),
                              lr_j, loss=model.loss, dtype=dtype)
                server = mix(server, local, keep_j, jnp.asarray(w[part], jnp.float32),
                             dtype=dtype)
                keep_j = jnp.float32(1.0)
        if "keep" in e:
            dispatched[int(ks[0])] = server
        else:
            dispatched = [server] * k_all
        accs.append(float(evaluate(server, eval_x, eval_y, accuracy=model.accuracy)))
    return jax.tree_util.tree_map(lambda a: np.asarray(a, np.float32), server), accs

