"""Pure-jnp oracles for every Pallas kernel (the contract the kernel
tests `assert_allclose` against)."""

from __future__ import annotations

import jax
import jax.numpy as jnp

__all__ = [
    "flash_attention_ref",
    "wkv6_ref",
    "fed_agg_ref",
    "swiglu_ref",
    "mamba_scan_ref",
    "waterfill_residual_ref",
    "waterfill_energy_residual_ref",
    "train_agg_step_ref",
]


def flash_attention_ref(q, k, v, *, causal=True, window=None):
    """O(S^2) dense attention with explicit masking (NOT the chunked scan —
    an independent formulation so the two implementations cross-check)."""
    b, sq, h, d = q.shape
    _, skv, kv, _ = k.shape
    g = h // kv
    qg = q.reshape(b, sq, kv, g, d).astype(jnp.float32)
    s = jnp.einsum("bqkgd,bckd->bqkgc", qg, k.astype(jnp.float32)) / jnp.sqrt(d)
    qpos = jnp.arange(sq)[:, None]
    kpos = jnp.arange(skv)[None, :]
    mask = jnp.ones((sq, skv), bool)
    if causal:
        mask &= qpos >= kpos
    if window is not None:
        mask &= qpos - kpos < window
    s = jnp.where(mask[None, :, None, None, :], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bqkgc,bckd->bqkgd", p, v.astype(jnp.float32))
    return out.reshape(b, sq, h, d).astype(q.dtype)


def wkv6_ref(r, k, v, w, u, s0=None):
    from repro.models.rwkv6 import wkv_scan

    return wkv_scan(r, k, v, w, u, s0=s0)


def fed_agg_ref(stacked, weights):
    w = weights.reshape((-1,) + (1,) * (stacked.ndim - 1)).astype(jnp.float32)
    return (stacked.astype(jnp.float32) * w).sum(axis=0).astype(stacked.dtype)


def swiglu_ref(x, w_gate, w_up, w_down):
    from repro.models.layers import swiglu

    return swiglu(x, w_gate, w_up, w_down)


def waterfill_residual_ref(tau_star, c2, c1, c0, T, d_lo, d_hi, total):
    """Batched KKT water-filling residual (core.solver_batched layout):
    tau_star/T/total: (B,); c2/c1/c0/d_lo/d_hi: (B, K). Returns (B,)."""
    d = (T[:, None] - c0) / (c2 * tau_star[:, None] + c1)
    return jnp.clip(d, d_lo, d_hi).sum(axis=-1) - total


def waterfill_energy_residual_ref(tau_star, c2, c1, c0, T, e2, e1, e0, eb,
                                  d_lo, d_hi, total):
    """Energy-budgeted water-filling residual (arXiv 2012.00143): each
    learner absorbs the tightest of the deadline hyperbola
    ``(T - c0)/(c2 tau* + c1)`` and the budget hyperbola
    ``(eb - e0)/(e2 tau* + e1)``. The time branch repeats
    ``waterfill_residual_ref`` op-for-op, and ``min(d_time, inf)`` selects
    it bitwise under IEEE inf arithmetic, so ``eb = +inf`` rows degenerate
    to the unbudgeted residual exactly. tau_star/T/total: (B,); the six
    coefficient rows and the bounds: (B, K). Returns (B,)."""
    dt = (T[:, None] - c0) / (c2 * tau_star[:, None] + c1)
    de = (eb - e0) / (e2 * tau_star[:, None] + e1)
    return jnp.clip(jnp.minimum(dt, de), d_lo, d_hi).sum(axis=-1) - total


def train_agg_step_ref(disp, x, y, m, tau, weights, lr, *, loss_fn, max_tau,
                       server=None, acc=None, keep=None, flush=None):
    """Unfused train+aggregate composition — literally
    ``local_train_stacked`` followed by the ``fed_agg_ref`` contractions,
    so the megakernel's f32-tolerance contract is pinned against the exact
    ops the scan bodies run today. ``acc=None`` selects the cycle form (plain
    weighted aggregation of the trained locals); otherwise the async
    accumulate/flush form ``server' = keep*server + flush*(acc + sum_k
    w_k local_k)``, ``acc' = (1-flush)*(acc + sum_k w_k local_k)``.
    Returns ``(new_server, new_acc)`` with ``new_acc=None`` in cycle form.
    """
    from repro.fed.orchestrator import local_train_stacked

    locals_ = local_train_stacked(disp, x, y, m, tau, lr,
                                  max_tau=max_tau, loss_fn=loss_fn)
    with jax.named_scope("aggregate"):
        w = jnp.asarray(weights, jnp.float32)
        if acc is None:
            new = jax.tree_util.tree_map(lambda l: fed_agg_ref(l, w), locals_)
            return new, None
        one = jnp.ones((1,), jnp.float32)
        w_acc = jnp.concatenate([one, w])
        acc1 = jax.tree_util.tree_map(
            lambda a, l: fed_agg_ref(jnp.concatenate([a[None], l], axis=0), w_acc),
            acc, locals_,
        )
        keep = jnp.asarray(keep, jnp.float32)
        flush = jnp.asarray(flush, jnp.float32)
        w_flush = jnp.stack([keep, flush])
        server1 = jax.tree_util.tree_map(
            lambda s, a: fed_agg_ref(jnp.stack([s, a]), w_flush), server, acc1
        )
        acc2 = jax.tree_util.tree_map(lambda a: (1.0 - flush) * a, acc1)
        return server1, acc2


def mamba_scan_ref(dt, x, b, c, a, h0=None):
    """Sequential S6 scan. dt,x: (B,S,D); b,c: (B,S,N); a: (D,N); h0: (B,D,N)."""
    bsz, s, d = dt.shape
    n = b.shape[-1]
    if h0 is None:
        h0 = jnp.zeros((bsz, d, n), jnp.float32)

    def step(h, inp):
        dt_t, x_t, b_t, c_t = inp
        da = jnp.exp(dt_t[:, :, None] * a[None])
        h = h * da + (dt_t * x_t)[:, :, None] * b_t[:, None, :]
        y = jnp.einsum("bdn,bn->bd", h, c_t)
        return h, y

    xs = tuple(t.transpose(1, 0, 2) for t in (dt, x, b, c))
    h_last, ys = jax.lax.scan(step, h0.astype(jnp.float32), xs)
    return ys.transpose(1, 0, 2), h_last
