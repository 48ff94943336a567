"""Jit'd dispatch wrappers for the compute hot spots.

Every op has two backends:
  * pure-jnp reference (``repro.kernels.ref`` / ``repro.models.layers``) —
    the default on CPU and the oracle the Pallas kernels are tested against;
  * a Pallas TPU kernel (``use_pallas=True``) with explicit BlockSpec VMEM
    tiling — the deployment path on a TPU. On CPU the kernels run in
    ``interpret=True`` mode (tests) only.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

__all__ = [
    "flash_attention",
    "wkv6",
    "fed_agg",
    "swiglu_fused",
    "mamba_scan",
    "waterfill_residual",
    "waterfill_energy_residual",
    "train_agg_step",
]


def flash_attention(q, k, v, *, causal=True, window=None, chunk=512,
                    use_pallas=False, interpret=False, p_bf16=False, q_block=0):
    if use_pallas:
        from repro.kernels.flash_attention import flash_attention_pallas

        return flash_attention_pallas(
            q, k, v, causal=causal, window=window, interpret=interpret
        )
    from repro.models.layers import flash_attention as ref

    return ref(q, k, v, causal=causal, window=window, chunk=chunk,
               p_bf16=p_bf16, q_block=q_block)


def wkv6(r, k, v, w, u, s0=None, *, use_pallas=False, interpret=False, unroll=1,
         backend="scan", chunk=16):
    if use_pallas:
        from repro.kernels.wkv6 import wkv6_pallas

        return wkv6_pallas(r, k, v, w, u, s0=s0, interpret=interpret)
    if backend == "chunked":
        from repro.models.rwkv6 import wkv_chunked

        return wkv_chunked(r, k, v, w, u, s0=s0, chunk=chunk)
    from repro.models.rwkv6 import wkv_scan

    return wkv_scan(r, k, v, w, u, s0=s0, unroll=unroll)


def fed_agg(stacked, weights, *, use_pallas=False, interpret=False):
    """Weighted sum over the leading learner axis of a stacked tensor
    (device scope ``aggregate``)."""
    with jax.named_scope("aggregate"):
        if use_pallas:
            from repro.kernels.fed_agg import fed_agg_pallas

            return fed_agg_pallas(stacked, weights, interpret=interpret)
        w = weights.reshape((-1,) + (1,) * (stacked.ndim - 1)).astype(jnp.float32)
        return (stacked.astype(jnp.float32) * w).sum(axis=0).astype(stacked.dtype)


def waterfill_residual(tau_star, c2, c1, c0, T, d_lo, d_hi, total, *,
                       use_pallas=False, interpret=False):
    """Batched water-filling residual sum_k clip((T-c0)/(c2*tau+c1), lo, hi)
    - total for a (B, K) fleet batch — the inner evaluation of every
    bisection step in ``core.solver_batched``."""
    if use_pallas:
        from repro.kernels.waterfill import waterfill_residual_pallas

        return waterfill_residual_pallas(
            tau_star, c2, c1, c0, T, d_lo, d_hi, total, interpret=interpret
        )
    from repro.kernels.ref import waterfill_residual_ref

    return waterfill_residual_ref(tau_star, c2, c1, c0, T, d_lo, d_hi, total)


def waterfill_energy_residual(tau_star, c2, c1, c0, T, e2, e1, e0, eb,
                              d_lo, d_hi, total, *,
                              use_pallas=False, interpret=False):
    """Energy-budgeted water-filling residual
    sum_k clip(min((T-c0)/(c2*tau+c1), (eb-e0)/(e2*tau+e1)), lo, hi)
    - total for a (B, K) fleet batch — the inner evaluation of every
    ``kkt_energy`` bisection step (arXiv 2012.00143). ``eb = +inf`` rows
    reproduce ``waterfill_residual`` bitwise on both backends."""
    if use_pallas:
        from repro.kernels.waterfill import waterfill_energy_residual_pallas

        return waterfill_energy_residual_pallas(
            tau_star, c2, c1, c0, T, e2, e1, e0, eb, d_lo, d_hi, total,
            interpret=interpret,
        )
    from repro.kernels.ref import waterfill_energy_residual_ref

    return waterfill_energy_residual_ref(
        tau_star, c2, c1, c0, T, e2, e1, e0, eb, d_lo, d_hi, total
    )


def train_agg_step(disp, x, y, m, tau, weights, lr, *, loss_fn, max_tau=None,
                   server=None, acc=None, keep=None, flush=None,
                   use_pallas=False, interpret=False):
    """One fused train+aggregate scan step: masked per-learner GD
    (``local_train_stacked`` numerics — ``tau_k`` steps from each
    learner's own start params, data mask in the loss contraction),
    weighted accumulate, and the masked ``fed_agg`` flush contraction.

    ``acc=None`` is the cycle form (``run_fused``/fleet rounds): returns
    ``(fed_agg(locals, weights), None)``. Passing ``server``/``acc``/
    ``keep``/``flush`` is the async form (``_bucketed_events``): returns
    ``(keep*server + flush*acc1, (1-flush)*acc1)`` with
    ``acc1 = acc + sum_k w_k local_k``.

    The unfused path needs a static ``max_tau`` bound (it runs the
    ``lax.scan`` of ``local_train_stacked``); the Pallas megakernel runs
    each learner's in-kernel ``fori_loop`` to its traced ``tau_k`` and
    ignores ``max_tau``. The two agree to the f32 tolerance stated in
    ``kernels.train_step`` (``tests/test_kernel_parity.py``).

    Device scopes: the unfused path's ops fall in ``local_train`` and
    ``aggregate``; the megakernel is one custom call, in ``local_train``.
    """
    if use_pallas:
        from repro.kernels.train_step import train_agg_step_pallas

        with jax.named_scope("local_train"):
            return train_agg_step_pallas(
                disp, x, y, m, tau, weights, lr, loss_fn=loss_fn,
                server=server, acc=acc, keep=keep, flush=flush,
                interpret=interpret,
            )
    from repro.kernels.ref import train_agg_step_ref

    if max_tau is None:
        raise ValueError("the unfused path needs a static max_tau bound")
    return train_agg_step_ref(
        disp, x, y, m, tau, weights, lr, loss_fn=loss_fn, max_tau=max_tau,
        server=server, acc=acc, keep=keep, flush=flush,
    )


def swiglu_fused(x, w_gate, w_up, w_down, *, use_pallas=False, interpret=False):
    if use_pallas:
        from repro.kernels.swiglu import swiglu_pallas

        return swiglu_pallas(x, w_gate, w_up, w_down, interpret=interpret)
    from repro.models.layers import swiglu as ref

    return ref(x, w_gate, w_up, w_down)


def mamba_scan(dt, x, b, c, a, h0=None, *, use_pallas=False, interpret=False):
    """Selective scan: state-resident Pallas kernel on TPU, lax.scan ref."""
    if use_pallas:
        from repro.kernels.mamba_scan import mamba_scan_pallas

        return mamba_scan_pallas(dt, x, b, c, a, h0=h0, interpret=interpret)
    from repro.kernels.ref import mamba_scan_ref

    return mamba_scan_ref(dt, x, b, c, a, h0=h0)
