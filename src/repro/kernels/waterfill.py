"""Pallas TPU kernel: batched KKT water-filling residual.

One bisection step of the batched allocator (``core.solver_batched``)
evaluates, for every fleet b in a (B, K) problem batch,

    r_b = sum_k clip((T_b - C0_bk) / (C2_bk * tau_b + C1_bk), dl_bk, du_bk)
          - d_b

i.e. how much data the fleet absorbs at the trial water level tau_b minus
the sum constraint. The kernel streams one (block_b, K) coefficient tile
per grid step with the per-fleet scalars broadcast from a (block_b, 1)
column, computes the clipped divide and the K-reduction in VMEM, and
writes the (block_b, 1) residual — every coefficient byte is touched
exactly once per bisection step.

Layout conventions (shared with ``core.solver_batched``):
  * coefficients / bounds: (B, K), fleets on the sublane axis so K sits on
    the 128-lane axis (padded here to a lane multiple);
  * per-fleet scalars (tau*, T, d): (B,) reshaped to (B, 1) columns;
  * padded learner slots carry d_lo = d_hi = 0 so they clip to zero and
    never contribute to the residual.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["waterfill_residual_pallas", "waterfill_energy_residual_pallas"]


def _kernel(tau_ref, c2_ref, c1_ref, c0_ref, t_ref, lo_ref, hi_ref, tot_ref, o_ref):
    tau = tau_ref[...].astype(jnp.float32)      # (bb, 1)
    t = t_ref[...].astype(jnp.float32)          # (bb, 1)
    c2 = c2_ref[...].astype(jnp.float32)        # (bb, K)
    c1 = c1_ref[...].astype(jnp.float32)
    c0 = c0_ref[...].astype(jnp.float32)
    d = (t - c0) / (c2 * tau + c1)
    d = jnp.clip(d, lo_ref[...].astype(jnp.float32), hi_ref[...].astype(jnp.float32))
    r = d.sum(axis=1, keepdims=True) - tot_ref[...].astype(jnp.float32)
    o_ref[...] = r.astype(o_ref.dtype)


def waterfill_residual_pallas(
    tau_star, c2, c1, c0, T, d_lo, d_hi, total,
    *, block_b: int = 8, lane: int = 128, interpret: bool = False,
):
    """tau_star/T/total: (B,); c2/c1/c0/d_lo/d_hi: (B, K). Returns (B,)."""
    b, k = c2.shape
    dtype = c2.dtype

    pad_b = (-b) % block_b
    pad_k = (-k) % lane
    # Padded learners: c2 = c1 = 1, c0 = 0, lo = hi = 0  ->  clip(...) == 0.
    # Padded fleets: T = 0, total = 0                    ->  residual == 0.
    if pad_k:
        kw = dict(mode="constant")
        c2 = jnp.pad(c2, ((0, 0), (0, pad_k)), constant_values=1.0, **kw)
        c1 = jnp.pad(c1, ((0, 0), (0, pad_k)), constant_values=1.0, **kw)
        c0 = jnp.pad(c0, ((0, 0), (0, pad_k)), **kw)
        d_lo = jnp.pad(d_lo, ((0, 0), (0, pad_k)), **kw)
        d_hi = jnp.pad(d_hi, ((0, 0), (0, pad_k)), **kw)
    if pad_b:
        c2 = jnp.pad(c2, ((0, pad_b), (0, 0)), constant_values=1.0)
        c1 = jnp.pad(c1, ((0, pad_b), (0, 0)), constant_values=1.0)
        c0 = jnp.pad(c0, ((0, pad_b), (0, 0)))
        d_lo = jnp.pad(d_lo, ((0, pad_b), (0, 0)))
        d_hi = jnp.pad(d_hi, ((0, pad_b), (0, 0)))
        tau_star = jnp.pad(tau_star, (0, pad_b))
        T = jnp.pad(T, (0, pad_b))
        total = jnp.pad(total, (0, pad_b))

    bp, kp = c2.shape
    col = lambda v: v.reshape(bp, 1).astype(dtype)
    nb = bp // block_b
    mat_spec = pl.BlockSpec((block_b, kp), lambda i: (i, 0))
    col_spec = pl.BlockSpec((block_b, 1), lambda i: (i, 0))

    out = pl.pallas_call(
        _kernel,
        grid=(nb,),
        in_specs=[col_spec, mat_spec, mat_spec, mat_spec, col_spec,
                  mat_spec, mat_spec, col_spec],
        out_specs=col_spec,
        out_shape=jax.ShapeDtypeStruct((bp, 1), dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
        ),
        interpret=interpret,
    )(col(tau_star), c2, c1, c0, col(T), d_lo, d_hi, col(total))
    return out.reshape(-1)[:b]


def _energy_kernel(tau_ref, c2_ref, c1_ref, c0_ref, t_ref, e2_ref, e1_ref,
                   e0_ref, eb_ref, lo_ref, hi_ref, tot_ref, o_ref):
    tau = tau_ref[...].astype(jnp.float32)      # (bb, 1)
    t = t_ref[...].astype(jnp.float32)          # (bb, 1)
    c2 = c2_ref[...].astype(jnp.float32)        # (bb, K)
    c1 = c1_ref[...].astype(jnp.float32)
    c0 = c0_ref[...].astype(jnp.float32)
    e2 = e2_ref[...].astype(jnp.float32)
    e1 = e1_ref[...].astype(jnp.float32)
    e0 = e0_ref[...].astype(jnp.float32)
    eb = eb_ref[...].astype(jnp.float32)
    dt = (t - c0) / (c2 * tau + c1)
    de = (eb - e0) / (e2 * tau + e1)
    d = jnp.clip(jnp.minimum(dt, de),
                 lo_ref[...].astype(jnp.float32),
                 hi_ref[...].astype(jnp.float32))
    r = d.sum(axis=1, keepdims=True) - tot_ref[...].astype(jnp.float32)
    o_ref[...] = r.astype(o_ref.dtype)


def waterfill_energy_residual_pallas(
    tau_star, c2, c1, c0, T, e2, e1, e0, eb, d_lo, d_hi, total,
    *, block_b: int = 8, lane: int = 128, interpret: bool = False,
):
    """Budgeted twin of ``waterfill_residual_pallas``: each learner's
    absorbable data is ``min(d_time, d_energy)`` before the box clip, with
    the ``(e2, e1, e0, eb)`` rows streamed alongside the time rows (four
    more (block_b, K) tiles per grid step — still one pass over every
    coefficient byte per bisection step). ``eb = +inf`` rows reproduce the
    time-only residual via IEEE ``min(d_time, inf)``. Shapes as in the
    time kernel; the energy rows are (B, K)."""
    b, k = c2.shape
    dtype = c2.dtype

    pad_b = (-b) % block_b
    pad_k = (-k) % lane
    # Padded learners: unit coefficient rows with a zero box — both
    # hyperbolae stay finite and clip(..., 0, 0) == 0 regardless.
    # Padded fleets: T = 0, eb = 0, total = 0 -> residual == 0.
    if pad_k:
        kw = dict(mode="constant")
        c2 = jnp.pad(c2, ((0, 0), (0, pad_k)), constant_values=1.0, **kw)
        c1 = jnp.pad(c1, ((0, 0), (0, pad_k)), constant_values=1.0, **kw)
        c0 = jnp.pad(c0, ((0, 0), (0, pad_k)), **kw)
        e2 = jnp.pad(e2, ((0, 0), (0, pad_k)), constant_values=1.0, **kw)
        e1 = jnp.pad(e1, ((0, 0), (0, pad_k)), constant_values=1.0, **kw)
        e0 = jnp.pad(e0, ((0, 0), (0, pad_k)), **kw)
        eb = jnp.pad(eb, ((0, 0), (0, pad_k)), **kw)
        d_lo = jnp.pad(d_lo, ((0, 0), (0, pad_k)), **kw)
        d_hi = jnp.pad(d_hi, ((0, 0), (0, pad_k)), **kw)
    if pad_b:
        c2 = jnp.pad(c2, ((0, pad_b), (0, 0)), constant_values=1.0)
        c1 = jnp.pad(c1, ((0, pad_b), (0, 0)), constant_values=1.0)
        c0 = jnp.pad(c0, ((0, pad_b), (0, 0)))
        e2 = jnp.pad(e2, ((0, pad_b), (0, 0)), constant_values=1.0)
        e1 = jnp.pad(e1, ((0, pad_b), (0, 0)), constant_values=1.0)
        e0 = jnp.pad(e0, ((0, pad_b), (0, 0)))
        eb = jnp.pad(eb, ((0, pad_b), (0, 0)))
        d_lo = jnp.pad(d_lo, ((0, pad_b), (0, 0)))
        d_hi = jnp.pad(d_hi, ((0, pad_b), (0, 0)))
        tau_star = jnp.pad(tau_star, (0, pad_b))
        T = jnp.pad(T, (0, pad_b))
        total = jnp.pad(total, (0, pad_b))

    bp, kp = c2.shape
    col = lambda v: v.reshape(bp, 1).astype(dtype)
    nb = bp // block_b
    mat_spec = pl.BlockSpec((block_b, kp), lambda i: (i, 0))
    col_spec = pl.BlockSpec((block_b, 1), lambda i: (i, 0))

    out = pl.pallas_call(
        _energy_kernel,
        grid=(nb,),
        in_specs=[col_spec, mat_spec, mat_spec, mat_spec, col_spec,
                  mat_spec, mat_spec, mat_spec, mat_spec,
                  mat_spec, mat_spec, col_spec],
        out_specs=col_spec,
        out_shape=jax.ShapeDtypeStruct((bp, 1), dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
        ),
        interpret=interpret,
    )(col(tau_star), c2, c1, c0, col(T), e2, e1, e0, eb,
      d_lo, d_hi, col(total))
    return out.reshape(-1)[:b]
