"""Pallas TPU kernel for the RWKV-6 WKV recurrence.

TPU adaptation of the CUDA wkv6 kernel: grid = (batch, head, time-chunks)
with the time dimension innermost/"arbitrary"; the (hd x hd) state matrix
lives in VMEM scratch across chunk iterations (never spilled to HBM, the
whole point of the fused kernel — the jnp `lax.scan` reference round-trips
the state through HBM every step). Inside a chunk the recurrence is a
`fori_loop` of rank-1 updates: per step
    y_t = r_t (S + diag(u) k_t v_t^T)
    S  <- diag(w_t) S + k_t v_t^T

r/k/v/w chunks are (block_t, hd) VMEM tiles; u is (1, hd); the final state
is written once at the last chunk (grid revisiting guarantees ordering).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["wkv6_pallas"]


def _kernel(r_ref, k_ref, v_ref, w_ref, u_ref, s0_ref, y_ref, s_out_ref, state,
            *, block_t: int, nt: int):
    ti = pl.program_id(2)

    @pl.when(ti == 0)
    def _init():
        state[...] = s0_ref[0, 0].astype(jnp.float32)

    u = u_ref[0, 0].astype(jnp.float32)                    # (hd,)

    def step(t, _):
        r = r_ref[0, t, 0, :].astype(jnp.float32)          # (hd,)
        k = k_ref[0, t, 0, :].astype(jnp.float32)
        v = v_ref[0, t, 0, :].astype(jnp.float32)
        w = w_ref[0, t, 0, :].astype(jnp.float32)
        kv = k[:, None] * v[None, :]                       # (hd, hd)
        y = ((state[...] + u[:, None] * kv) * r[:, None]).sum(axis=0)
        y_ref[0, t, 0, :] = y.astype(y_ref.dtype)
        state[...] = w[:, None] * state[...] + kv
        return ()

    jax.lax.fori_loop(0, block_t, step, ())

    @pl.when(ti == nt - 1)
    def _flush():
        s_out_ref[0, 0] = state[...].astype(s_out_ref.dtype)


def wkv6_pallas(r, k, v, w, u, s0=None, *, block_t: int = 64, interpret: bool = False):
    """r,k,v,w: (B,S,H,hd); u: (H,hd); s0: (B,H,hd,hd) f32 or None.
    Returns (y (B,S,H,hd) f32, s_last (B,H,hd,hd) f32) — matching
    ``repro.models.rwkv6.wkv_scan``."""
    b, s, h, hd = r.shape
    if s0 is None:
        s0 = jnp.zeros((b, h, hd, hd), jnp.float32)
    block_t = min(block_t, s)
    while s % block_t:
        block_t -= 1
    nt = s // block_t

    kernel = functools.partial(_kernel, block_t=block_t, nt=nt)
    seq_spec = pl.BlockSpec((1, block_t, 1, hd), lambda bi, hi, ti: (bi, ti, hi, 0))
    y, s_last = pl.pallas_call(
        kernel,
        grid=(b, h, nt),
        in_specs=[
            seq_spec, seq_spec, seq_spec, seq_spec,
            pl.BlockSpec((1, 1, hd), lambda bi, hi, ti: (0, hi, 0)),
            pl.BlockSpec((1, 1, hd, hd), lambda bi, hi, ti: (bi, hi, 0, 0)),
        ],
        out_specs=[
            seq_spec,
            pl.BlockSpec((1, 1, hd, hd), lambda bi, hi, ti: (bi, hi, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, s, h, hd), jnp.float32),
            jax.ShapeDtypeStruct((b, h, hd, hd), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((hd, hd), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(r, k, v, w, u[None], s0)
    return y, s_last
