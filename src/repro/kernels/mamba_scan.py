"""Pallas TPU kernel for the Mamba (S6) selective scan.

Mamba-1's decay is input-dependent PER (channel, state) pair
(da_t = exp(dt_t * A)), which resists the chunked-matmul reformulation
that works for RWKV-6 (see ``repro.models.rwkv6.wkv_chunked`` — there the
intra-chunk exponents contract over the channel axis). The TPU answer is
the same as the CUDA kernel's: keep the (block_d, N) state resident in
fast memory (VMEM here, SRAM there) and stream the time axis.

Grid = (batch, d_inner blocks, time chunks), time innermost/"arbitrary";
the state scratch persists across time chunks, so HBM traffic is exactly
inputs + outputs — the jnp ``lax.scan`` reference round-trips the
(B, d_inner, N) state every step, which is why jamba training is
memory-bound at ~139 s/step (EXPERIMENTS §Roofline).

    h_t = exp(dt_t * A) * h_{t-1} + (dt_t * x_t) B_t
    y_t = h_t . C_t
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["mamba_scan_pallas"]


def _kernel(dt_ref, x_ref, b_ref, c_ref, a_ref, h0_ref, y_ref, h_out_ref, state,
            *, block_t: int, nt: int):
    ti = pl.program_id(2)

    @pl.when(ti == 0)
    def _init():
        state[...] = h0_ref[0].astype(jnp.float32)

    a = a_ref[...].astype(jnp.float32)                     # (bd, N)

    def step(t, _):
        dt = dt_ref[0, t, :].astype(jnp.float32)           # (bd,)
        x = x_ref[0, t, :].astype(jnp.float32)             # (bd,)
        b = b_ref[0, t, :].astype(jnp.float32)             # (N,)
        c = c_ref[0, t, :].astype(jnp.float32)             # (N,)
        da = jnp.exp(dt[:, None] * a)                      # (bd, N)
        state[...] = state[...] * da + (dt * x)[:, None] * b[None, :]
        y_ref[0, t, :] = (state[...] * c[None, :]).sum(axis=1).astype(y_ref.dtype)
        return ()

    jax.lax.fori_loop(0, block_t, step, ())

    @pl.when(ti == nt - 1)
    def _flush():
        h_out_ref[0] = state[...].astype(h_out_ref.dtype)


def mamba_scan_pallas(dt, x, b, c, a, h0=None, *, block_d: int = 512,
                      block_t: int = 64, interpret: bool = False):
    """dt, x: (B, S, D); b, c: (B, S, N); a: (D, N) (negative);
    h0: (B, D, N) f32 or None. Returns (y (B,S,D) f32, h_last (B,D,N) f32)
    — matching the scan inside ``repro.models.mamba.apply``."""
    bsz, s, d = dt.shape
    n = b.shape[-1]
    if h0 is None:
        h0 = jnp.zeros((bsz, d, n), jnp.float32)
    block_d = min(block_d, d)
    while d % block_d:
        block_d -= 1
    block_t = min(block_t, s)
    while s % block_t:
        block_t -= 1
    nd, nt = d // block_d, s // block_t

    kernel = functools.partial(_kernel, block_t=block_t, nt=nt)
    chan_spec = pl.BlockSpec((1, block_t, block_d), lambda bi, di, ti: (bi, ti, di))
    state_spec = pl.BlockSpec((1, block_t, n), lambda bi, di, ti: (bi, ti, 0))
    y, h_last = pl.pallas_call(
        kernel,
        grid=(bsz, nd, nt),
        in_specs=[
            chan_spec, chan_spec, state_spec, state_spec,
            pl.BlockSpec((block_d, n), lambda bi, di, ti: (di, 0)),
            pl.BlockSpec((1, block_d, n), lambda bi, di, ti: (bi, di, 0)),
        ],
        out_specs=[
            chan_spec,
            pl.BlockSpec((1, block_d, n), lambda bi, di, ti: (bi, di, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bsz, s, d), jnp.float32),
            jax.ShapeDtypeStruct((bsz, d, n), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((block_d, n), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(dt, x, b, c, a, h0)
    return y, h_last
