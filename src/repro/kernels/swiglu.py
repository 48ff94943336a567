"""Pallas TPU kernel: fused SwiGLU FFN  down( silu(x Wg) * (x Wu) ).

Grid = (m_blocks, f_blocks) with the hidden/f dimension innermost: the
(block_m, d) output accumulator stays in VMEM scratch while gate/up/down
weight tiles stream through, so the (m, f) silu(g)*u intermediate is never
materialized to HBM — that is the fusion win over the 3-matmul jnp
reference (which writes g, u, h to HBM at (tokens x d_ff) each).

Tiles: x (block_m, d), Wg/Wu (d, block_f), Wd (block_f, d) — all
MXU-aligned multiples of 128 for the assigned architectures.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["swiglu_pallas"]


def _kernel(x_ref, wg_ref, wu_ref, wd_ref, o_ref, acc, *, nf: int):
    fi = pl.program_id(1)

    @pl.when(fi == 0)
    def _init():
        acc[...] = jnp.zeros_like(acc)

    x = x_ref[...].astype(jnp.float32)
    g = jax.lax.dot(x, wg_ref[...].astype(jnp.float32),
                    preferred_element_type=jnp.float32)
    u = jax.lax.dot(x, wu_ref[...].astype(jnp.float32),
                    preferred_element_type=jnp.float32)
    h = (g * jax.nn.sigmoid(g)) * u
    acc[...] += jax.lax.dot(h, wd_ref[...].astype(jnp.float32),
                            preferred_element_type=jnp.float32)

    @pl.when(fi == nf - 1)
    def _flush():
        o_ref[...] = acc[...].astype(o_ref.dtype)


def swiglu_pallas(x, w_gate, w_up, w_down, *, block_m: int = 256,
                  block_f: int = 512, interpret: bool = False):
    """x: (..., d); w_gate/w_up: (d, f); w_down: (f, d)."""
    orig_shape = x.shape
    d = x.shape[-1]
    f = w_gate.shape[1]
    xm = x.reshape(-1, d)
    m = xm.shape[0]
    block_m = min(block_m, m)
    while m % block_m:
        block_m -= 1
    block_f = min(block_f, f)
    while f % block_f:
        block_f -= 1
    nm, nf = m // block_m, f // block_f

    out = pl.pallas_call(
        functools.partial(_kernel, nf=nf),
        grid=(nm, nf),
        in_specs=[
            pl.BlockSpec((block_m, d), lambda mi, fi: (mi, 0)),
            pl.BlockSpec((d, block_f), lambda mi, fi: (0, fi)),
            pl.BlockSpec((d, block_f), lambda mi, fi: (0, fi)),
            pl.BlockSpec((block_f, d), lambda mi, fi: (fi, 0)),
        ],
        out_specs=pl.BlockSpec((block_m, d), lambda mi, fi: (mi, 0)),
        out_shape=jax.ShapeDtypeStruct((m, d), x.dtype),
        scratch_shapes=[pltpu.VMEM((block_m, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(xm, w_gate, w_up, w_down)
    return out.reshape(orig_shape)
