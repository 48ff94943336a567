"""Pallas TPU kernel: staleness-weighted federated aggregation.

The orchestrator's hot loop is `w_global = sum_k alpha_k * w_k` over K
stacked learner models — a memory-bound contraction over a small leading
axis. The fused kernel streams one (K, block_n) VMEM tile per grid step
and writes the (block_n,) weighted sum, touching every byte exactly once;
alpha lives in SMEM-friendly (1, K) form.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["fed_agg_pallas"]


def _kernel(x_ref, w_ref, o_ref):
    x = x_ref[...].astype(jnp.float32)          # (K, bn)
    w = w_ref[0, :].astype(jnp.float32)         # (K,)
    o_ref[...] = (w[:, None] * x).sum(axis=0, keepdims=True).astype(o_ref.dtype)


def fed_agg_pallas(stacked, weights, *, block_n: int = 2048, interpret: bool = False):
    """stacked: (K, ...) learner-stacked tensor; weights: (K,).
    Returns the weighted sum over axis 0 with the input dtype."""
    k = stacked.shape[0]
    orig_shape = stacked.shape[1:]
    flat = stacked.reshape(k, -1)
    n = flat.shape[1]
    pad = (-n) % block_n
    if pad:
        flat = jnp.pad(flat, ((0, 0), (0, pad)))
    nb = flat.shape[1] // block_n

    out = pl.pallas_call(
        _kernel,
        grid=(nb,),
        in_specs=[
            pl.BlockSpec((k, block_n), lambda i: (0, i)),
            pl.BlockSpec((1, k), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_n), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, flat.shape[1]), stacked.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
        ),
        interpret=interpret,
    )(flat, weights.reshape(1, k))
    out = out.reshape(-1)
    if pad:
        out = out[:n]
    return out.reshape(orig_shape)
