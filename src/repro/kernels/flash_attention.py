"""Pallas TPU flash attention (GQA, causal, optional sliding window).

Online-softmax blockwise attention: grid = (batch, q_head, q_blocks,
kv_blocks) with the kv dimension innermost/"arbitrary" so the running
(m, l, acc) statistics live in VMEM scratch across kv iterations. Fully
masked kv blocks (beyond the causal frontier or outside the sliding
window) are skipped with ``pl.when`` — on TPU this prunes ~half the
compute for causal attention, which the pure-jnp reference (scan over all
chunks + where-mask) cannot do.

Block shapes are (block_q, head_dim) / (block_k, head_dim) VMEM tiles;
head_dim is kept whole (128 for every assigned arch — MXU-aligned).

Validated against ``repro.kernels.ref.flash_attention_ref`` in
interpret mode; on real TPU drop ``interpret=True``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["flash_attention_pallas"]

NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
            causal: bool, window: int | None, block_q: int, block_k: int,
            nk: int, sq: int, skv: int):
    qi = pl.program_id(2)
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q_start = qi * block_q
    k_start = ki * block_k

    # block-level skip tests (static under the grid, dynamic in program ids)
    live = jnp.asarray(True)
    if causal:
        live &= k_start <= q_start + block_q - 1
    if window is not None:
        live &= k_start + block_k - 1 >= q_start - window + 1

    @pl.when(live)
    def _compute():
        q = q_ref[0, :, 0, :].astype(jnp.float32)          # (bq, d)
        k = k_ref[0, :, 0, :].astype(jnp.float32)          # (bk, d)
        v = v_ref[0, :, 0, :].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) / (q.shape[-1] ** 0.5)                            # (bq, bk)

        qpos = q_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        mask = jnp.ones_like(s, dtype=jnp.bool_)
        if causal:
            mask &= qpos >= kpos
        if window is not None:
            mask &= qpos - kpos < window
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, s.max(axis=1))
        p = jnp.exp(s - m_new[:, None])
        p = jnp.where(mask, p, 0.0)
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * corr + p.sum(axis=1)
        acc_ref[...] = acc_ref[...] * corr[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        m_ref[...] = m_new

    @pl.when(ki == nk - 1)
    def _flush():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, :, 0, :] = (acc_ref[...] / l[:, None]).astype(o_ref.dtype)


def flash_attention_pallas(
    q, k, v, *, causal: bool = True, window: int | None = None,
    block_q: int = 128, block_k: int = 128, interpret: bool = False,
):
    b, sq, h, d = q.shape
    _, skv, kv, _ = k.shape
    g = h // kv
    block_q = min(block_q, sq)
    block_k = min(block_k, skv)
    while sq % block_q:
        block_q -= 1
    while skv % block_k:
        block_k -= 1
    nq, nk = sq // block_q, skv // block_k

    kernel = functools.partial(
        _kernel, causal=causal, window=window, block_q=block_q,
        block_k=block_k, nk=nk, sq=sq, skv=skv,
    )
    return pl.pallas_call(
        kernel,
        grid=(b, h, nq, nk),
        in_specs=[
            pl.BlockSpec((1, block_q, 1, d), lambda bi, hi, qi, ki: (bi, qi, hi, 0)),
            pl.BlockSpec((1, block_k, 1, d), lambda bi, hi, qi, ki, g=g: (bi, ki, hi // g, 0)),
            pl.BlockSpec((1, block_k, 1, d), lambda bi, hi, qi, ki, g=g: (bi, ki, hi // g, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, 1, d), lambda bi, hi, qi, ki: (bi, qi, hi, 0)),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q, d), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(q, k, v)
