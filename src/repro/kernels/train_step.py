"""Pallas TPU megakernel: fused local-GD + weighted accumulate + flush.

Every scan fast path (``Orchestrator.run_fused``, the reallocating scan,
the async engine's jagged ``run_events``, the fleet engine's vmapped
per-fleet round) spends its step on the same composition:

  1. masked local GD — each of K learners runs ``tau_k`` gradient steps
     from its OWN start params on its masked shard
     (``fed.orchestrator.local_train_stacked``);
  2. a weighted accumulate of the trained locals
     (``acc' = acc + sum_k w_k * local_k``);
  3. the masked ``ops.fed_agg`` flush contraction into the server
     (``server' = keep * server + f * acc'``).

Unfused, that launches one XLA op per GD step per leaf plus the
aggregation contractions. This kernel runs the WHOLE composition as one
Pallas program with a grid over the K learners: grid step k DMAs learner
k's shard block and start params into VMEM, runs its ``tau_k`` GD steps
in an in-kernel ``fori_loop`` (``tau_k`` and ``w_k`` are SMEM scalars;
the data mask is applied inside the loss contraction), and adds
``w_k * local_k`` into output blocks that stay VMEM-resident across the
grid. The last grid step applies the flush. While learner k trains, the
pipeline fetches learner k+1's shard.

Numerics contract (pinned by ``tests/test_kernel_parity.py``): the kernel
matches the unfused ``local_train_stacked`` + accumulate + ``fed_agg``
composition (``kernels.ref.train_agg_step_ref``) to f32 tolerance
(``F32_RTOL``/``F32_ATOL`` per call, ``RUN_ATOL`` on params after a whole
engine run), not bitwise: each learner's GD runs 2-D matmuls where the
reference runs them batched over K, and the accumulate is a sequential
sum over the grid where the reference reduces the stacked axis in one
XLA reduction; both round differently in the last bits. Schedule-side
values (tau, d, versions, weights) never pass through the kernel and
stay exact.

Fusion boundary: one learner's working set — its (d_cap, F) shard block
(double-buffered), its params, gradients and activations, plus the
resident output leaves — must fit the scoped VMEM limit, which the kernel
raises to ``VMEM_LIMIT_BYTES`` (64 MiB; the v5e core has 128 MiB and XLA
keeps some of it for the surrounding program). K is not bounded by VMEM:
learners stream through the grid. For the paper's [784, 300, 124, 60, 10]
MLP the v5e compiler accepts d_cap = 6144 and refuses d_cap = 7168 (out
of VMEM), so the paper's largest shard, d_upper = 1800 at K = 10, fits
with room; ``tests/test_tpu_compile.py`` compiles that size. Larger models
or shards stay on the unfused path (the default everywhere). The loss_fn
is traced into the kernel body, so on a TPU it must lower to Mosaic: 2-D
matmuls, elementwise ops and last-axis reductions (``models.mlp.loss``
does; a gather such as ``take_along_axis`` does not).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["train_agg_step_pallas", "F32_RTOL", "F32_ATOL", "RUN_ATOL",
           "CHIP_RTOL", "CHIP_ATOL", "VMEM_LIMIT_BYTES"]

#: f32 tolerance of one interpreted kernel call against
#: ``train_agg_step_ref`` on the CPU (the two differ by ~1e-7)
F32_RTOL = 1e-5
F32_ATOL = 1e-6
#: absolute tolerance on params after a whole engine run, where the
#: per-step rounding differences compound over hundreds of GD steps
#: (up to 2.1e-4 measured after 3 cycles of ~80 steps at K=3)
RUN_ATOL = 1e-3
#: tolerance of one compiled call on a TPU v5e against the oracle run at
#: highest matmul precision on the same chip
CHIP_RTOL = 1e-3
CHIP_ATOL = 1e-3

#: scoped VMEM the kernel may use (v5e has 128 MiB of VMEM per core)
VMEM_LIMIT_BYTES = 64 * 1024 * 1024


def _block_shape(shape):
    """The 2-D (or higher) VMEM block of one model leaf: 0-D and 1-D
    leaves get leading unit dims so every block's last two dims equal the
    array's (Mosaic's tiling rule)."""
    return (1,) * max(0, 2 - len(shape)) + tuple(shape)


def _make_kernel(treedef, shapes, loss_fn, with_acc: bool):
    """Kernel body for grid step k over flattened model leaves. Ref
    layout: ``(tau, w, scal, x, y, m, *disp[, *server, *acc], *outs)``
    where ``outs`` is ``server' + acc'`` (with_acc) or the aggregated
    model (cycle form)."""
    L = len(shapes)

    def kernel(tau_ref, w_ref, scal_ref, x_ref, y_ref, m_ref, *refs):
        k = pl.program_id(0)
        last = pl.num_programs(0) - 1
        lr = scal_ref[0]
        batch = {"x": x_ref[0], "y": y_ref[0][:, 0], "mask": m_ref[0][:, 0]}
        start = [refs[i][0].reshape(shapes[i]) for i in range(L)]

        def gd(_, leaves):
            p = jax.tree_util.tree_unflatten(treedef, leaves)
            g = jax.grad(loss_fn)(p, batch)
            return jax.tree_util.tree_leaves(
                jax.tree_util.tree_map(lambda pk, gk: pk - lr * gk, p, g)
            )

        local = jax.lax.fori_loop(0, tau_ref[k], gd, start)
        contrib = [w_ref[k] * local[i].reshape(_block_shape(shapes[i]))
                   for i in range(L)]

        if not with_acc:
            outs = refs[L:]

            @pl.when(k == 0)
            def _():
                for i in range(L):
                    outs[i][...] = contrib[i]

            @pl.when(k > 0)
            def _():
                for i in range(L):
                    outs[i][...] += contrib[i]

            return

        server, acc = refs[L:2 * L], refs[2 * L:3 * L]
        out_server, out_acc = refs[3 * L:4 * L], refs[4 * L:5 * L]

        @pl.when(k == 0)
        def _():
            for i in range(L):
                out_acc[i][...] = acc[i][...] + contrib[i]

        @pl.when(k > 0)
        def _():
            for i in range(L):
                out_acc[i][...] += contrib[i]

        @pl.when(k == last)
        def _():
            keep, flush = scal_ref[1], scal_ref[2]
            for i in range(L):
                acc1 = out_acc[i][...]
                out_server[i][...] = keep * server[i][...] + flush * acc1
                out_acc[i][...] = (1.0 - flush) * acc1

    return kernel


def train_agg_step_pallas(disp, x, y, m, tau, weights, lr, *, loss_fn,
                          server=None, acc=None, keep=None, flush=None,
                          interpret: bool = False):
    """One fused train+aggregate step (see module docstring).

    disp : model pytree with a leading K learner axis on every leaf
    x : (K, d_cap, F); y, m : (K, d_cap); tau, weights : (K,)
    server, acc : model pytrees (no K axis) — the async accumulate/flush
        form; ``None`` selects the cycle form (plain weighted aggregation
        of the trained locals, ``keep``/``flush`` unused)
    keep, flush : f32 scalars — the flush contraction coefficients

    Returns ``(new_server, new_acc)``; ``new_acc`` is None in cycle form.
    """
    with_acc = acc is not None
    if with_acc and (server is None or keep is None or flush is None):
        raise ValueError("the accumulate/flush form needs server, keep "
                         "and flush alongside acc")
    if not with_acc and (server is not None or keep is not None
                        or flush is not None):
        raise ValueError("server/keep/flush have no meaning without acc "
                         "(cycle form aggregates the locals directly)")

    d_leaves, treedef = jax.tree_util.tree_flatten(disp)
    k, n, feat = x.shape
    shapes = [leaf.shape[1:] for leaf in d_leaves]
    blocks = [_block_shape(s) for s in shapes]
    zero = jnp.zeros((), jnp.float32)
    scal = jnp.stack([
        jnp.asarray(lr, jnp.float32),
        jnp.asarray(keep, jnp.float32) if with_acc else zero,
        jnp.asarray(flush, jnp.float32) if with_acc else zero,
    ])

    smem = pl.BlockSpec(memory_space=pltpu.MemorySpace.SMEM)

    def per_learner(block):
        nd = len(block)
        return pl.BlockSpec((1,) + block,
                            lambda i, nd=nd: (i,) + (0,) * nd)

    def resident(block):
        nd = len(block)
        return pl.BlockSpec(block, lambda i, nd=nd: (0,) * nd)

    in_specs = [smem, smem, smem, per_learner((n, feat)),
                per_learner((n, 1)), per_learner((n, 1))]
    in_specs += [per_learner(b) for b in blocks]
    operands = [
        jnp.asarray(tau, jnp.int32).reshape(k),
        jnp.asarray(weights, jnp.float32).reshape(k),
        scal, x, y.reshape(k, n, 1), m.reshape(k, n, 1),
    ]
    operands += [leaf.reshape((k,) + b) for leaf, b in zip(d_leaves, blocks)]
    out_shape = [jax.ShapeDtypeStruct(b, leaf.dtype)
                 for leaf, b in zip(d_leaves, blocks)]
    if with_acc:
        for tree in (server, acc):
            in_specs += [resident(b) for b in blocks]
            operands += [leaf.reshape(b) for leaf, b in
                         zip(jax.tree_util.tree_leaves(tree), blocks)]
        out_shape = out_shape * 2
    out_specs = [resident(s.shape) for s in out_shape]

    # The realloc scan and the fleet rounds trace this inside a
    # jax.enable_x64 scope, where the block index maps would return i64,
    # which Mosaic cannot lower. Every operand is 32-bit, so the kernel is
    # built in 32-bit mode.
    with jax.enable_x64(False):
        outs = pl.pallas_call(
            _make_kernel(treedef, shapes, loss_fn, with_acc),
            grid=(k,),
            in_specs=in_specs,
            out_specs=out_specs,
            out_shape=out_shape,
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary",),
                vmem_limit_bytes=VMEM_LIMIT_BYTES,
            ),
            interpret=interpret,
        )(*operands)

    outs = [o.reshape(s) for o, s in zip(outs, shapes * (1 + with_acc))]
    if with_acc:
        new_server = jax.tree_util.tree_unflatten(treedef, outs[:len(shapes)])
        new_acc = jax.tree_util.tree_unflatten(treedef, outs[len(shapes):])
        return new_server, new_acc
    return jax.tree_util.tree_unflatten(treedef, outs), None
