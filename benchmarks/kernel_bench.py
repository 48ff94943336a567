"""Micro-benchmarks of the kernel hot spots (jnp reference path, CPU):
wall time per call for flash attention, WKV6, fed-agg, SwiGLU.

Prints CSV: name,us_per_call,derived
(the Pallas kernels target TPU; on this CPU container we time the jnp
reference and verify the Pallas interpret path agrees — the derived column
is achieved GFLOP/s of the reference.)
"""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp

from repro.kernels import ops


def _time(fn, *args, iters=5, **kw):
    out = fn(*args, **kw)
    jax.block_until_ready(out)
    t0 = time.time()
    for _ in range(iters):
        out = fn(*args, **kw)
    jax.block_until_ready(out)
    return (time.time() - t0) / iters * 1e6


def run():
    rows = []
    key = jax.random.key(0)

    b, s, h, kv, d = 2, 1024, 8, 4, 64
    q = jax.random.normal(key, (b, s, h, d), jnp.float32)
    k = jax.random.normal(key, (b, s, kv, d), jnp.float32)
    v = jax.random.normal(key, (b, s, kv, d), jnp.float32)
    fa = jax.jit(lambda q, k, v: ops.flash_attention(q, k, v, causal=True, chunk=256))
    us = _time(fa, q, k, v)
    flops = 4 * b * h * s * s * d / 2
    rows.append(("flash_attention_1k", us, f"{flops/us*1e-3:.1f}GFLOPs"))

    r_ = jax.random.normal(key, (b, 512, 4, 64), jnp.float32) * 0.5
    w_ = jax.nn.sigmoid(jax.random.normal(key, (b, 512, 4, 64))) * 0.5 + 0.45
    u_ = jax.random.normal(key, (4, 64)) * 0.1
    wkv = jax.jit(lambda r, k, v, w, u: ops.wkv6(r, k, v, w, u)[0])
    us = _time(wkv, r_, r_, r_, w_, u_)
    rows.append(("wkv6_512", us, f"state={4*64*64*4}B"))

    stacked = jax.random.normal(key, (10, 1_000_000), jnp.float32)
    wts = jax.nn.softmax(jax.random.normal(key, (10,)))
    agg = jax.jit(ops.fed_agg)
    us = _time(agg, stacked, wts)
    rows.append(("fed_agg_10x1M", us, f"{10*4e6/us*1e-3:.1f}GB/s"))

    x = jax.random.normal(key, (512, 512), jnp.float32)
    wg = jax.random.normal(key, (512, 2048)) * 0.02
    wd = jax.random.normal(key, (2048, 512)) * 0.02
    sg = jax.jit(lambda x: ops.swiglu_fused(x, wg, wg, wd))
    us = _time(sg, x)
    rows.append(("swiglu_512x2048", us, f"{3*2*512*512*2048/us*1e-3:.1f}GFLOPs"))
    return rows


def main(quick: bool = False):
    print("name,us_per_call,derived")
    for name, us, derived in run():
        print(f"{name},{us:.1f},{derived}")


if __name__ == "__main__":
    main()


# ---------------------------------------------------------------------------
# megakernel: the fused train+aggregate step (ops.train_agg_step)
# ---------------------------------------------------------------------------

def _megakernel_case(k: int, n: int, tau_hi: int, layers, seed: int):
    """f32 fixtures in the exact shapes the async scan feeds the kernel."""
    import numpy as np

    from repro.models import mlp

    rng = np.random.default_rng(seed)
    stack = [mlp.init(jax.random.key(int(s)), layers)
             for s in rng.integers(2**31, size=k)]
    disp = jax.tree_util.tree_map(lambda *ls: jnp.stack(ls), *stack)
    x = jnp.asarray(rng.standard_normal((k, n, layers[0])), jnp.float32)
    y = jnp.asarray(rng.integers(0, layers[-1], (k, n)), jnp.int32)
    m = jnp.asarray(rng.integers(0, 2, (k, n)), jnp.float32)
    tau = jnp.asarray(rng.integers(1, tau_hi + 1, (k,)), jnp.int32)
    w = jnp.asarray(rng.uniform(0.1, 1.0, (k,)), jnp.float32)
    return disp, x, y, m, tau, w


def _megakernel_parity(layers=(16, 16, 4), seed=0) -> str:
    """Fixed-seed gate: the Pallas megakernel (compiled on a TPU,
    interpreted elsewhere) must match the unfused local_train_stacked +
    fed_agg composition, run at highest matmul precision, to the kernel's
    stated f32 tolerance before any timing row is merged. Raises on the
    first leaf outside it; returns the tolerance it held."""
    import numpy as np

    from repro.kernels import train_step
    from repro.models import mlp

    on_tpu = jax.default_backend() == "tpu"
    rtol, atol = ((train_step.CHIP_RTOL, train_step.CHIP_ATOL) if on_tpu
                  else (train_step.F32_RTOL, train_step.F32_ATOL))
    disp, x, y, m, tau, w = _megakernel_case(4, 16, 3, list(layers), seed)
    lr = jnp.float32(0.05)
    with jax.default_matmul_precision("highest"):
        want, _ = ops.train_agg_step(disp, x, y, m, tau, w, lr,
                                     loss_fn=mlp.loss, max_tau=int(tau.max()))
    got, _ = ops.train_agg_step(disp, x, y, m, tau, w, lr, loss_fn=mlp.loss,
                                use_pallas=True, interpret=not on_tpu)
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=rtol, atol=atol,
            err_msg="megakernel parity gate failed: fused != unfused",
        )
    return f"rtol={rtol:g} atol={atol:g}"


def megakernel_rows(quick: bool = True):
    """Per-step wall time, fused vs unfused. The fused row is timed only
    on a real accelerator backend — interpret mode is a correctness
    vehicle, not a performance path, and is EXCLUDED from timing."""
    from repro.models import mlp

    cases = [("mlp_paper_k4_n64_tau16", 4, 64, 16, mlp.PAPER_LAYERS)]
    if not quick:
        cases.append(("mlp_paper_k10_n128_tau16", 10, 128, 16,
                      mlp.PAPER_LAYERS))
    backend = jax.default_backend()
    lr = jnp.float32(0.05)
    rows = []
    for name, k, n, tau_hi, layers in cases:
        operands = _megakernel_case(k, n, tau_hi, layers, seed=0)
        max_tau = int(operands[4].max())

        unfused = jax.jit(lambda d_, x_, y_, m_, t_, w_: ops.train_agg_step(
            d_, x_, y_, m_, t_, w_, lr, loss_fn=mlp.loss, max_tau=max_tau)[0])
        rows.append({"case": name, "path": "unfused", "backend": backend,
                     "us_per_step": round(_time(unfused, *operands), 1)})

        if backend != "cpu":
            fused = jax.jit(lambda d_, x_, y_, m_, t_, w_: ops.train_agg_step(
                d_, x_, y_, m_, t_, w_, lr, loss_fn=mlp.loss,
                use_pallas=True)[0])
            rows.append({"case": name, "path": "pallas", "backend": backend,
                         "us_per_step": round(_time(fused, *operands), 1)})
        else:
            rows.append({"case": name, "path": "pallas", "backend": backend,
                         "us_per_step": None,
                         "note": "interpret-only on CPU; excluded from timing"})
    return rows


def megakernel_main(quick: bool = False):
    """`--only megakernel`: the tolerance parity gate first, then the
    per-step fused-vs-unfused table merged under
    BENCH_alloc.json[megakernel]."""
    from benchmarks.alloc_bench import _merge_out

    tol = _megakernel_parity()
    print(f"parity: fused == unfused within {tol} on fixed seed", flush=True)
    rows = megakernel_rows(quick=quick)
    for r in rows:
        print(f"{r['case']},{r['path']},{r['us_per_step']}")
    _merge_out("megakernel", {"parity_tolerance": tol, "rows": rows})
