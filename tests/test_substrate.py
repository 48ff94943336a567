"""Substrate unit tests: optimizers, schedules-free LR handling, layers,
data pipeline determinism, time-model algebra."""

import numpy as np
import pytest
from _prop import given, settings, st  # hypothesis, or fixed-seed fallback

import jax
import jax.numpy as jnp

from repro.core.time_model import ChannelParams, TimeModel, indoor_80211_profile
from repro.data.pipeline import synthetic_mnist, token_batches
from repro.models.layers import layer_norm, rms_norm, rope
from repro.optim.optimizers import adamw, clip_by_global_norm, get_optimizer, momentum, sgd

KEY = jax.random.key(0)


# ---------------------------------------------------------------------------
# optimizers
# ---------------------------------------------------------------------------

def _quad_loss(params):
    return jnp.sum((params["w"] - 3.0) ** 2)


@pytest.mark.parametrize("name", ["sgd", "momentum", "adam", "adamw"])
def test_optimizers_converge_on_quadratic(name):
    opt = get_optimizer(name, 0.1)
    params = {"w": jnp.zeros(4)}
    state = opt.init(params)
    for _ in range(200):
        g = jax.grad(_quad_loss)(params)
        params, state = opt.apply(g, state, params)
    # adamw's weight decay biases the fixed point slightly below 3.0
    tol = 0.2 if name == "adamw" else 1e-2
    np.testing.assert_allclose(np.asarray(params["w"]), 3.0, atol=tol)


def test_adam_matches_reference_first_step():
    opt = adamw(lr=0.001, wd=0.0)
    params = {"w": jnp.ones(3)}
    state = opt.init(params)
    g = {"w": jnp.asarray([1.0, -2.0, 0.5])}
    new, _ = opt.apply(g, state, params)
    # first Adam step is -lr * sign(g) (bias-corrected m/sqrt(v) = sign)
    np.testing.assert_allclose(np.asarray(new["w"]), 1.0 - 0.001 * np.sign([1.0, -2.0, 0.5]), rtol=1e-5)


def test_clip_by_global_norm():
    g = {"a": jnp.asarray([3.0, 4.0])}          # norm 5
    clipped, gn = clip_by_global_norm(g, 1.0)
    assert float(gn) == pytest.approx(5.0)
    np.testing.assert_allclose(np.asarray(clipped["a"]), [0.6, 0.8], rtol=1e-6)
    same, _ = clip_by_global_norm(g, 10.0)
    np.testing.assert_allclose(np.asarray(same["a"]), [3.0, 4.0])


def test_optimizer_state_dtype_f32_for_bf16_params():
    opt = adamw(lr=1e-3)
    params = {"w": jnp.ones(3, jnp.bfloat16)}
    state = opt.init(params)
    assert state["m"]["w"].dtype == jnp.float32
    g = {"w": jnp.ones(3, jnp.bfloat16)}
    new, state = opt.apply(g, state, params)
    assert new["w"].dtype == jnp.bfloat16


# ---------------------------------------------------------------------------
# layers
# ---------------------------------------------------------------------------

def test_rms_norm_unit_scale():
    x = jax.random.normal(KEY, (4, 64)) * 7.0
    out = rms_norm(x, jnp.ones(64))
    rms = jnp.sqrt(jnp.mean(out.astype(jnp.float32) ** 2, axis=-1))
    np.testing.assert_allclose(np.asarray(rms), 1.0, atol=1e-3)


def test_layer_norm_zero_mean():
    x = jax.random.normal(KEY, (4, 64)) + 5.0
    out = layer_norm(x, jnp.ones(64), jnp.zeros(64))
    np.testing.assert_allclose(np.asarray(out.mean(-1)), 0.0, atol=1e-4)


def test_rope_preserves_norm_and_relative_phase():
    x = jax.random.normal(KEY, (1, 8, 2, 32))
    pos = jnp.broadcast_to(jnp.arange(8), (1, 8))
    out = rope(x, pos, theta=10000.0)
    np.testing.assert_allclose(
        np.linalg.norm(np.asarray(out), axis=-1),
        np.linalg.norm(np.asarray(x), axis=-1),
        rtol=1e-5,
    )
    # dot(q_i, k_j) depends only on i - j: shift both positions by 3
    q, k = x[:, :1], x[:, 1:2]
    d1 = jnp.einsum("bshd,bshd->", rope(q, pos[:, :1]), rope(k, pos[:, 1:2]))
    d2 = jnp.einsum(
        "bshd,bshd->", rope(q, pos[:, :1] + 3), rope(k, pos[:, 1:2] + 3)
    )
    assert float(jnp.abs(d1 - d2)) < 1e-3


# ---------------------------------------------------------------------------
# data
# ---------------------------------------------------------------------------

def test_synthetic_mnist_deterministic():
    a, _ = synthetic_mnist(500, n_test=10, seed=7)
    b, _ = synthetic_mnist(500, n_test=10, seed=7)
    np.testing.assert_array_equal(a.x, b.x)
    c, _ = synthetic_mnist(500, n_test=10, seed=8)
    assert not np.array_equal(a.x, c.x)


def test_token_batches_shapes_and_learnability():
    gen = token_batches(np.random.default_rng(0), batch=4, seq=33, vocab=97)
    b = next(gen)
    assert b["tokens"].shape == (4, 32) and b["labels"].shape == (4, 32)
    assert (b["labels"][:, :-1] == b["tokens"][:, 1:]).all()
    assert b["tokens"].max() < 97


# ---------------------------------------------------------------------------
# time model algebra (hypothesis)
# ---------------------------------------------------------------------------

@settings(max_examples=30, deadline=None)
@given(
    k=st.integers(2, 12),
    seed=st.integers(0, 999),
    t=st.floats(5.0, 50.0),
)
def test_tau_d_inverse_maps(k, seed, t):
    from repro.core import mnist_dnn_cost

    cost = mnist_dnn_cost()
    tm = TimeModel.build(
        indoor_80211_profile(k, seed=seed),
        model_complexity_flops=cost.flops_per_sample,
        model_size_bits=cost.model_bits,
    )
    d = np.linspace(50, 500, k)
    tau = tm.tau_of_d(d, t)
    d_back = tm.d_of_tau(tau, t)
    np.testing.assert_allclose(d_back, d, rtol=1e-9)
    np.testing.assert_allclose(tm.cycle_time(tau, d), t, rtol=1e-9)


def test_channel_rate_monotone_in_gain():
    lo = ChannelParams(gain=1e-9).rate_bps()
    hi = ChannelParams(gain=1e-7).rate_bps()
    assert hi > lo > 0


# ---------------------------------------------------------------------------
# schedules
# ---------------------------------------------------------------------------

def test_warmup_cosine_shape():
    from repro.optim.schedules import warmup_cosine

    f = warmup_cosine(1.0, warmup_steps=10, total_steps=110, final_frac=0.1)
    assert float(f(0)) == 0.0
    assert float(f(10)) == pytest.approx(1.0)
    assert float(f(5)) == pytest.approx(0.5)
    assert float(f(110)) == pytest.approx(0.1, abs=1e-6)
    vals = [float(f(i)) for i in range(10, 111, 10)]
    assert all(a >= b for a, b in zip(vals, vals[1:]))  # monotone decay


def test_warmup_linear_decay_endpoints():
    from repro.optim.schedules import warmup_linear_decay

    f = warmup_linear_decay(2.0, warmup_steps=4, total_steps=20)
    assert float(f(0)) == 0.0
    assert float(f(4)) == pytest.approx(2.0)
    assert float(f(20)) == pytest.approx(0.0, abs=1e-6)


# ---------------------------------------------------------------------------
# batched JAX PGD allocator (fleet-scale scheduling tick)
# ---------------------------------------------------------------------------

def test_pgd_relaxed_batch_vmapped_fleets():
    from repro.core import mnist_dnn_cost
    from repro.core.solver_numeric import pgd_relaxed_batch

    cost = mnist_dnn_cost()
    fleets = []
    for seed in (0, 1, 2, 3):
        tm = TimeModel.build(
            indoor_80211_profile(6, seed=seed),
            model_complexity_flops=cost.flops_per_sample,
            model_size_bits=cost.model_bits,
        )
        fleets.append(tm)
    c2 = jnp.stack([jnp.asarray(t.c2) for t in fleets])
    c1 = jnp.stack([jnp.asarray(t.c1) for t in fleets])
    c0 = jnp.stack([jnp.asarray(t.c0) for t in fleets])
    total = jnp.full((4,), 3000.0)
    d_lo = jnp.full((4,), 100.0)
    d_hi = jnp.full((4,), 1500.0)
    d0 = jnp.full((4, 6), 500.0)
    T = jnp.full((4,), 15.0)
    tau, d = pgd_relaxed_batch(d0, c2, c1, c0, T, d_lo, d_hi, total)
    assert tau.shape == (4, 6) and d.shape == (4, 6)
    np.testing.assert_allclose(np.asarray(d.sum(1)), 3000.0, rtol=1e-3)
    assert np.all(np.asarray(d) >= 100.0 - 1e-3)
    assert np.all(np.asarray(d) <= 1500.0 + 1e-3)
    # relaxed staleness small: spread of tau within each fleet
    spread = np.asarray(tau.max(1) - tau.min(1))
    assert np.all(spread < 3.0)


# ---------------------------------------------------------------------------
# fed runtime lowers on a mesh (learner axis sharded over data)
# ---------------------------------------------------------------------------

def test_local_train_lowers_sharded_over_learners():
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.fed.orchestrator import local_train
    from repro.models import mlp

    mesh = jax.make_mesh((1, 1), ("data", "model"))
    k, dmax, feat = 4, 32, 784
    params = mlp.init(jax.random.key(0))
    x = jax.ShapeDtypeStruct((k, dmax, feat), jnp.float32)
    y = jax.ShapeDtypeStruct((k, dmax), jnp.int32)
    m = jax.ShapeDtypeStruct((k, dmax), jnp.float32)
    tau = jax.ShapeDtypeStruct((k,), jnp.int32)
    lsh = NamedSharding(mesh, P("data"))
    import functools

    fn = functools.partial(local_train, max_tau=4, loss_fn=mlp.loss)
    with mesh:  # Mesh is the context manager (jax.set_mesh is newer-jax only)
        lowered = jax.jit(
            fn,
            in_shardings=(None, lsh, lsh, lsh, lsh, None),
        ).lower(params, x, y, m, tau, jnp.float32(0.1))
        compiled = lowered.compile()
    assert compiled is not None


def test_compile_cache_dir_from_env_is_left_to_jax(monkeypatch, tmp_path):
    """With JAX_COMPILATION_CACHE_DIR set, the helper returns it and sets
    no directory itself (JAX reads the variable)."""
    from repro.launch.compile_cache import CACHE_ENV, enable_compile_cache

    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv(CACHE_ENV, str(tmp_path))
    assert enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_defaults_to_fixed_checkout_dir(monkeypatch):
    """Without the variable the cache goes to ``<checkout>/.jax_cache``: a
    fixed path (part of each entry's key), never a temp or per-run one."""
    from pathlib import Path

    from repro.launch.compile_cache import CACHE_ENV, enable_compile_cache

    before = jax.config.jax_compilation_cache_dir
    monkeypatch.delenv(CACHE_ENV, raising=False)
    try:
        path = enable_compile_cache()
        assert path == enable_compile_cache()
        assert Path(path) == Path(__file__).resolve().parents[1] / ".jax_cache"
        assert jax.config.jax_compilation_cache_dir == path
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


def test_compile_cache_keys_hold_op_metadata(monkeypatch, tmp_path):
    """Cache keys include op metadata (the engines' named scopes, which
    profiles read), with source paths relative to the checkout: a program
    that changes only its scopes is compiled again, and a checkout that
    moves still hits."""
    import re
    from pathlib import Path

    from repro.launch import compile_cache

    keys = ("jax_compilation_cache_include_metadata_in_key",
            "jax_hlo_source_file_canonicalization_regex")
    before = {k: getattr(jax.config, k) for k in keys}
    monkeypatch.setenv(compile_cache.CACHE_ENV, str(tmp_path))
    try:
        compile_cache.enable_compile_cache()
        assert jax.config.jax_compilation_cache_include_metadata_in_key
        pattern = jax.config.jax_hlo_source_file_canonicalization_regex
        source = str(Path(compile_cache.__file__).resolve())
        assert re.sub(pattern, "", source) == "src/repro/launch/compile_cache.py"
    finally:
        for k, v in before.items():
            jax.config.update(k, v)


def test_chip_smoke_refuses_to_run_without_a_tpu():
    """``chip_smoke.py`` has no CPU fallback: on a CPU-only JAX it exits
    non-zero, says why, and prints no result line."""
    import os
    import subprocess
    import sys
    from pathlib import Path

    root = Path(__file__).resolve().parents[1]
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, str(root / "chip_smoke.py")],
                       env=env, capture_output=True, text=True, timeout=120)
    assert r.returncode != 0
    assert "no TPU" in r.stderr
    assert '"ok"' not in r.stdout


def test_host_mesh_spans_every_device():
    from repro.launch.mesh import host_mesh

    mesh = host_mesh()
    assert mesh.axis_names == ("data", "model")
    assert mesh.devices.size == len(jax.devices())
