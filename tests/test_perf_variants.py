"""The §Perf optimization variants must preserve model semantics."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_reduced
from repro.kernels.ref import flash_attention_ref
from repro.models.layers import flash_attention
from repro.models.model import Model
from repro.models.rwkv6 import wkv_chunked, wkv_scan

KEY = jax.random.key(0)


@pytest.mark.parametrize("chunk", [8, 16, 64])
def test_wkv_chunked_matches_scan(chunk):
    b, s, h, hd = 2, 128, 3, 32
    mk = lambda i, sc=0.5: jax.random.normal(jax.random.key(i), (b, s, h, hd)) * sc
    r, k, v = mk(0), mk(1), mk(2)
    # adversarially strong decays: exponent safety is the point
    w = jnp.exp(-jnp.exp(jax.random.normal(jax.random.key(3), (b, s, h, hd)) * 2.5))
    u = jax.random.normal(jax.random.key(4), (h, hd)) * 0.1
    s0 = jax.random.normal(jax.random.key(5), (b, h, hd, hd)) * 0.1
    y1, st1 = wkv_scan(r, k, v, w, u, s0=s0)
    y2, st2 = wkv_chunked(r, k, v, w, u, s0=s0, chunk=chunk)
    np.testing.assert_allclose(y1, y2, rtol=2e-3, atol=2e-3)
    np.testing.assert_allclose(st1, st2, rtol=2e-3, atol=2e-3)


def test_wkv_chunked_no_overflow_extreme_decay():
    b, s, h, hd = 1, 96, 1, 16
    r = jnp.ones((b, s, h, hd)) * 0.3
    k = jnp.ones((b, s, h, hd)) * 0.3
    v = jnp.ones((b, s, h, hd))
    w = jnp.full((b, s, h, hd), 1e-9)      # near-total forgetting each step
    u = jnp.zeros((h, hd))
    y, st = wkv_chunked(r, k, v, w, u, chunk=32)
    assert np.all(np.isfinite(np.asarray(y)))
    assert np.all(np.isfinite(np.asarray(st)))
    y2, st2 = wkv_scan(r, k, v, w, u)
    np.testing.assert_allclose(y, y2, rtol=1e-4, atol=1e-4)


def test_attn_q_block_exact():
    q = jax.random.normal(KEY, (2, 128, 4, 32))
    k = jax.random.normal(jax.random.key(1), (2, 128, 2, 32))
    v = jax.random.normal(jax.random.key(2), (2, 128, 2, 32))
    want = flash_attention_ref(q, k, v, causal=True)
    got = flash_attention(q, k, v, causal=True, chunk=32, q_block=32)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)


def test_attn_p_bf16_close():
    q = jax.random.normal(KEY, (1, 128, 2, 32))
    k = jax.random.normal(jax.random.key(1), (1, 128, 2, 32))
    v = jax.random.normal(jax.random.key(2), (1, 128, 2, 32))
    want = flash_attention_ref(q, k, v, causal=True)
    got = flash_attention(q, k, v, causal=True, chunk=32, p_bf16=True)
    np.testing.assert_allclose(got, want, rtol=3e-2, atol=3e-2)


def test_rwkv_model_chunked_backend_end_to_end():
    cfg = dataclasses.replace(get_reduced("rwkv6-7b"), wkv_backend="chunked", wkv_chunk=8)
    base = get_reduced("rwkv6-7b")
    m1, m2 = Model(base), Model(cfg)
    params = m1.init(KEY)
    batch = {
        "tokens": jax.random.randint(KEY, (2, 32), 0, base.vocab_size),
        "labels": jax.random.randint(jax.random.key(1), (2, 32), 0, base.vocab_size),
    }
    l1 = float(m1.loss(params, batch))
    l2 = float(m2.loss(params, batch))
    assert abs(l1 - l2) < 1e-3


def test_moe_shard_map_matches_plain_vmap():
    cfg = get_reduced("deepseek-moe-16b")
    m = Model(cfg)
    params = m.init(KEY)
    batch = {
        "tokens": jax.random.randint(KEY, (2, 16), 0, cfg.vocab_size),
        "labels": jax.random.randint(jax.random.key(1), (2, 16), 0, cfg.vocab_size),
    }
    mesh = jax.make_mesh((1, 1), ("data", "model"))
    plain = float(m.loss(params, batch))  # no mesh context -> vmap path
    with jax.set_mesh(mesh):
        sharded = float(jax.jit(m.loss)(params, batch))  # shard_map path
    assert abs(plain - sharded) < 1e-4
