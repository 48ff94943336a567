"""Consolidated Pallas-vs-ref parity over EVERY ``repro.kernels.ops`` entry
point, in one harness.

Three layers:

* a property sweep — hypothesis-drawn seeds/variants (``tests._prop``)
  mapped through deterministic builders, each op's ``use_pallas=True,
  interpret=True`` dispatch checked against its ``ref.py`` oracle to the
  shared dtype tolerance;
* one degenerate-case table (K=1, all-masked data, zero local steps,
  infinite energy budget) where the contracts tighten to bitwise, except
  the megakernel's, which stays at its f32 tolerance;
* the ``ops.train_agg_step`` megakernel contract: interpret-mode output
  matches the unfused ``local_train_stacked`` + accumulate + ``fed_agg``
  composition to the stated f32 tolerance (``F32_RTOL``/``F32_ATOL``)
  on f32 fixtures across seeds x (K, tau, mask), in
  both the cycle and the async (server/acc/keep/flush) forms — and the
  same equivalence threaded through the three scan bodies
  (``Orchestrator.run_fused``, ``AsyncFedEngine.run_events``,
  ``FleetEngine.run``).

Per-kernel block-size sweeps stay in ``tests/test_kernels.py``; this file
owns the cross-cutting dispatch contract.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref
from repro.kernels.train_step import F32_ATOL, F32_RTOL, RUN_ATOL
from repro.models import mlp

from tests._prop import given, settings, st  # hypothesis, or fixed-seed fallback

_DTYPES = [jnp.float32, jnp.bfloat16]
_LAYERS = [6, 5, 3]  # tiny MLP family the megakernel fixtures train


def _tol(dtype):
    return dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 else dict(rtol=2e-5, atol=2e-5)


def _allclose(got, want, dtype):
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32), **_tol(dtype)
    )


def _trees_close(a, b, atol=F32_ATOL):
    """The megakernel contract: f32 tolerance, not bitwise (its per-learner
    matmuls and grid-order accumulate round differently in the last bits
    from the batched reference). ``atol=RUN_ATOL`` after a whole run."""
    la = jax.tree_util.tree_leaves(a)
    lb = jax.tree_util.tree_leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        assert x.shape == y.shape and x.dtype == y.dtype
        np.testing.assert_allclose(np.asarray(x), np.asarray(y),
                                   rtol=F32_RTOL, atol=atol)


# ---------------------------------------------------------------------------
# deterministic per-op builders (rng carries all the entropy)
# ---------------------------------------------------------------------------

def _check_flash_attention(rng, variant):
    dtype = _DTYPES[variant % 2]
    b = int(rng.integers(1, 3))
    s = int(rng.choice([16, 32, 64]))
    h = int(rng.choice([2, 4]))
    kv = int(rng.choice([1, h]))
    d = int(rng.choice([8, 16]))
    causal = bool(rng.integers(2))
    window = None if rng.integers(2) else max(4, s // 4)
    q = jnp.asarray(rng.standard_normal((b, s, h, d)) * 0.5, dtype)
    k = jnp.asarray(rng.standard_normal((b, s, kv, d)) * 0.5, dtype)
    v = jnp.asarray(rng.standard_normal((b, s, kv, d)) * 0.5, dtype)
    got = ops.flash_attention(q, k, v, causal=causal, window=window,
                              use_pallas=True, interpret=True)
    want = ref.flash_attention_ref(q, k, v, causal=causal, window=window)
    _allclose(got, want, dtype)


def _check_wkv6(rng, variant):
    dtype = _DTYPES[variant % 2]
    b = int(rng.integers(1, 3))
    s = int(rng.choice([16, 32]))
    h = int(rng.choice([1, 2]))
    hd = int(rng.choice([8, 16]))
    mk = lambda scale: jnp.asarray(rng.standard_normal((b, s, h, hd)) * scale, dtype)
    r, k, v = mk(0.5), mk(0.5), mk(0.5)
    w = jnp.asarray(rng.uniform(0.5, 0.95, (b, s, h, hd)), dtype)
    u = jnp.asarray(rng.standard_normal((h, hd)) * 0.1, jnp.float32)
    s0 = (jnp.asarray(rng.standard_normal((b, h, hd, hd)) * 0.1, jnp.float32)
          if variant % 3 else None)
    y, s_last = ops.wkv6(r, k, v, w, u, s0=s0, use_pallas=True, interpret=True)
    yr, sr = ref.wkv6_ref(r, k, v, w, u, s0=s0)
    _allclose(y, yr, dtype)
    _allclose(s_last, sr, dtype)


def _check_fed_agg(rng, variant):
    dtype = _DTYPES[variant % 2]
    k = int(rng.integers(1, 7))
    shape = [(257,), (33, 7), (16, 3, 5)][variant % 3]
    x = jnp.asarray(rng.standard_normal((k, *shape)) * 2.0, dtype)
    w = jnp.asarray(rng.uniform(0.0, 1.0, (k,)), jnp.float32)
    w = w / w.sum()
    got = ops.fed_agg(x, w, use_pallas=True, interpret=True)
    want = ref.fed_agg_ref(x, w)
    _allclose(got, want, dtype)
    assert got.shape == shape and got.dtype == x.dtype


def _check_swiglu_fused(rng, variant):
    dtype = _DTYPES[variant % 2]
    m = int(rng.choice([16, 32]))
    d = int(rng.choice([8, 16]))
    f = int(rng.choice([32, 64]))
    x = jnp.asarray(rng.standard_normal((m, d)) * 0.5, dtype)
    wg = jnp.asarray(rng.standard_normal((d, f)) * 0.05, dtype)
    wu = jnp.asarray(rng.standard_normal((d, f)) * 0.05, dtype)
    wd = jnp.asarray(rng.standard_normal((f, d)) * 0.05, dtype)
    got = ops.swiglu_fused(x, wg, wu, wd, use_pallas=True, interpret=True)
    want = ref.swiglu_ref(x, wg, wu, wd)
    _allclose(got, want, dtype)


def _check_mamba_scan(rng, variant):
    dtype = _DTYPES[variant % 2]
    bsz = int(rng.integers(1, 3))
    s = int(rng.choice([16, 32]))
    d = int(rng.choice([8, 16]))
    n = int(rng.choice([4, 8]))
    sp = lambda z: np.log1p(np.exp(z))  # softplus, stays in numpy
    dt = jnp.asarray(sp(rng.standard_normal((bsz, s, d)) * 0.5), dtype)
    x = jnp.asarray(rng.standard_normal((bsz, s, d)) * 0.5, dtype)
    b = jnp.asarray(rng.standard_normal((bsz, s, n)) * 0.5, dtype)
    c = jnp.asarray(rng.standard_normal((bsz, s, n)) * 0.5, dtype)
    a = -jnp.exp(jnp.asarray(rng.standard_normal((d, n)) * 0.3, jnp.float32))
    h0 = (jnp.asarray(rng.standard_normal((bsz, d, n)) * 0.1, jnp.float32)
          if variant % 3 else None)
    yp, hp = ops.mamba_scan(dt, x, b, c, a, h0=h0, use_pallas=True, interpret=True)
    yr, hr = ref.mamba_scan_ref(dt, x, b, c, a, h0=h0)
    _allclose(yp, yr, dtype)
    _allclose(hp, hr, dtype)


def _time_rows(rng, b, k, variant):
    """Shared waterfill fixture: f32 time coefficients + a tau* that lands
    in the interior / lo-saturated / hi-slack regimes by variant."""
    c2 = jnp.asarray(rng.uniform(1e-4, 1e-2, (b, k)), jnp.float32)
    c1 = jnp.asarray(rng.uniform(1e-4, 1e-2, (b, k)), jnp.float32)
    c0 = jnp.asarray(rng.uniform(0.1, 2.0, (b, k)), jnp.float32)
    tau_v, scale_T = [(50.0, 1.0), (1e6, 1.0), (0.0, 1e4)][variant % 3]
    T = jnp.asarray(rng.uniform(5.0, 20.0, (b,)) * scale_T, jnp.float32)
    lo = jnp.full((b, k), 10.0, jnp.float32)
    hi = jnp.full((b, k), 900.0, jnp.float32)
    tot = jnp.asarray(rng.uniform(1e3, 5e3, (b,)), jnp.float32)
    return jnp.full((b,), tau_v, jnp.float32), c2, c1, c0, T, lo, hi, tot


def _check_waterfill_residual(rng, variant):
    b = int(rng.integers(1, 6))
    k = int(rng.integers(1, 14))
    args = _time_rows(rng, b, k, variant)
    got = ops.waterfill_residual(*args, use_pallas=True, interpret=True)
    want = ref.waterfill_residual_ref(*args)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-3)


def _check_waterfill_energy_residual(rng, variant):
    b = int(rng.integers(1, 6))
    k = int(rng.integers(1, 14))
    tau_v, c2, c1, c0, T, lo, hi, tot = _time_rows(rng, b, k, variant)
    e2 = jnp.asarray(rng.uniform(1e-4, 1e-2, (b, k)), jnp.float32)
    e1 = jnp.asarray(rng.uniform(1e-4, 1e-2, (b, k)), jnp.float32)
    e0 = jnp.asarray(rng.uniform(0.05, 1.0, (b, k)), jnp.float32)
    eb = jnp.asarray(
        np.full((b, k), np.inf) if variant % 4 == 0
        else rng.uniform(2.0, 12.0, (b, k)),
        jnp.float32,
    )
    args = (tau_v, c2, c1, c0, T, e2, e1, e0, eb, lo, hi, tot)
    got = ops.waterfill_energy_residual(*args, use_pallas=True, interpret=True)
    want = ref.waterfill_energy_residual_ref(*args)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-3)


def _train_fixture(rng, k, n, *, mask_kind):
    """f32 megakernel operands: per-learner start params, padded data with
    mask, per-learner tau/weights — the exact ``_bucketed_events`` shapes."""
    feat, classes = _LAYERS[0], _LAYERS[-1]
    stack = [mlp.init(jax.random.key(int(s)), _LAYERS)
             for s in rng.integers(2**31, size=k)]
    disp = jax.tree_util.tree_map(lambda *ls: jnp.stack(ls), *stack)
    x = jnp.asarray(rng.standard_normal((k, n, feat)), jnp.float32)
    y = jnp.asarray(rng.integers(0, classes, (k, n)), jnp.int32)
    if mask_kind == "random":
        m = jnp.asarray(rng.integers(0, 2, (k, n)), jnp.float32)
    elif mask_kind == "full":
        m = jnp.ones((k, n), jnp.float32)
    else:  # one learner fully masked out
        m = jnp.ones((k, n), jnp.float32)
        m = m.at[int(rng.integers(k))].set(0.0)
    tau = jnp.asarray(rng.integers(0, 4, (k,)), jnp.int32)
    w = jnp.asarray(rng.uniform(0.1, 1.0, (k,)), jnp.float32)
    return disp, x, y, m, tau, w


def _check_train_agg_step(rng, variant):
    k = int(rng.integers(1, 5))
    n = int(rng.integers(3, 9))
    mask_kind = ["random", "full", "one_out"][variant % 3]
    disp, x, y, m, tau, w = _train_fixture(rng, k, n, mask_kind=mask_kind)
    lr = jnp.float32(0.05)
    max_tau = max(1, int(tau.max()))

    # cycle form: f32 tolerance against the unfused composition
    want, _ = ops.train_agg_step(disp, x, y, m, tau, w, lr,
                                 loss_fn=mlp.loss, max_tau=max_tau)
    got, _ = ops.train_agg_step(disp, x, y, m, tau, w, lr, loss_fn=mlp.loss,
                                use_pallas=True, interpret=True)
    _trees_close(got, want)

    # async form: server/acc carry + keep/flush contraction, same tolerance
    server = mlp.init(jax.random.key(int(rng.integers(2**31))), _LAYERS)
    acc = jax.tree_util.tree_map(
        lambda l: jnp.asarray(rng.standard_normal(l.shape) * 0.1, jnp.float32),
        server)
    keep = jnp.float32(rng.uniform(0.0, 1.0))
    flush = jnp.float32(rng.uniform(0.0, 1.0))
    s_ref, a_ref = ops.train_agg_step(
        disp, x, y, m, tau, w, lr, loss_fn=mlp.loss, max_tau=max_tau,
        server=server, acc=acc, keep=keep, flush=flush)
    s_pal, a_pal = ops.train_agg_step(
        disp, x, y, m, tau, w, lr, loss_fn=mlp.loss,
        server=server, acc=acc, keep=keep, flush=flush,
        use_pallas=True, interpret=True)
    _trees_close(s_pal, s_ref)
    _trees_close(a_pal, a_ref)


CHECKS = {
    "flash_attention": _check_flash_attention,
    "wkv6": _check_wkv6,
    "fed_agg": _check_fed_agg,
    "swiglu_fused": _check_swiglu_fused,
    "mamba_scan": _check_mamba_scan,
    "waterfill_residual": _check_waterfill_residual,
    "waterfill_energy_residual": _check_waterfill_energy_residual,
    "train_agg_step": _check_train_agg_step,
}

assert sorted(CHECKS) == sorted(ops.__all__), "every ops entry point is covered"


@pytest.mark.parametrize("op", sorted(CHECKS))
def test_ops_parity_property(op):
    """Hypothesis-drawn shapes/dtypes/seeds: ops(use_pallas=True,
    interpret=True) vs the ref oracle, per-op tolerance (F32_RTOL/F32_ATOL
    for train_agg_step)."""
    check = CHECKS[op]

    @settings(max_examples=6, deadline=None)
    @given(seed=st.integers(0, 2**16 - 1), variant=st.integers(0, 23))
    def prop(seed, variant):
        check(np.random.default_rng(seed * 31 + 7), variant)

    prop()


# ---------------------------------------------------------------------------
# degenerate-case table: the contracts tighten to bitwise (the
# megakernel's rows keep its f32 tolerance)
# ---------------------------------------------------------------------------

def _degen_fed_agg_k1():
    """K=1 with unit weight is the identity, bit for bit."""
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((1, 129)), jnp.float32)
    w = jnp.ones((1,), jnp.float32)
    got = ops.fed_agg(x, w, use_pallas=True, interpret=True)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(x[0]))
    np.testing.assert_array_equal(np.asarray(ref.fed_agg_ref(x, w)),
                                  np.asarray(x[0]))


def _degen_train_k1():
    rng = np.random.default_rng(1)
    _check_train_agg_step(rng, variant=1)  # draws K from rng; force K=1 below
    rng = np.random.default_rng(2)
    disp, x, y, m, tau, w = _train_fixture(rng, 1, 5, mask_kind="full")
    got, _ = ops.train_agg_step(disp, x, y, m, tau, w, jnp.float32(0.05),
                                loss_fn=mlp.loss, use_pallas=True,
                                interpret=True)
    want, _ = ops.train_agg_step(disp, x, y, m, tau, w, jnp.float32(0.05),
                                 loss_fn=mlp.loss,
                                 max_tau=max(1, int(tau.max())))
    _trees_close(got, want)


def _degen_train_all_masked():
    """All-masked data: the loss contraction zeroes every gradient, so the
    fused step reduces to fed_agg over the UNTRAINED dispatch params."""
    rng = np.random.default_rng(3)
    disp, x, y, _, _, w = _train_fixture(rng, 3, 5, mask_kind="full")
    m = jnp.zeros_like(x[..., 0])
    tau = jnp.asarray([3, 1, 2], jnp.int32)
    got, _ = ops.train_agg_step(disp, x, y, m, tau, w, jnp.float32(0.05),
                                loss_fn=mlp.loss, use_pallas=True,
                                interpret=True)
    want = jax.tree_util.tree_map(lambda l: ref.fed_agg_ref(l, w), disp)
    _trees_close(got, want)


def _degen_train_zero_tau():
    """tau == 0 everywhere: no GD step runs; the kernel's traced
    ``max(tau)`` loop bound hits zero and the output is the plain
    aggregate of the start params."""
    rng = np.random.default_rng(4)
    disp, x, y, m, _, w = _train_fixture(rng, 3, 5, mask_kind="random")
    tau = jnp.zeros((3,), jnp.int32)
    got, _ = ops.train_agg_step(disp, x, y, m, tau, w, jnp.float32(0.05),
                                loss_fn=mlp.loss, use_pallas=True,
                                interpret=True)
    want = jax.tree_util.tree_map(lambda l: ref.fed_agg_ref(l, w), disp)
    _trees_close(got, want)


def _degen_energy_inf_budget():
    """eb = +inf rows reproduce the time-only residual bitwise on BOTH
    backends (the documented ops contract)."""
    rng = np.random.default_rng(5)
    tau_v, c2, c1, c0, T, lo, hi, tot = _time_rows(rng, 3, 7, 0)
    e2 = jnp.asarray(rng.uniform(1e-4, 1e-2, (3, 7)), jnp.float32)
    e1 = jnp.asarray(rng.uniform(1e-4, 1e-2, (3, 7)), jnp.float32)
    e0 = jnp.asarray(rng.uniform(0.05, 1.0, (3, 7)), jnp.float32)
    eb = jnp.full((3, 7), jnp.inf, jnp.float32)
    for backend in (dict(use_pallas=True, interpret=True), dict()):
        with_e = ops.waterfill_energy_residual(
            tau_v, c2, c1, c0, T, e2, e1, e0, eb, lo, hi, tot, **backend)
        time_only = ops.waterfill_residual(
            tau_v, c2, c1, c0, T, lo, hi, tot, **backend)
        np.testing.assert_array_equal(np.asarray(with_e),
                                      np.asarray(time_only))


DEGENERATE = {
    "fed_agg_k1": _degen_fed_agg_k1,
    "train_k1": _degen_train_k1,
    "train_all_masked": _degen_train_all_masked,
    "train_zero_tau": _degen_train_zero_tau,
    "energy_inf_budget": _degen_energy_inf_budget,
}


@pytest.mark.parametrize("case", sorted(DEGENERATE))
def test_degenerate_cases(case):
    DEGENERATE[case]()


# ---------------------------------------------------------------------------
# engine threading: the three scan bodies accept use_pallas and agree
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def small_data():
    from repro.data.pipeline import synthetic_mnist

    return synthetic_mnist(1500, n_test=300, seed=0)


@pytest.mark.parametrize("reallocate", [False, True])
def test_run_fused_pallas_matches_unfused(small_data, reallocate):
    """Orchestrator.run_fused: the megakernel cycle body matches the
    unfused scan body to f32 tolerance, with identical tau/d (fresh params per run — the fused cycles
    donate their carry)."""
    from repro.fed.orchestrator import MELConfig, Orchestrator
    from repro.fed.simulation import build_problem

    train, _ = small_data
    prob = build_problem(3, 10.0, total_samples=600, seed=3)

    runs = []
    for use_pallas in (False, True):
        orch = Orchestrator(MELConfig(T=10.0, total_samples=600), prob,
                            mlp.loss, mlp.init(jax.random.key(3)), seed=3)
        hist = orch.run(train, 3, fused=True, reallocate=reallocate,
                        use_pallas=use_pallas, interpret=use_pallas)
        runs.append((hist, orch.params))

    (h0, p0), (h1, p1) = runs
    assert len(h0) == len(h1) == 3
    for r0, r1 in zip(h0, h1):
        np.testing.assert_array_equal(r0["tau"], r1["tau"])
        np.testing.assert_array_equal(r0["d"], r1["d"])
    _trees_close(p0, p1, atol=RUN_ATOL)


def test_run_events_pallas_matches_unfused(small_data):
    """AsyncFedEngine.run_events: every jagged-segment scan step through
    the megakernel reproduces the unfused history exactly and its params
    to f32 tolerance."""
    from repro.fed.async_engine import AsyncConfig, AsyncFedEngine
    from repro.fed.simulation import build_problem

    train, _ = small_data
    prob = build_problem(4, 15.0, total_samples=1200, seed=3)

    runs = []
    for use_pallas in (False, True):
        eng = AsyncFedEngine(AsyncConfig(mode="fedasync"), prob, mlp.loss,
                             mlp.init(jax.random.key(2)), seed=2)
        hist = eng.run_events(train, 40.0, use_pallas=use_pallas,
                              interpret=use_pallas)
        runs.append((hist, eng.params))

    (h0, p0), (h1, p1) = runs
    assert len(h0) == len(h1) >= 3
    for r0, r1 in zip(h0, h1):
        assert r0["server_version"] == r1["server_version"]
        assert r0["staleness_list"] == r1["staleness_list"]
        np.testing.assert_array_equal(r0["weights"], r1["weights"])
    _trees_close(p0, p1, atol=RUN_ATOL)


def test_fleet_rounds_pallas_matches_unfused(small_data):
    """FleetEngine: the vmapped per-fleet round through the megakernel is
    equal to the unfused local_train + weighted-sum body to f32 tolerance,
    with identical tau/d."""
    from repro.fed.fleet import FleetConfig, FleetEngine, build_fleet_problems
    from repro.launch.mesh import make_mesh_by_name

    train, _ = small_data
    probs = build_fleet_problems(2, 3, T=2.0, total_samples=30, seed=2)

    runs = []
    for use_pallas in (False, True):
        eng = FleetEngine(FleetConfig(), probs, mlp.loss,
                          mlp.init(jax.random.key(3)), seed=3,
                          mesh=make_mesh_by_name("cpu"))
        hist = eng.run(train, 2, use_pallas=use_pallas, interpret=use_pallas)
        runs.append((hist, eng.global_params, eng.fleet_params))

    (h0, g0, f0), (h1, g1, f1) = runs
    for r0, r1 in zip(h0, h1):
        np.testing.assert_array_equal(r0["tau"], r1["tau"])
        np.testing.assert_array_equal(r0["d"], r1["d"])
    _trees_close(g0, g1, atol=RUN_ATOL)
    _trees_close(f0, f1, atol=RUN_ATOL)
