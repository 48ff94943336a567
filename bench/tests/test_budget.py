"""The benchmark holds its own copies of params to the device memory
budget (``work.memory_budget``): the reference trains the learners of an
aggregation in groups where K learners' copies do not fit, and the
window moves finished campaigns' params to the host once they pass it.
Both agree with the unbudgeted paths; the faults and the control readings
still fail through them (``test_faults.py``).
"""

import math
import types

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import tiny
from bench import harness, kinds, models, reference, work
from bench.fleet import build_fleet


@pytest.fixture(scope="module")
def data():
    from repro.data.pipeline import Dataset

    cfg = tiny.config()
    xtr, ytr, xte, yte = models.load(cfg).data(cfg, 3)
    return cfg, Dataset(xtr, ytr), (xte, yte)


def _campaign(data, mix, **kw):
    cfg, train, ev = data
    traffic = tiny.traffic(mix)
    model = models.load(cfg)
    p0 = jax.tree_util.tree_map(np.asarray, model.init(cfg)(11))
    rows = kinds.load(traffic).schedule(build_fleet(cfg), traffic, 5, train.size)
    params, accs = reference.run_campaign(rows, p0, train.x, train.y, *ev, model=model,
                                          lr=cfg["train"]["lr"], gamma=1.0, **kw)
    return params, accs, p0, rows


def test_memory_budget_is_half_of_what_the_fullest_device_leaves(monkeypatch):
    """Half of the free bytes at the peak of the device with the highest
    peak; unbounded where the backend reports no memory (the CPU)."""

    class Device:
        def __init__(self, stats):
            self.stats = stats

        def memory_stats(self):
            return self.stats

    monkeypatch.setattr(jax, "devices", lambda: [
        Device({"bytes_limit": 16e9, "peak_bytes_in_use": 2e9, "bytes_in_use": 9e9}),
        Device({"bytes_limit": 15e9, "peak_bytes_in_use": 7e9, "bytes_in_use": 1e9})])
    assert work.memory_budget() == 4e9
    monkeypatch.setattr(jax, "devices", lambda: [Device(None)])
    assert work.memory_budget() == math.inf


@pytest.mark.parametrize("mix,copies,groups", [
    ("barrier", 0.5, [(), (), (), ()]),     # not one learner fits: one at a time
    ("barrier", 2, [(2,), (2,)]),
    ("barrier", 3, [(3,), ()]),             # the last group holds one learner
    ("fedasync", 0.5, [()]),
    ("fedasync", 2, [(1,)]),                # each arrival's one learner fits
])
def test_streamed_reference_equals_one_group(monkeypatch, data, mix, copies, groups):
    """With a budget of ``copies`` learners' three copies, the learners of
    each aggregation train in ``groups`` (their ``tau`` shapes), and the
    campaign ends where the unbounded one-group path does."""
    whole, whole_accs, p0, rows = _campaign(data, mix)
    budget = int(copies * 3 * work.tree_bytes(p0))
    monkeypatch.setattr(work, "memory_budget", lambda: budget)
    seen = []

    def spy(start, x, y, m, tau, lr, **kw):
        seen.append(tau.shape)
        return reference.train_learners(start, x, y, m, tau, lr, **kw)

    params, accs, _, _ = _campaign(data, mix, train_override=spy)
    assert seen == groups * len(rows)
    for a, b in zip(jax.tree_util.tree_leaves(params), jax.tree_util.tree_leaves(whole)):
        np.testing.assert_allclose(a, b, rtol=0, atol=1e-6)
    np.testing.assert_allclose(accs, whole_accs, rtol=0, atol=1e-6)


@pytest.mark.usefixtures("fresh_programs")
@pytest.mark.parametrize("mix", sorted(tiny.CELLS))
def test_zero_budget_run_reads_as_unbudgeted(monkeypatch, mix):
    """At a window budget of 0 the window copies each finished campaign's
    params to the host: the run is correct and reads the same check
    numbers. With the reference's budget at 0 too, it trains one learner
    at a time and the numbers agree to rounding."""
    base = tiny.run_tiny(mix)
    window_work = types.SimpleNamespace(**vars(work))
    window_work.memory_budget = lambda: 0
    monkeypatch.setattr(harness, "work", window_work)
    forced = tiny.run_tiny(mix)
    assert forced["correct"], forced["checks"]
    assert forced["checks"] == base["checks"]

    monkeypatch.setattr(work, "memory_budget", lambda: 0)
    both = tiny.run_tiny(mix)
    assert both["correct"], both["checks"]
    for name, shown in base["checks"].items():
        assert both["checks"][name]["value"] == pytest.approx(shown["value"], abs=1e-6)


@pytest.mark.usefixtures("fresh_programs")
def test_window_past_the_budget_holds_one_finished_campaign(monkeypatch):
    """While the finished campaigns' params fit they stay on the device;
    from the campaign that passes the budget on, each lands on the host as
    the next one runs, so at most the newest is left on the device."""
    p0 = models.load(tiny.config()).init(tiny.config())(0)
    monkeypatch.setattr(work, "memory_budget", lambda: 2.5 * work.tree_bytes(p0))
    real, seen = harness._offload, []

    def spy(outs, moving):
        moving = real(outs, moving)
        on_device = [i for i, o in enumerate(outs)
                     if isinstance(jax.tree_util.tree_leaves(o.params)[0], jax.Array)]
        seen.append((len(outs), on_device))
        return moving

    monkeypatch.setattr(harness, "_offload", spy)
    res = tiny.run_tiny("barrier", seconds=1.0)
    assert res["correct"], res["checks"]
    assert len(seen) >= 2
    assert seen[0] == (3, [0, 1, 2])         # the third passes the budget
    for n, on_device in seen[1:]:
        assert on_device == [n - 1]


def _cond_family():
    """A linear softmax classifier whose ``loss`` takes the second half of
    the rows behind a ``lax.cond`` that skips it where its mask is all 0."""

    def logits(params, x):
        return jnp.matmul(x, params["w"], precision=jax.lax.Precision.HIGHEST)

    def nll(params, x, y, m):
        logp = jax.nn.log_softmax(logits(params, x))
        return (-jnp.take_along_axis(logp, y[:, None], axis=1)[:, 0] * m).sum()

    def loss(params, x, y, m):
        h = x.shape[0] // 2
        tail = jax.lax.cond(m[h:].sum() > 0,
                            lambda: nll(params, x[h:], y[h:], m[h:]),
                            lambda: jnp.zeros((), x.dtype))
        return (nll(params, x[:h], y[:h], m[:h]) + tail) / jnp.maximum(m.sum(), 1)

    def accuracy(params, x, y):
        return jnp.mean(jnp.argmax(logits(params, x), axis=-1) == y)

    return types.SimpleNamespace(loss=loss, accuracy=accuracy)


def test_group_of_one_keeps_the_familys_cond(monkeypatch):
    """A one-learner group is traced without ``vmap``: the cond over the
    all-zero padding block stays a ``cond`` (and is skipped), where the
    vmapped group turns it into a ``select_n`` that computes both sides."""
    rng = np.random.default_rng(0)
    x = rng.normal(size=(40, 12)).astype(np.float32)
    y = rng.integers(0, 3, size=40).astype(np.int32)
    rows = [{"learners": np.arange(2), "tau": np.array([3, 2]), "d": np.array([5, 9]),
             "idx": [np.arange(5), np.arange(5, 14)]}]
    p0 = {"w": rng.normal(size=(12, 3)).astype(np.float32)}
    fam = _cond_family()
    jaxprs = []

    def spy(start, x, y, m, tau, lr, **kw):
        jaxprs.append(str(jax.make_jaxpr(
            lambda *a: reference.train_learners(*a, **kw))(start, x, y, m, tau, lr)))
        return reference.train_learners(start, x, y, m, tau, lr, **kw)

    def campaign():
        return reference.run_campaign(rows, p0, x, y, x, y, model=fam, lr=0.1,
                                      gamma=1.0, train_override=spy)

    paired, paired_acc = campaign()
    monkeypatch.setattr(work, "memory_budget", lambda: 0)
    single, single_acc = campaign()
    vmapped, alone = jaxprs[0], jaxprs[1]
    assert len(jaxprs) == 3
    assert "cond[" in alone and "cond[" not in vmapped
    assert alone.count("select_n") < vmapped.count("select_n")
    np.testing.assert_allclose(single["w"], paired["w"], rtol=0, atol=1e-6)
    assert single_acc == paired_acc
