"""A configuration names its learner model's family, and the harness takes
everything that depends on the model from ``bench/models/<family>.py``.

A family the harness has never seen runs through ``harness.run`` once it
is registered by name; a family that lacks a required name is refused
when it is loaded; and the MLP family gives, on both real
configurations, exactly the numbers of the formulas the harness used
before the family existed.
"""

import json
import sys
import types

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import tiny
from bench import models, work
from bench.fleet import build_fleet

TOY = "toy_lowrank"


def toy_family(loss_scale=1.0):
    """A low-rank tanh classifier over Gaussian blobs: params are a nested
    dict, not a list of ``{"w", "b"}`` dicts. ``loss_scale`` scales the
    program's loss (1.0 is sound)."""

    def dims(cfg):
        m = cfg["model"]
        return m["features"], m["rank"], m["classes"]

    def data(cfg, seed):
        f, _, c = dims(cfg)
        rng = np.random.default_rng(seed)
        means = rng.normal(size=(c, f)).astype(np.float32)

        def make(n):
            y = rng.integers(0, c, size=n).astype(np.int32)
            return (means[y] + rng.normal(size=(n, f)).astype(np.float32)), y

        return (*make(cfg["data"]["train"]), *make(cfg["data"]["eval"]))

    def init(cfg):
        f, r, c = dims(cfg)

        @jax.jit
        def draw(seed):
            ku, kv = jax.random.split(jax.random.key(seed))
            return {"proj": {"u": jax.random.normal(ku, (f, r)) / np.sqrt(f)},
                    "head": {"v": jax.random.normal(kv, (r, c)) / np.sqrt(r),
                             "c": jnp.zeros((c,))}}

        return lambda s: draw(np.uint32(s))

    def logits(params, x, dot):
        h = jnp.tanh(dot(x, params["proj"]["u"]))
        return dot(h, params["head"]["v"]) + params["head"]["c"]

    def program_loss(params, batch):
        logp = jax.nn.log_softmax(logits(params, batch["x"], jnp.matmul))
        nll = -jnp.take_along_axis(logp, batch["y"][:, None], axis=1)[:, 0]
        m = batch["mask"]
        return loss_scale * (nll * m).sum() / jnp.maximum(m.sum(), 1.0)

    def program_eval(params, x, y):
        return jnp.mean(jnp.argmax(logits(params, x, jnp.matmul), axis=-1) == y)

    def ref_dot(a, b):
        if a.dtype == jnp.float32:
            return jnp.matmul(a, b, precision=jax.lax.Precision.HIGHEST)
        return jnp.matmul(a, b)

    def loss(params, x, y, m):
        logp = jax.nn.log_softmax(logits(params, x, ref_dot))
        nll = -jnp.take_along_axis(logp, y[:, None], axis=1)[:, 0]
        return (nll * m).sum() / jnp.maximum(m.sum(), 1)

    def accuracy(params, x, y):
        return jnp.mean(jnp.argmax(logits(params, x, ref_dot), axis=-1) == y)

    def params(cfg):
        f, r, c = dims(cfg)
        return f * r + r * c + c

    mod = types.ModuleType(f"bench.models.{TOY}")
    mod.__dict__.update(
        data=data, init=init, loss=loss, accuracy=accuracy,
        program=lambda cfg: (program_loss, program_eval),
        time_terms=lambda cfg: (6.0 * params(cfg), 32.0 * params(cfg),
                                32 * dims(cfg)[0]),
        # the two matmuls, forward and backward; the bias and tanh are free
        flops_per_sample_step=lambda cfg: 6.0 * (params(cfg) - dims(cfg)[2]),
        least_bytes=lambda cfg, rows: float(sum(
            int(r["d"].sum()) * (dims(cfg)[0] + 1) * 4 + 8 * params(cfg) * len(r["d"])
            for r in rows)))
    return mod


def toy_config():
    cfg = tiny.config()
    cfg["model"] = {"family": TOY, "features": 64, "rank": 8, "classes": 4}
    cfg["data"].update(features=64, classes=4)
    return cfg


@pytest.mark.usefixtures("fresh_programs")
@pytest.mark.parametrize("mix", ["barrier", "fedasync"])
def test_unseen_family_runs_by_name(monkeypatch, mix):
    monkeypatch.setitem(sys.modules, f"bench.models.{TOY}", toy_family())
    cfg = toy_config()
    model = models.load(cfg)
    p0 = model.init(cfg)(3)
    assert isinstance(p0, dict) and set(p0) == {"proj", "head"}
    assert model.flops_per_sample_step(cfg) != 6.0 * sum(
        a.size for a in jax.tree_util.tree_leaves(p0))
    res = tiny.run_tiny(mix, cfg=cfg)
    assert res["correct"], res["checks"]
    assert res["checks"]["param_dev"]["value"] < 1e-3
    mfu = res["metrics"]["mfu"]["value"]
    rate = res["metrics"]["sample_steps_per_s"]["value"]
    assert mfu == pytest.approx(100 * rate * model.flops_per_sample_step(cfg) / 197e12)


@pytest.mark.usefixtures("fresh_programs")
@pytest.mark.parametrize("mix", ["barrier", "fedasync"])
def test_unseen_family_with_a_faulty_program_loss_is_not_correct(monkeypatch, mix):
    monkeypatch.setitem(sys.modules, f"bench.models.{TOY}", toy_family(loss_scale=0.5))
    res = tiny.run_tiny(mix, cfg=toy_config())
    assert not res["correct"], res["checks"]
    assert res["checks"]["param_gap"]["value"] > res["checks"]["param_gap"]["limit"]


@pytest.mark.parametrize("name", models.REQUIRED)
def test_family_missing_a_required_name_is_refused(monkeypatch, name):
    mod = toy_family()
    delattr(mod, name)
    monkeypatch.setitem(sys.modules, f"bench.models.{TOY}", mod)
    with pytest.raises(ValueError, match=name):
        models.load(toy_config())


def _pre_change_fleet(cfg, widths):
    """``build_fleet`` as the harness wrote it for the MLP alone: C_m = 4
    FLOPs per parameter per sample, S_m = the weights at 32 bits, and 32
    bits per feature of a sample."""
    fl = cfg["fleet"]
    k = int(fl["learners"])
    rng = np.random.default_rng(int(fl["seed"]))
    dist = rng.uniform(2.0, 50.0, size=k)
    pl_db = 40.0 + 10.0 * 3.0 * np.log10(dist) + rng.normal(0.0, 4.0, size=k)
    gain = 10.0 ** (-pl_db / 10.0)
    clock = np.empty(k)
    for i in range(k):
        base = 2.4e9 if i % 2 == 0 else 0.7e9
        clock[i] = base * rng.uniform(0.9, 1.1)
    snr = 0.1 * gain / (4e-21 * 5e6)
    rate = 5e6 * np.log2(1.0 + snr)
    c_m = 4.0 * _params(widths)
    s_m = float(sum(a * b for a, b in zip(widths[:-1], widths[1:]))) * 32
    return c_m / clock, widths[0] * 32 / rate, 2.0 * s_m / rate


def _params(widths):
    return sum(a * b + b for a, b in zip(widths[:-1], widths[1:]))


@pytest.mark.parametrize("name,params", [("mel_mnist_k20", 280934),
                                         ("fedavg_2nn_k100", 199210)])
def test_mlp_family_keeps_the_pre_change_formulas(name, params):
    cfg = json.loads((tiny.BENCH / "configs" / f"{name}.json").read_text())
    widths = tiny.WIDTHS[name]
    model = models.load(cfg)
    assert _params(widths) == params

    f = build_fleet(cfg)
    for ours, pinned in zip((f.c2, f.c1, f.c0), _pre_change_fleet(cfg, widths)):
        np.testing.assert_array_equal(ours, pinned)

    assert model.flops_per_sample_step(cfg) == 6.0 * params
    assert work.flops(model, cfg, 1000) == 6.0 * params * 1000

    rows = [{"tau": np.array([66, 65, 66]), "d": np.array([501, 125, 300])},
            {"tau": np.array([70]), "d": np.array([17])}]
    pinned = float(sum(int(r["d"].sum()) * (widths[0] + 1) * 4
                       + 2 * (params * 4) * len(r["d"]) for r in rows))
    assert model.least_bytes(cfg, rows) == pinned
