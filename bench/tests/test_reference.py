"""The plain reference agrees with the program's eager paths at a tiny
size on the CPU: the barrier ``Orchestrator.run`` (with and without a
capacity drift) and the FedAsync ``AsyncFedEngine.run``."""

import numpy as np
import pytest

import jax

import tiny
from bench import check, kinds, models, reference
from bench.fleet import build_fleet

SEED = 2**31 + 7


@pytest.fixture(scope="module")
def data():
    from repro.data.pipeline import Dataset

    cfg = tiny.config()
    xtr, ytr, xte, yte = models.load(cfg).data(cfg, 3)
    return cfg, Dataset(xtr, ytr), (xte, yte)


def _eager(cfg, traffic, train, ev, engine_seed, p0):
    """The program's eager (host-loop) twin of the cell's timed path."""
    from repro.core import AllocationProblem, CapacityDrift, TimeModel

    loss, accuracy = models.load(cfg).program(cfg)
    f = build_fleet(cfg)
    prob = AllocationProblem(TimeModel(f.c2, f.c1, f.c0), f.T, f.total, f.d_lo, f.d_hi)
    tr = cfg["train"]
    if traffic["kind"] == "barrier":
        from repro.fed.orchestrator import MELConfig, Orchestrator

        d = traffic["drift"]
        drift = None if d is None else CapacityDrift(
            clock_jitter=d["clock_jitter"], fading_sigma_db=d["fading_sigma_db"],
            fading_clip_db=d["fading_clip_db"], seed=d["seed"])
        eng = Orchestrator(MELConfig(T=f.T, total_samples=f.total, lr=tr["lr"]),
                           prob, loss, p0, seed=engine_seed, drift=drift)
        acc = jax.jit(accuracy)
        hist = eng.run(train, traffic["cycles"], reallocate=traffic["reallocate"],
                       eval_fn=lambda p: acc(p, *ev))
    else:
        from repro.fed.async_engine import AsyncConfig, AsyncFedEngine

        eng = AsyncFedEngine(AsyncConfig(mode="fedasync", alpha=traffic["alpha"],
                                         staleness_a=traffic["staleness_a"],
                                         lr=tr["lr"]), prob, loss, p0,
                             seed=engine_seed)
        hist = eng.run(train, traffic["horizon_cycles"] * f.T,
                       eval_fn=accuracy, eval_batch=ev)
    return eng.params, hist


@pytest.mark.parametrize("mix", ["barrier", "realloc_drift", "fedasync"])
def test_reference_matches_eager_engine(data, mix):
    from bench.campaign import _rows

    cfg, train, ev = data
    traffic = tiny.traffic(mix)
    model = models.load(cfg)
    p0 = jax.tree_util.tree_map(np.asarray, model.init(cfg)(11))
    params, hist = _eager(cfg, traffic, train, ev, 5, p0)
    rows = kinds.load(traffic).schedule(build_fleet(cfg), traffic, 5, train.size)
    assert check.sched_mismatch(_rows(hist), rows) == 0
    rp, racc = reference.run_campaign(rows, p0, train.x, train.y, *ev, model=model,
                                      lr=cfg["train"]["lr"], gamma=1.0)
    gap, dev = check.param_numbers(p0, params, rp)
    assert dev < 1e-5 and gap < 1e-5
    assert check.acc_gap([r["accuracy"] for r in hist], racc) == 0.0
    assert racc[-1] > 0.2          # it learns: 10 classes, chance is 0.1


def test_reference_allocation_is_the_papers(data):
    """On the real fleets the reference's KKT/SAI is the program's."""
    import json

    from repro.core import AllocationProblem, TimeModel, solve_kkt_sai

    for name in ("mel_mnist_k20", "fedavg_2nn_k100"):
        cfg = json.loads((tiny.BENCH / "configs" / f"{name}.json").read_text())
        f = build_fleet(cfg)
        tau, d = reference.allocate(f.c2, f.c1, f.c0, f.T, f.total, f.d_lo, f.d_hi)
        a = solve_kkt_sai(AllocationProblem(TimeModel(f.c2, f.c1, f.c0), f.T,
                                            f.total, f.d_lo, f.d_hi))
        np.testing.assert_array_equal(tau, a.tau)
        np.testing.assert_array_equal(d, a.d)
        assert d.sum() == f.total and (tau * d).sum() > 0


@pytest.mark.parametrize("mix,key,value", [
    ("fedasync", "mode", "buffered"),     # a key the kind does not read
    ("fedasync", "drift", None),          # the async kind takes no drift
    ("barrier", "cycles", "delete"),      # a key the kind needs
])
def test_traffic_keys_a_kind_does_not_read_are_refused(mix, key, value):
    """A mix that sets a key its kind does not read, or leaves one out,
    is refused before any run, so no parameter is silently ignored."""
    traffic = tiny.traffic(mix)
    if value == "delete":
        del traffic[key]
    else:
        traffic[key] = value
    with pytest.raises(ValueError, match="keys"):
        kinds.load(traffic)


def test_drift_without_reallocation_is_refused():
    traffic = tiny.traffic("realloc_drift")
    traffic["reallocate"] = False
    with pytest.raises(ValueError, match="reallocate"):
        kinds.load(traffic).schedule(build_fleet(tiny.config()), traffic, 5, 400)


@pytest.mark.parametrize("name", ["mel_mnist_k20", "fedavg_2nn_k100"])
def test_fleet_copy_is_the_programs_builder(name):
    """``bench/fleet.py`` and ``bench/models/mlp.py`` copy the program's
    fleet builders so that the yardstick does not move with the program;
    today the copy and the originals (``indoor_80211_profile``,
    ``mlp_cost``, ``TimeModel.build``) give the same Eq. 5 coefficients."""
    import json

    from repro.core import TimeModel, indoor_80211_profile, mlp_cost

    cfg = json.loads((tiny.BENCH / "configs" / f"{name}.json").read_text())
    f = build_fleet(cfg)
    cost = mlp_cost(tiny.WIDTHS[name])
    tm = TimeModel.build(indoor_80211_profile(cfg["fleet"]["learners"],
                                              seed=cfg["fleet"]["seed"]),
                         model_complexity_flops=cost.flops_per_sample,
                         model_size_bits=cost.model_bits,
                         features_per_sample=tiny.WIDTHS[name][0])
    for ours, theirs in ((f.c2, tm.c2), (f.c1, tm.c1), (f.c0, tm.c0)):
        np.testing.assert_allclose(ours, theirs, rtol=1e-12, atol=0)
