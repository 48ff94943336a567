"""A run with the timed path broken underneath comes out not correct.

Each test drives ``harness.run`` (the whole run after the look for a
chip) on a tiny cell on the CPU, with one fault planted in the program,
and holds it to the cell's committed limits:

  unchanged   the train+aggregate step returns its state unchanged
  half_batch  half of each learner's samples left out, the mean taken
              over the rest
  altered     an answer altered where it is produced: the aggregated
              server params (1% off), and for the barrier also the
              allocation (one sample moved between two learners)

A run also comes out not correct where the window breaks one of its
own invariants: a campaign that raises, a campaign that replays a staged
schedule (a staging-cache hit), or a program lowered inside the window.

The cells run on one chip, so no exchange between chips can be left out.
The control (the reference in bfloat16 in the program's place) must fail
the same limits, and so must ``bench/control.py``'s ``half_batch``.

Each fault and reading fails also at a memory budget of 0, where the
reference trains one learner at a time and the window moves each
finished campaign's params to the host.
"""

import pytest

import jax
import jax.numpy as jnp

import tiny
from bench import campaign, check, control, harness, reference, work
from tiny import CELLS, limits, run_tiny

pytestmark = pytest.mark.usefixtures("fresh_programs")


def _halve(m):
    keep = jnp.cumsum(m, axis=-1) <= jnp.ceil(m.sum(axis=-1, keepdims=True) / 2)
    return m * keep


def plant(monkeypatch, mix, fault):
    from repro.fed import orchestrator
    from repro.kernels import ops

    off = lambda t: jax.tree_util.tree_map(lambda a: a * 1.01, t)
    if mix == "realloc_drift":       # its scan trains with _local_train_dynamic
        train = orchestrator._local_train_dynamic

        def broken(params, x, y, mask, tau, lr, *, loss_fn):
            if fault == "unchanged":
                k = x.shape[0]
                return jax.tree_util.tree_map(
                    lambda p: jnp.broadcast_to(p, (k,) + p.shape), params)
            if fault == "half_batch":
                mask = _halve(mask)
            out = train(params, x, y, mask, tau, lr, loss_fn=loss_fn)
            return off(out) if fault == "altered" else out

        monkeypatch.setattr(orchestrator, "_local_train_dynamic", broken)
        return
    step = ops.train_agg_step

    def broken(disp, x, y, m, tau, w, lr, **kw):
        if fault == "unchanged":
            if kw.get("acc") is None:
                return jax.tree_util.tree_map(lambda l: l[0], disp), None
            return kw["server"], kw["acc"]
        if fault == "half_batch":
            m = _halve(m)
        new, acc = step(disp, x, y, m, tau, w, lr, **kw)
        return (off(new) if fault == "altered" else new), acc

    monkeypatch.setattr(ops, "train_agg_step", broken)


@pytest.mark.parametrize("mix", sorted(CELLS))
def test_sound_run_is_correct(mix):
    res = run_tiny(mix)
    assert res["correct"], res["checks"]
    assert list(res)[-1] == "checks"


@pytest.mark.parametrize("fault", ["unchanged", "half_batch", "altered"])
@pytest.mark.parametrize("mix", sorted(CELLS))
def test_fault_is_not_correct(monkeypatch, mix, fault):
    plant(monkeypatch, mix, fault)
    res = run_tiny(mix)
    assert not res["correct"], res["checks"]


@pytest.mark.parametrize("fault", ["unchanged", "half_batch", "altered"])
@pytest.mark.parametrize("mix", sorted(CELLS))
def test_fault_is_not_correct_past_the_budget(monkeypatch, mix, fault):
    monkeypatch.setattr(work, "memory_budget", lambda: 0)
    plant(monkeypatch, mix, fault)
    res = run_tiny(mix)
    assert not res["correct"], res["checks"]


def test_altered_allocation_is_not_correct(monkeypatch):
    from repro.core import Allocation
    from repro.fed import orchestrator

    solve = orchestrator.SCHEMES["kkt_sai"]

    def moved(prob):
        a = solve(prob)
        d = a.d.copy()
        d[0], d[1] = d[0] + 1, d[1] - 1
        return Allocation(tau=a.tau, d=d, method="altered")

    monkeypatch.setitem(orchestrator.SCHEMES, "kkt_sai", moved)
    res = run_tiny("barrier")
    assert res["checks"]["sched_mismatch"]["value"] > 0
    assert not res["correct"]


def _reading_is_not_correct(mix, **kw):
    """The reference run with ``kw``, put in the program's place."""
    cfg, traffic = tiny.config(), tiny.traffic(mix)
    cell_in = harness.inputs(cfg, traffic, 5)
    o = cell_in.runner.run(5, 0)
    rows, p0, rp, racc = harness.reference_of(o, cell_in, cfg, traffic)
    _, _, cp, cacc = harness.reference_of(o, cell_in, cfg, traffic, **kw)
    o.params, o.accs = cp, cacc
    ok, shown = check.verdict(check.numbers(o, rows, rp, racc, p0), limits(mix))
    assert not ok, shown


@pytest.mark.parametrize("mix", sorted(CELLS))
def test_control_is_not_correct(mix):
    """The reference computed in bfloat16, put in the program's place."""
    _reading_is_not_correct(mix, dtype=jnp.bfloat16)


@pytest.mark.parametrize("reading", ["control", "half_batch"])
@pytest.mark.parametrize("mix", sorted(CELLS))
def test_control_reading_is_not_correct_past_the_budget(monkeypatch, mix, reading):
    monkeypatch.setattr(work, "memory_budget", lambda: 0)
    _reading_is_not_correct(mix, **(
        {"dtype": jnp.bfloat16} if reading == "control"
        else {"train_override": control.half_batch(reference.train_learners)}))


def test_raising_campaign_is_not_correct(monkeypatch):
    real = campaign.Runner.run

    def first_raises(self, seed, index):
        if index == 0:
            raise ValueError("planted: infeasible campaign")
        return real(self, seed, index)

    monkeypatch.setattr(campaign.Runner, "run", first_raises)
    res = run_tiny("barrier", seconds=0.05)
    assert res["attempted"] >= 2 and res["failed"] == 1
    assert res["checks"]["failed_campaigns"] == {"value": 1, "limit": 0}
    assert res["checks"]["param_dev"]["value"] is not None
    assert not res["correct"]


def test_staging_cache_hit_in_window_is_not_correct(monkeypatch):
    """Every campaign given the warm-up's seeds: the window's campaign
    replays the staged schedule of the warm-up."""
    fixed = campaign.campaign_seeds(5, -1)
    monkeypatch.setattr(campaign, "campaign_seeds", lambda seed, index: fixed)
    res = run_tiny("fedasync")
    assert res["checks"]["staging_hits"]["value"] >= 1
    assert res["checks"]["sched_mismatch"]["value"] == 0
    assert not res["correct"]


def test_compile_in_window_is_not_correct(monkeypatch):
    real = campaign.Runner.run

    def compiles(self, seed, index):
        out = real(self, seed, index)
        if index >= 0:
            jax.jit(lambda a: a + index)(jnp.ones(3)).block_until_ready()
        return out

    monkeypatch.setattr(campaign.Runner, "run", compiles)
    res = run_tiny("barrier")
    assert res["checks"]["window_compiles"]["value"] >= 1
    assert not res["correct"]
