"""The engines' host spans and device scopes (``repro.fed.tracing``).

Tiny ``run_fused`` (static, and ``reallocate=True`` under a
``CapacityDrift``) and ``run_events`` campaigns run under
``jax.profiler.trace``: each ``fed.*`` span appears under its expected
parent as often as expected, and the ``bytes`` stats of ``fed.stage``
equal the staged arrays' sizes: the sample-index blocks of the fused
paths, which gather their shards on the device, and the group blocks of
the event path. The campaign program each one ran,
lowered again, carries the device scopes in its op metadata.
"""

from __future__ import annotations

import collections
import glob
import re
import warnings

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.profiler import ProfileData, TraceAnnotation

from repro.core import CapacityDrift
from repro.data.pipeline import synthetic_mnist
from repro.fed import async_engine, orchestrator
from repro.fed.async_engine import AsyncConfig, AsyncFedEngine
from repro.fed.orchestrator import MELConfig, Orchestrator
from repro.fed.simulation import build_problem
from repro.models import mlp

LAYERS = [784, 16, 10]
K, CYCLES, TOTAL, T = 4, 2, 400, 15.0
ROOT = "campaign"


@pytest.fixture(scope="module")
def data():
    train, test = synthetic_mnist(1200, n_test=100, seed=0)
    return train, (jnp.asarray(test.x), jnp.asarray(test.y))


def _engine(kind: str):
    prob = build_problem(K, T, total_samples=TOTAL, seed=3)
    params = mlp.init(jax.random.key(1), LAYERS)
    if kind == "events":
        return AsyncFedEngine(AsyncConfig(mode="fedasync"), prob, mlp.loss,
                              params, seed=2)
    drift = (CapacityDrift(clock_jitter=0.1, fading_sigma_db=2.0, seed=7)
             if kind == "realloc" else None)
    return Orchestrator(MELConfig(T=T, total_samples=TOTAL), prob, mlp.loss,
                        params, seed=2, drift=drift)


PROGRAMS = {"static": (orchestrator, "_fused_cycles"),
            "realloc": (orchestrator, "_fused_realloc_cycles"),
            "events": (async_engine, "_bucketed_events")}


@pytest.fixture(scope="module", params=sorted(PROGRAMS))
def campaign(request, data, tmp_path_factory):
    """One traced campaign of the engine path ``request.param``: the host
    spans as ``(name, parent, stats)``, the engine, its history, and the
    abstract arguments its campaign program was called with."""
    kind = request.param
    train, ev = data
    module, name = PROGRAMS[kind]
    program, calls = getattr(module, name), []

    def spy(*args, **kw):
        calls.append(jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype)
            if isinstance(a, jax.Array) else a, (args, kw)))
        return program(*args, **kw)

    log_dir = tmp_path_factory.mktemp(f"trace_{kind}")
    async_engine.clear_staging_cache()
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(module, name, spy)
        jax.profiler.start_trace(str(log_dir))
        try:
            with TraceAnnotation(ROOT):
                eng = _engine(kind)
                if kind == "events":
                    hist = eng.run_events(train, CYCLES * T,
                                          eval_fn=mlp.accuracy, eval_batch=ev)
                else:
                    hist = eng.run_fused(train, CYCLES, eval_fn=mlp.accuracy,
                                         eval_batch=ev,
                                         reallocate=kind == "realloc")
        finally:
            jax.profiler.stop_trace()
    (path,) = glob.glob(str(log_dir / "**" / "*.xplane.pb"), recursive=True)
    with warnings.catch_warnings():
        # the first read of an event's stats builds a nanobind type that
        # warns (no __module__); under warnings-as-errors that aborts
        warnings.simplefilter("ignore", DeprecationWarning)
        events = sorted(
            ((e.start_ns, e.start_ns + e.duration_ns, e.name, dict(e.stats))
             for plane in ProfileData.from_file(path).planes
             if plane.name.startswith("/host")
             for line in plane.lines for e in line.events
             if e.name == ROOT or e.name.startswith("fed.")),
            key=lambda ev: (ev[0], -ev[1]))
    spans, stack = [], []
    for s, e, n, st in events:
        while stack and stack[-1][0] <= s:
            stack.pop()
        spans.append((n, stack[-1][1] if stack else None, st))
        stack.append((e, n))
    return kind, spans, eng, hist, calls


def _staged_nbytes(kind, eng) -> int:
    """The host bytes each engine path stages for the campaign program:
    the group blocks of the event path; the int32 sample indices of the
    fused paths, (C, total) flat or (C, K, d_max) per learner."""
    if kind == "events":
        ((_, staged),) = async_engine._STAGING_CACHE.values()
        return sum(a.nbytes for a in staged)
    if kind == "realloc":
        return CYCLES * TOTAL * 4
    d_max = int(np.max(eng.allocation.d))
    return CYCLES * K * d_max * 4


def test_spans_nest_and_count(campaign):
    kind, spans, _, _, _ = campaign
    tally = collections.Counter((n, parent) for n, parent, _ in spans)
    expected = {(ROOT, None): 1, ("fed.allocate", ROOT): 1,
                ("fed.h2d", ROOT): 1, ("fed.dispatch", ROOT): 1,
                ("fed.wait", ROOT): 1, ("fed.history", ROOT): 1}
    if kind == "events":
        expected.update({("fed.schedule", ROOT): 1,
                         ("fed.allocate", "fed.schedule"): 1,
                         ("fed.stage", ROOT): 1})
    else:
        # the zeroed blocks, then a stage per cycle with its draw inside;
        # the realloc path moves its eval batch apart, outside x64
        expected.update({("fed.stage", ROOT): 1 + CYCLES,
                         ("fed.draw", "fed.stage"): CYCLES})
        if kind == "realloc":
            expected[("fed.h2d", ROOT)] = 2
    assert dict(tally) == expected


def test_span_stats_count_the_staged_bytes(campaign, data):
    kind, spans, eng, hist, _ = campaign
    stats = collections.defaultdict(list)
    for n, _, st in spans:
        stats[n].append(st)
    staged = _staged_nbytes(kind, eng)
    assert sum(st.get("bytes", 0) for st in stats["fed.stage"]) == staged
    # the fused paths also copy the training set they gather from
    train, _ = data
    table = 0 if kind == "events" else train.x.nbytes + train.y.nbytes
    moved = sum(st["bytes"] for st in stats["fed.h2d"])
    assert staged + table <= moved < staged + table + 1024   # + small args
    assert [st["k"] for st in stats["fed.allocate"]] == [K] * len(stats["fed.allocate"])
    if kind == "events":
        assert stats["fed.stage"][0]["hit"] == 0
        # every fedasync arrival in the horizon is one aggregation
        assert [st["arrivals"] for st in stats["fed.schedule"]] == [len(hist)]


@pytest.mark.parametrize("scope", ["local_train", "aggregate", "eval",
                                   "policy_solve"])
def test_program_carries_device_scopes(campaign, scope):
    kind, _, _, _, calls = campaign
    (args, kw), = calls
    module, name = PROGRAMS[kind]
    program = getattr(module, name)
    with jax.enable_x64(kind == "realloc"):
        text = program.lower(*args, **kw).as_text(debug_info=True)
    # an op's location names its scope path, relative to the function it
    # is lowered in (a nested jit's ops start at that jit)
    in_program = scope != "policy_solve" or kind == "realloc"
    assert bool(re.search(rf'loc\("(?:[^"]*/)?{scope}/', text)) == in_program
