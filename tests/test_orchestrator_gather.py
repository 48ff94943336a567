"""``Orchestrator.run_fused`` gathers its shards on the device.

The fused programs take a ``SampleIndex`` (per-cycle sample indices plus
the training set) and gather each cycle's rows in their scan. Checked
here on a tiny fleet, static and ``reallocate=True`` under a
``CapacityDrift``, unfused and through the interpreted megakernel:

* the final params and accuracies are bitwise those of the same program
  fed the same seed's draws staged on the host (``_stage_shards`` of
  ``part.draw(d)``, or ``train.x[idx]``) and read back through identity
  indices, and the tau/d history is the eager ``run``'s;
* the host stages only the int32 index block (``fed.stage`` ``bytes``),
  whether the training set holds fewer or more rows than the padded
  shard block it replaces.
"""

from __future__ import annotations

import contextlib

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import CapacityDrift
from repro.data.pipeline import FederatedPartitioner, synthetic_mnist
from repro.fed import orchestrator
from repro.fed.orchestrator import MELConfig, Orchestrator, SampleIndex
from repro.fed.simulation import build_problem
from repro.models import mlp

LAYERS = [784, 16, 10]
K, CYCLES, TOTAL, T, SEED = 4, 2, 400, 15.0, 2
F = 784
#: no larger than every padded block: C·total rows (realloc), C·K·d_max
SMALL = CYCLES * TOTAL
#: larger than every padded block: d_max <= d_upper = 3·TOTAL/K
LARGE = CYCLES * K * 3 * TOTAL // K + 1


def _data(n):
    train, test = synthetic_mnist(n, n_test=100, seed=0)
    return train, (jnp.asarray(test.x), jnp.asarray(test.y))


@pytest.fixture(scope="module")
def datasets():
    return {"small": _data(SMALL), "large": _data(LARGE)}


def _engine(reallocate: bool) -> Orchestrator:
    prob = build_problem(K, T, total_samples=TOTAL, seed=3)
    drift = (CapacityDrift(clock_jitter=0.1, fading_sigma_db=2.0, seed=7)
             if reallocate else None)
    return Orchestrator(MELConfig(T=T, total_samples=TOTAL), prob, mlp.loss,
                        mlp.init(jax.random.key(1), LAYERS), seed=SEED,
                        drift=drift)


def _program(reallocate: bool):
    name = "_fused_realloc_cycles" if reallocate else "_fused_cycles"
    return name, getattr(orchestrator, name)


def _run_capturing(data, reallocate, **kw):
    """``run_fused`` on a fresh engine; returns the engine, its history and
    the arguments its campaign program was called with."""
    train, ev = data
    name, program = _program(reallocate)
    calls = []

    def spy(*args, **kwargs):
        calls.append((args, kwargs))
        return program(*args, **kwargs)

    eng = _engine(reallocate)
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(orchestrator, name, spy)
        hist = eng.run_fused(train, CYCLES, eval_fn=mlp.accuracy,
                             eval_batch=ev, reallocate=reallocate, **kw)
    (call,) = calls
    return eng, hist, call


def _shards_arg(reallocate, call):
    return call[0][2 if reallocate else 1]


def _host_staged(train, d, reallocate) -> SampleIndex:
    """The same seed's draws staged on the host, as blocks of shards, and
    handed to the program as a table read back through identity indices:
    the program then trains on exactly the host-staged operands."""
    part = FederatedPartitioner(
        train, seed=int(np.random.default_rng(SEED).integers(2**31)))
    if reallocate:
        idx = [part.draw_indices(TOTAL) for _ in range(CYCLES)]
        xs = np.stack([train.x[i] for i in idx])
        ys = np.stack([train.y[i] for i in idx])
        return SampleIndex(np.arange(xs.shape[0] * TOTAL, dtype=np.int32)
                           .reshape(CYCLES, TOTAL),
                           xs.reshape(-1, F), ys.reshape(-1))
    d_max = int(d.max())
    staged = [orchestrator._stage_shards(part.draw(d), d_max, F)
              for _ in range(CYCLES)]
    xs, ys, ms = (np.stack(b) for b in zip(*staged))
    # the mask the program derives from d is the host-staged one
    np.testing.assert_array_equal(
        ms, np.broadcast_to(np.arange(d_max) < d[:, None], ms.shape))
    return SampleIndex(np.arange(ys.size, dtype=np.int32).reshape(ys.shape),
                       xs.reshape(-1, F), ys.reshape(-1), d.astype(np.int32))


def _rerun(reallocate, call, shards):
    """The captured program call again, from fresh params, on ``shards``."""
    (params, *args), kw = call
    _, program = _program(reallocate)
    pos = 1 if reallocate else 0          # after the drift state
    args[pos] = jax.tree_util.tree_map(jnp.asarray, shards)
    with jax.enable_x64(reallocate):
        out = program(mlp.init(jax.random.key(1), LAYERS), *args, **kw)
    if reallocate:
        (p, _, _), (accs, *_) = out
    else:
        p, accs = out
    return p, np.asarray(accs)


def _assert_host_staged(eng, hist, call, train, reallocate):
    ref_params, ref_accs = _rerun(reallocate, call,
                                  _host_staged(train, hist[0]["d"], reallocate))
    for a, b in zip(jax.tree_util.tree_leaves(eng.params),
                    jax.tree_util.tree_leaves(ref_params), strict=True):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert [h["accuracy"] for h in hist] == [float(a) for a in ref_accs]


@pytest.mark.parametrize("use_pallas", [False, True],
                         ids=["unfused", "megakernel"])
@pytest.mark.parametrize("reallocate", [False, True],
                         ids=["static", "realloc"])
def test_gather_is_bitwise_the_host_staged_program(datasets, reallocate,
                                                   use_pallas):
    data = datasets["small"]
    train, _ = data
    eng, hist, call = _run_capturing(data, reallocate, use_pallas=use_pallas,
                                     interpret=use_pallas)
    assert isinstance(_shards_arg(reallocate, call), SampleIndex)
    _assert_host_staged(eng, hist, call, train, reallocate)

    eager = _engine(reallocate).run(train, CYCLES, reallocate=reallocate)
    for he, hf in zip(eager, hist, strict=True):
        np.testing.assert_array_equal(he["tau"], hf["tau"])
        np.testing.assert_array_equal(he["d"], hf["d"])


@contextlib.contextmanager
def _recorded_spans():
    """Replace the orchestrator's spans by a recorder of (phase, stats)."""
    spans = []

    def record(phase, **stats):
        spans.append((phase, stats))
        return contextlib.nullcontext()

    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(orchestrator, "span", record)
        yield spans


@pytest.mark.parametrize("size", ["small", "large"])
@pytest.mark.parametrize("reallocate", [False, True],
                         ids=["static", "realloc"])
def test_stages_the_index_block_at_any_dataset_size(datasets, reallocate,
                                                     size):
    data = datasets[size]
    train, _ = data
    with _recorded_spans() as spans:
        eng, hist, call = _run_capturing(data, reallocate)
    shards = _shards_arg(reallocate, call)
    assert isinstance(shards, SampleIndex)
    rows = CYCLES * (TOTAL if reallocate else K * int(hist[0]["d"].max()))
    assert (train.size <= rows) == (size == "small")
    assert shards.idx.dtype == jnp.int32 and shards.idx.size == rows
    assert shards.x.shape == (train.size, F)
    staged = sum(st.get("bytes", 0) for phase, st in spans if phase == "stage")
    assert staged == rows * 4 == shards.idx.nbytes
    _assert_host_staged(eng, hist, call, train, reallocate)
