"""The general campaign runner: one traffic mix file, any configuration.

A traffic mix (``bench/traffic/<mix>.json``) names its kind and the
kind's parameters; the kind's module (``bench/kinds/<kind>.py``) builds
the program's engine for one campaign the way users do and runs it, and
this module reads what it returned. Three host spans, written with
``jax.profiler.TraceAnnotation``, bracket the calls into the program so
the trace can attribute the device's idle time:

  campaign.build  the engine (and its allocation solve) and fresh params
  campaign.run    the engine's campaign call (``Orchestrator.run_fused``,
                  ``AsyncFedEngine.run_events``, ...)
  campaign.read   waiting for the final params, reading the history
"""

from __future__ import annotations

import dataclasses
import types

import numpy as np

import jax

from bench import kinds, models, work
from bench.fleet import sub_seed


@dataclasses.dataclass(eq=False)
class Outcome:
    """What one campaign returned, in the program's own words."""

    index: int
    engine_seed: int
    init_seed: int
    params: object                  # final server params (device)
    schedule: list                  # one dict per aggregation (see ``_rows``)
    accs: list
    useful: int                     # sum of tau_k d_k over the aggregations
    seconds: float = 0.0


def campaign_seeds(seed: int, index: int) -> tuple[int, int]:
    """(engine seed, init seed) of campaign ``index`` of a run. The warm-up
    campaign has index -1."""
    return sub_seed(seed, index + 1, 0), sub_seed(seed, index + 1, 1)


class Runner:
    """Runs campaigns of one cell on the program under test."""

    def __init__(self, cfg: dict, traffic: dict, fleet, data, eval_dev, init):
        from repro.core import AllocationProblem, TimeModel

        self.kind = kinds.load(traffic)
        self.cfg, self.traffic, self.fleet = cfg, traffic, fleet
        self.train = data
        self.eval = eval_dev
        self.init = init
        loss, ev = models.load(cfg).program(cfg)
        self.model = types.SimpleNamespace(loss=loss, eval=ev)
        self.problem = AllocationProblem(
            time_model=TimeModel(c2=fleet.c2, c1=fleet.c1, c0=fleet.c0),
            T=fleet.T, total_samples=fleet.total, d_lower=fleet.d_lo,
            d_upper=fleet.d_hi)

    def run(self, seed: int, index: int) -> Outcome:
        from jax.profiler import TraceAnnotation

        engine_seed, init_seed = campaign_seeds(seed, index)
        with TraceAnnotation("campaign.build"):
            eng = self.kind.build(self, self.init(init_seed), engine_seed)
        with TraceAnnotation("campaign.run"):
            hist = self.kind.run(self, eng)
        with TraceAnnotation("campaign.read"):
            out = jax.block_until_ready(eng.params)
            rows = _rows(hist)
            accs = [float(r["accuracy"]) for r in hist]
        return Outcome(index, engine_seed, init_seed, out, rows, accs,
                       work.sample_steps(rows))


def _rows(hist) -> list:
    """The schedule fields of each history row that the reference
    recomputes: learners, tau, d, and for asynchronous rows the version,
    the staleness of each upload, the mixing weights and the keep."""
    rows = []
    for r in hist:
        row = {"tau": np.asarray(r["tau"], np.int64),
               "d": np.asarray(r["d"], np.int64)}
        if "server_version" in r and r.get("mode") != "cycle":
            row.update(learners=np.asarray(r["learners"], np.int64),
                       staleness=list(r["staleness_list"]),
                       version=int(r["server_version"]),
                       weights=np.asarray(r["weights"], np.float64),
                       keep=float(r["keep"]))
        rows.append(row)
    return rows

