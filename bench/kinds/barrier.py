"""Barrier campaigns: ``Orchestrator.run_fused`` over ``cycles`` cycles.

With ``reallocate`` false the allocation is solved once on the host;
with ``reallocate`` true the program re-solves it in its scan every
cycle, under the capacity drift ``drift`` (or none). Every learner
trains tau_k full-batch steps on d_k fresh samples per cycle, and the
server takes the staleness-weighted average.
"""

from __future__ import annotations

import numpy as np

from bench.reference import Draws, allocate, drift_factors

KEYS = ("cycles", "reallocate", "drift")


def _drift(traffic: dict) -> dict | None:
    d = traffic["drift"]
    if d is not None and not traffic["reallocate"]:
        raise ValueError("a capacity drift needs reallocate: true")
    return d


def build(cell, params, engine_seed: int):
    from repro.core import CapacityDrift
    from repro.fed.orchestrator import MELConfig, Orchestrator

    d, train = _drift(cell.traffic), cell.cfg["train"]
    drift = None if d is None else CapacityDrift(
        clock_jitter=d["clock_jitter"], fading_sigma_db=d["fading_sigma_db"],
        fading_clip_db=d["fading_clip_db"], seed=int(d["seed"]))
    mel = MELConfig(T=cell.fleet.T, total_samples=cell.fleet.total,
                    lr=train["lr"], scheme=train["scheme"],
                    aggregation=train["aggregation"])
    return Orchestrator(mel, cell.problem, cell.model.loss, params,
                        seed=engine_seed, drift=drift)


def run(cell, engine):
    return engine.run_fused(cell.train, int(cell.traffic["cycles"]),
                            eval_fn=cell.model.eval, eval_batch=cell.eval,
                            reallocate=bool(cell.traffic["reallocate"]))


def schedule(fleet, traffic: dict, engine_seed: int, n_train: int) -> list:
    """Per cycle: tau, d and each learner's sample indices."""
    cycles, drift = int(traffic["cycles"]), _drift(traffic)
    c2, c1, c0 = fleet.c2, fleet.c1, fleet.c0
    if drift is not None:
        clock, rate = drift_factors(int(drift["seed"]), cycles, fleet.k,
                                    clock_jitter=drift["clock_jitter"],
                                    fading_sigma_db=drift["fading_sigma_db"],
                                    fading_clip_db=drift["fading_clip_db"])
    draw = Draws(engine_seed, n_train)
    static = allocate(c2, c1, c0, fleet.T, fleet.total, fleet.d_lo, fleet.d_hi)
    out = []
    for c in range(cycles):
        if drift is None:
            tau, d = static
        else:
            tau, d = allocate(c2 / clock[c], c1 / rate[c], c0 / rate[c],
                              fleet.T, fleet.total, fleet.d_lo, fleet.d_hi)
        idx = draw(fleet.total)
        out.append({"tau": tau, "d": d, "idx": np.split(idx, np.cumsum(d)[:-1]),
                    "learners": np.arange(fleet.k)})
    return out
