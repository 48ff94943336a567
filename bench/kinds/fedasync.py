"""FedAsync campaigns: ``AsyncFedEngine.run_events`` in ``fedasync`` mode
to a horizon of ``horizon_cycles`` deadlines T, with no drift and no
faults. Every learner is dispatched at t=0 with the static allocation,
uploads after its Eq. 5 time, is mixed into the server at once with
weight alpha (1 + v)^-staleness_a for version staleness v, and is
redispatched with the new server.
"""

from __future__ import annotations

import heapq

import numpy as np

from bench.reference import Draws, allocate

KEYS = ("alpha", "staleness_a", "horizon_cycles")


def build(cell, params, engine_seed: int):
    from repro.fed.async_engine import AsyncConfig, AsyncFedEngine

    tr, train = cell.traffic, cell.cfg["train"]
    acfg = AsyncConfig(mode="fedasync", staleness_fn="poly", alpha=tr["alpha"],
                       staleness_a=tr["staleness_a"], lr=train["lr"],
                       scheme=train["scheme"], aggregation=train["aggregation"])
    return AsyncFedEngine(acfg, cell.problem, cell.model.loss, params,
                          seed=engine_seed)


def run(cell, engine):
    return engine.run_events(cell.train,
                             float(cell.traffic["horizon_cycles"]) * cell.fleet.T,
                             eval_fn=cell.model.eval, eval_batch=cell.eval)


def schedule(fleet, traffic: dict, engine_seed: int, n_train: int) -> list:
    """One entry per arrival up to the horizon."""
    alpha, a = float(traffic["alpha"]), float(traffic["staleness_a"])
    horizon = float(traffic["horizon_cycles"]) * fleet.T
    tau, d = allocate(fleet.c2, fleet.c1, fleet.c0, fleet.T, fleet.total,
                      fleet.d_lo, fleet.d_hi)
    draw = Draws(engine_seed, n_train)
    heap, seq, version = [], 0, 0

    def dispatch(k, t):
        nonlocal seq
        cost = float(fleet.c2[k] * tau[k] * d[k] + fleet.c1[k] * d[k] + fleet.c0[k])
        heapq.heappush(heap, (t + cost, seq, k, version, draw(d[k])))
        seq += 1

    for k in range(fleet.k):
        dispatch(k, 0.0)
    out = []
    while heap:
        t, _, k, v0, idx = heapq.heappop(heap)
        if t > horizon:
            break
        stale = version - v0
        w = alpha * (1.0 + stale) ** (-a)
        version += 1
        out.append({"learners": np.array([k]), "tau": tau[k:k + 1],
                    "d": d[k:k + 1], "idx": [idx], "staleness": [stale],
                    "version": version, "weights": np.array([w]),
                    "keep": 1.0 - w})
        dispatch(k, t)
    return out
