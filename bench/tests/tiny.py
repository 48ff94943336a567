"""A cell small enough for the CPU: the real configuration's shape with
a 4-learner fleet, a narrow MLP and two cycles, and ``run_tiny``, which
drives ``harness.run`` (the whole run after the look for a chip) on it
and holds it to the real cell's committed limits."""

import copy
import json
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
SPEC = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
#: the real cell whose committed limits a tiny run of each mix is held to
CELLS = {"barrier": "mel_mnist_k20.barrier",
         "fedasync": "mel_mnist_k20.fedasync",
         "realloc_drift": "mel_mnist_k20.realloc_drift"}

#: the widths of each real configuration's MLP, as its source gives them
WIDTHS = {"mel_mnist_k20": [784, 300, 124, 60, 10],
          "fedavg_2nn_k100": [784, 200, 200, 10]}


def config(name="mel_mnist_k20"):
    cfg = json.loads((BENCH / "configs" / f"{name}.json").read_text())
    cfg["model"]["layers"] = [784, 16, 10]
    cfg["fleet"].update(learners=4, samples_per_cycle=200, T=0.1)
    cfg["data"].update(train=400, eval=100)
    return cfg


def traffic(mix):
    tr = json.loads((BENCH / "traffic" / f"{mix}.json").read_text())
    tr = copy.deepcopy(tr)
    if "cycles" in tr:
        tr["cycles"] = 2
    tr["checked_campaigns"] = 2
    return tr


def limits(mix):
    return json.loads((BENCH / "checks" / f"{CELLS[mix]}.json").read_text())


def run_tiny(mix, seed=2**31 + 99, seconds=0.0, cfg=None):
    """``harness.run`` of the tiny cell (or of ``cfg``) under ``mix``, with
    the v5e's peaks standing in for the CPU's."""
    from bench import harness

    real_peaks = harness.work.peaks
    harness.work.peaks = lambda kind: real_peaks("TPU v5 lite")
    try:
        return harness.run(cfg or config(), traffic(mix), limits(mix),
                           seed=seed, seconds=seconds, trace=False,
                           t_start=time.perf_counter(), e2e=SPEC["end_to_end"],
                           per_layer=[])
    finally:
        harness.work.peaks = real_peaks
