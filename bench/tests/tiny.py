"""A cell small enough for the CPU: the real configuration's shape with
a 4-learner fleet, a narrow MLP and two cycles."""

import copy
import json
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]


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
