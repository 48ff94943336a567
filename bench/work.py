"""The useful work of a campaign, and the chip's peaks to hold it against.

Useful work counts what the algorithm requires, whatever runs it: every
learner's tau_k full-batch gradient steps over its d_k samples, so
sum(tau_k d_k) sample-steps per aggregation, at 6 FLOPs per parameter
per sample-step (forward 2, backward 4). Padded rows and steps past
tau_k are not counted, and neither is evaluation.
"""

from __future__ import annotations

import json
from pathlib import Path

from bench.fleet import param_count

PEAKS = Path(__file__).with_name("peaks.json")
BYTES_F32 = 4


class UnknownDevice(KeyError):
    pass


def peaks(device_kind: str, table: Path = PEAKS) -> dict:
    """Peak FLOP/s and HBM bytes/s of ``device_kind``. A kind that is not in
    the table is an error, never a default."""
    known = json.loads(table.read_text())
    if device_kind not in known:
        raise UnknownDevice(f"no peaks for device kind {device_kind!r} in "
                            f"{table.name}; known: {sorted(known)}")
    return known[device_kind]


def sample_steps(rows) -> int:
    """sum over aggregations and learners of tau_k d_k."""
    return sum(int((r["tau"] * r["d"]).sum()) for r in rows)


def flops(layers, steps: int) -> float:
    return 6.0 * param_count(layers) * steps


def least_bytes(layers, rows) -> float:
    """HBM traffic no implementation can avoid: each learner reads its
    shard (784 f32 features and an int32 label per sample) and its start
    params once, and writes its trained params once, per round."""
    p = param_count(layers) * BYTES_F32
    per_sample = (layers[0] + 1) * BYTES_F32
    return float(sum(int(r["d"].sum()) * per_sample + 2 * p * len(r["d"])
                     for r in rows))


def least_seconds(layers, rows, peak: dict) -> float:
    """The larger of the useful FLOPs at peak FLOP/s and the unavoidable
    bytes at peak HBM bandwidth."""
    return max(flops(layers, sample_steps(rows)) / peak["flops_per_s"],
               least_bytes(layers, rows) / peak["hbm_bytes_per_s"])
