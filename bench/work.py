"""The useful work of a campaign, the chip's peaks to hold it against,
and the device memory the benchmark's own state may take.

Useful work counts what the algorithm requires, whatever runs it: every
learner's tau_k full-batch gradient steps over its d_k samples, so
sum(tau_k d_k) sample-steps per aggregation, at the learner model's
FLOPs per sample-step (its family's ``flops_per_sample_step``, in
``bench/models/``). Padded rows and steps past tau_k are not counted,
and neither is evaluation.
"""

from __future__ import annotations

import json
import math
from pathlib import Path

PEAKS = Path(__file__).with_name("peaks.json")


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


def flops(model, cfg: dict, steps: int) -> float:
    """FLOPs of ``steps`` sample-steps of the family ``model``."""
    return model.flops_per_sample_step(cfg) * steps


def least_seconds(model, cfg: dict, rows, peak: dict) -> float:
    """The larger of the useful FLOPs at peak FLOP/s and the unavoidable
    bytes (the family's ``least_bytes``) at peak HBM bandwidth."""
    return max(flops(model, cfg, sample_steps(rows)) / peak["flops_per_s"],
               model.least_bytes(cfg, rows) / peak["hbm_bytes_per_s"])


def memory_budget() -> float:
    """Bytes of device memory that the benchmark's own copies of params may
    take: half of what the fullest device (the highest peak) leaves free
    at its peak so far, so that the program keeps room for what it needed
    (after set-up, the warm-up campaign's peak). Unbounded (``inf``) where
    the backend reports no memory, as the CPU does. The reference's groups
    of learners and the window's finished params are held to it."""
    import jax

    stats = [s for s in (d.memory_stats() for d in jax.devices()) if s]
    if not stats:
        return math.inf
    fullest = max(stats, key=lambda s: s.get("peak_bytes_in_use", 0))
    return (fullest["bytes_limit"] - fullest.get("peak_bytes_in_use", 0)) / 2


def tree_bytes(tree) -> int:
    """Bytes of the arrays of a pytree."""
    import jax

    return sum(int(a.nbytes) for a in jax.tree_util.tree_leaves(tree))
