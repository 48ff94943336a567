"""Host spans of the federated engines.

Each host phase of a campaign runs under a ``jax.profiler.TraceAnnotation``
named ``fed.<phase>``; the device programs carry ``jax.named_scope``
scopes (``local_train``, ``aggregate``, ``eval``, ``policy_solve``) in
their HLO op metadata. Under ``jax.profiler.trace`` the spans land on the
profiler's host plane, on the same clock as the device's ``XLA Ops``
events, with their counts as event stats. With no profiler running a span
is one inactive-TraceMe check and a scope is compile-time metadata only.
``docs/observability.md`` lists what each span and scope covers.
"""

from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp

__all__ = ["span", "to_device"]


def span(phase: str, **stats) -> jax.profiler.TraceAnnotation:
    """The host span ``fed.<phase>``; ``stats`` become its event stats
    (more can be added inside with ``set_metadata``)."""
    return jax.profiler.TraceAnnotation("fed." + phase, **stats)


def to_device(*arrays) -> tuple:
    """``jnp.asarray`` of each array leaf of ``arrays`` (pytrees; None
    stays None) under a ``fed.h2d`` span whose ``bytes`` stat counts the
    bytes of the NumPy arrays among them (device arrays are not moved)."""
    leaves = jax.tree_util.tree_leaves(arrays)
    moved = sum(a.nbytes for a in leaves if isinstance(a, np.ndarray))
    with span("h2d", bytes=int(moved)):
        return jax.tree_util.tree_map(jnp.asarray, arrays)
