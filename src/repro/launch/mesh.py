"""Mesh construction for the production pod(s), tests, and host-CPU runs.

Defined as FUNCTIONS so importing this module never touches jax device
state. The dry-run entrypoint sets XLA_FLAGS before importing jax; nothing
else in the codebase ever asks for more devices than exist.

Host-CPU fake-device path: XLA can split the host CPU into N fake devices
with ``--xla_force_host_platform_device_count=N`` (must be in XLA_FLAGS
*before* jax initializes — i.e. set in the environment of a fresh process,
as the fleet-scale CI step and the sharding subprocess tests do).
``host_mesh()`` lays a data×model mesh over every device the process
sees, so the same code runs 1-device CI, 8-fake-device sharded CI (the
(2, 4) ``"test"`` shape) and a four-chip TPU host (2, 2) unchanged.
"""

from __future__ import annotations

import jax

__all__ = [
    "make_production_mesh",
    "make_mesh_by_name",
    "MESH_SPECS",
    "device_count_for",
    "host_mesh",
    "host_device_flags",
]

#: the XLA flag that splits the host CPU into fake devices (set it in
#: XLA_FLAGS before jax import; see module docstring)
XLA_HOST_DEVICES_FLAG = "--xla_force_host_platform_device_count"


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes)


# name -> (shape, axes); "test" variants run inside CI with 8/16 fake devices
MESH_SPECS = {
    "pod": ((16, 16), ("data", "model")),
    "multipod": ((2, 16, 16), ("pod", "data", "model")),
    "test": ((2, 4), ("data", "model")),
    "multitest": ((2, 2, 4), ("pod", "data", "model")),
    "cpu": ((1, 1), ("data", "model")),
}


def device_count_for(name: str) -> int:
    shape, _ = MESH_SPECS[name]
    n = 1
    for s in shape:
        n *= s
    return n


def make_mesh_by_name(name: str):
    shape, axes = MESH_SPECS[name]
    return jax.make_mesh(shape, axes)


def host_device_flags(n: int = 8) -> str:
    """The XLA_FLAGS value that gives a fresh process ``n`` fake host-CPU
    devices (append to any existing flags, space-separated)."""
    return f"{XLA_HOST_DEVICES_FLAG}={n}"


def host_mesh():
    """A ``("data", "model")`` mesh over every device this process has:
    (2, n/2) for an even n >= 4 — the (2, 4) ``"test"`` shape on 8 fake
    host-CPU devices, (2, 2) on a four-chip TPU host — else (1, n). THE
    mesh entry point for the fleet engine and its CI step."""
    n = len(jax.devices())
    data = 2 if n >= 4 and n % 2 == 0 else 1
    return jax.make_mesh((data, n // data), ("data", "model"))
