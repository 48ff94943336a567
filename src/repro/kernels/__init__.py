"""Pallas TPU kernels (``<name>.py``), their pure-jnp oracles (``ref.py``)
and the ``use_pallas`` dispatch between the two (``ops.py``)."""
