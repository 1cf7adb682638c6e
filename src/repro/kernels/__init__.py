"""Pallas kernels: the CIM crossbar MAC (``cim_matmul.py``) and their
pure-jnp oracles (``ref.py``)."""
