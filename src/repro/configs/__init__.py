"""Configurations: the paper's CNN benchmarks (``cnn.py``) and the NoI
topology tables (``noi/``)."""
