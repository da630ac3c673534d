"""Pallas TPU kernels for the framework's compute hot spots.

Each kernel package ships three files per the repo convention:
``<name>.py`` (pl.pallas_call + BlockSpec), ``ops.py`` (jitted wrapper with
the public contract), ``ref.py`` (pure-jnp oracle).  Interpret mode is
derived from the backend in ``mode.py``: the kernels compile on a TPU and
run in the Pallas interpreter elsewhere (the CPU test suite).  BlockSpecs
follow the TPU (8,128) tiling rule.
"""
from . import (late_gather, embedding_bag, spmm_segment,  # noqa: F401
               frontier_expand, frontier_pull)
