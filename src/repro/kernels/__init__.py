"""Pallas TPU kernels for the framework's compute hot spots.

Each kernel ships three artifacts (per the repo convention):
  <name>.py  — pl.pallas_call + BlockSpec implementation (TPU target)
  ops.py     — jitted public wrappers (interpret=True only on request)
  ref.py     — pure-jnp oracles used by the allclose test sweeps
"""
from repro.kernels import ops, ref  # noqa: F401
from repro.kernels.ops import decode_attention, flash_attention, ssd_scan, vtrace  # noqa: F401
