"""Production mesh definitions (TPU v5e pods).

``make_production_mesh`` is a FUNCTION so importing this module never touches
jax device state.  Single pod: 16x16 = 256 chips, axes (data, model).
Multi-pod: 2x16x16 = 512 chips, axes (pod, data, model) — the leading ``pod``
axis is pure data parallelism across pods (DCN), matching how Acme's learner
would be replicated per pod with gradient all-reduce across pods.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _auto_mesh(shape, axes):
    # Auto axes: the model code places arrays with with_sharding_constraint
    # (repro.sharding.shard), which explicit-typed axes reject.
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_host_mesh():
    """1-device mesh for CPU smoke runs (axes exist, size 1)."""
    return _auto_mesh((1, 1), ("data", "model"))


# TPU v5e hardware constants for the roofline analysis.
PEAK_FLOPS_BF16 = 197e12          # per chip
HBM_BW = 819e9                    # bytes/s per chip
ICI_BW = 50e9                     # bytes/s per link (~per chip per direction)
HBM_PER_CHIP = 16 * 1024 ** 3     # 16 GiB
