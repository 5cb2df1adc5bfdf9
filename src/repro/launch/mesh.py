"""Production mesh construction.

A function (not a module-level constant) so importing this module never
touches jax device state.  Single pod: 16x16 = 256 chips (data x model).
Multi-pod: 2x16x16 = 512 chips (pod x data x model); the 'pod' axis crosses
DCN, 'data'/'model' stay on ICI.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _auto_mesh(shape, axes):
    # Auto axes: GSPMD propagates shardings from the constraints the model
    # code sets (``jax.make_mesh`` defaults to Explicit axes otherwise)
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_local_mesh(n_data: int = 1, n_model: int = 1):
    """Small mesh over however many local devices exist (tests/examples)."""
    return _auto_mesh((n_data, n_model), ("data", "model"))


# v5e-like hardware constants used by the roofline analysis (per chip).
PEAK_FLOPS_BF16 = 197e12          # FLOP/s
HBM_BW = 819e9                    # B/s
ICI_BW_PER_LINK = 50e9            # B/s per link
HBM_BYTES = 16 * 2 ** 30          # 16 GiB
