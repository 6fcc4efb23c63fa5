"""Production mesh construction.

A FUNCTION (not a module-level constant) so importing this module never
touches jax device state — dryrun.py must set XLA_FLAGS before any jax
device query, and tests/benches must keep seeing 1 device.
"""
from __future__ import annotations

import jax

SINGLE_POD = (16, 16)            # 256 chips (v5e pod)
MULTI_POD = (2, 16, 16)          # 2 pods = 512 chips


def make_production_mesh(*, multi_pod: bool = False):
    shape = MULTI_POD if multi_pod else SINGLE_POD
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    # GSPMD (Auto) axes: the train and dry-run steps place data through
    # sharding constraints, which explicit axes do not take
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def device_count_required(multi_pod: bool) -> int:
    shape = MULTI_POD if multi_pod else SINGLE_POD
    n = 1
    for s in shape:
        n *= s
    return n
