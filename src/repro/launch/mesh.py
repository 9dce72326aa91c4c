"""Production mesh builders.

Functions, not module-level constants: importing this module never touches
jax device state (device count is locked on first use).

Target: TPU v5e pods. Single pod = 16x16 = 256 chips with axes
('data', 'model'); multi-pod = 2 pods = 512 chips with ('pod', 'data',
'model') where 'pod' carries pure data parallelism over the slower
inter-pod links (its gradient all-reduce is the only traffic that crosses
pods, once per step, overlappable with the tail of backward).
"""

from __future__ import annotations

import jax

__all__ = ["make_production_mesh", "make_host_mesh", "HW"]


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_host_mesh(data: int = 1, model: int = 1):
    """Small mesh over whatever devices exist (tests / local runs)."""
    n = len(jax.devices())
    data = min(data, n)
    model = min(model, max(n // data, 1))
    return _auto_mesh((data, model), ("data", "model"))


def _auto_mesh(shape, axes):
    """``jax.make_mesh`` with Auto axes: the partitioner places what the
    code leaves unannotated (slot surgery's scatters included), as it did
    before Explicit axes became the default."""
    auto = (jax.sharding.AxisType.Auto,) * len(axes)
    return jax.make_mesh(shape, axes, axis_types=auto)


class HW:
    """TPU v5e hardware constants used by the roofline model."""
    PEAK_FLOPS_BF16 = 197e12      # per chip
    HBM_BW = 819e9                # bytes/s per chip
    ICI_BW = 50e9                 # bytes/s per link (~per-direction)
    HBM_BYTES = 16 * 2 ** 30      # 16 GiB
    VMEM_BYTES = 128 * 2 ** 20
