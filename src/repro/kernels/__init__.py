"""Pallas TPU kernels for the BILU(k) numeric phase.

Layout per kernel: ``<name>.py`` (pl.pallas_call + BlockSpec), wrapped by
``ops.py`` (jit + padding, kernel mode chosen by platform), oracled by
``ref.py`` (pure jnp). Kernels target TPU VMEM/MXU; on the CPU they run in
interpret mode.
"""

from .ops import (  # noqa: F401
    panel_update,
    trsm_left_unit_lower,
    trsm_right_upper,
)
