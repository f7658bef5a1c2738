"""Kernel runtime switches."""
from __future__ import annotations

import jax


def default_interpret() -> bool:
    """Whether a validation wrapper runs its Pallas kernel interpreted.

    True off TPU: ``pallas_call(interpret=True)`` runs the kernel body
    block-by-block in the Python interpreter, giving bit-faithful
    validation of the BlockSpec tiling logic without TPU hardware.  Read
    at call time, so importing ``repro.kernels`` initialises no backend.
    """
    return jax.default_backend() != "tpu"


def round_up(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m
