"""Pallas TPU kernels for the MDM/CIM hot paths.

Each kernel lives in its own subpackage with the canonical layout:

    kernels/<name>/kernel.py   pl.pallas_call + explicit BlockSpec tiling
    kernels/<name>/ops.py      jit'd public wrapper (padding, dtype mgmt)
    kernels/<name>/ref.py      pure-jnp oracle used by the allclose tests

Kernels target TPU (VMEM tiling, MXU-aligned blocks); on this CPU
container they are validated via ``interpret=True``, which executes the
kernel body per-block in Python.  The validation wrappers default to
``repro.kernels.runtime.default_interpret()``, read at call time.
"""
