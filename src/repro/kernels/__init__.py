"""Pallas TPU kernels (validated with interpret=True on CPU).

Each kernel ships three modules:
  kernel.py — pl.pallas_call + BlockSpec VMEM tiling
  ops.py    — jit'd wrapper (layout, padding, backend dispatch)
  ref.py    — pure-jnp oracle used by the allclose test sweeps
"""
import jax


def interpret_default() -> bool:
    """Whether kernels run in the Pallas interpreter: False (compiled
    with Mosaic) on a TPU backend, True anywhere else.  The one place
    every wrapper asks, so a check that the chip runs compiled kernels
    reads this and nothing else."""
    return jax.default_backend() != "tpu"
