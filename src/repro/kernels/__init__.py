"""Pallas TPU kernels for the framework's compute hot spots.

Each kernel package contains:
  kernel.py — pl.pallas_call with explicit BlockSpec VMEM tiling (TPU target)
  ops.py    — jit'd public wrapper (shape checks, dtype policy, vmap rules)
  ref.py    — pure-jnp oracle used by the interpret=True correctness sweeps
"""
