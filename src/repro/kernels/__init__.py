"""Pallas TPU kernels for the matcher's hot loop.

skipper_match/    — the paper's hot loop: windowed single-pass greedy matching

The package ships kernel.py (pl.pallas_call + BlockSpec), ops.py (jit'd
wrapper), ref.py (pure-jnp oracle). Validated with interpret=True on CPU;
BlockSpecs are written for TPU VMEM tiling (see docstrings).
"""
