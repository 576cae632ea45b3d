"""Pallas TPU kernels for TopoSZp's compute hot spots.

Each kernel ships as <name>.py (pl.pallas_call + BlockSpec tiling) with a
pure-jnp oracle in ref.py and a jit'd public wrapper in ops.py.  On the
CPU the tests run the kernel bodies with interpret=True; on a TPU they
compile through Mosaic (tests/test_chip_compile.py compiles each one for
a described v5e without a chip).
"""
