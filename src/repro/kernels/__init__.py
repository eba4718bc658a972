"""Pallas TPU kernels: run on a TPU, checked in interpret mode and
compiled for a described v5e on CPU (tests/test_kernels.py,
tests/test_tpu_compile.py); ``ops`` picks kernel or jnp fallback by
platform.

Each kernel: <name>.py (pl.pallas_call + BlockSpec), with jit'd wrappers in
ops.py and pure-jnp oracles in ref.py.
"""
from repro.kernels import ops, ref  # noqa: F401
