"""The one JAX surface the codebase imports through this module.

The installation is jax 0.9.0 / jaxlib 0.9.0 / libtpu 0.0.34:
``jax.shard_map`` is public there and takes ``check_vma``, which every
call site passes.
"""
from jax import shard_map

__all__ = ["shard_map"]
