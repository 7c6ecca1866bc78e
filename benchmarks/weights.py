"""The benchmark's own seeded weights: one jitted call on the device, in the
type the program holds them in. The program and the plain reference are both
handed these; neither makes its own."""

from __future__ import annotations

import functools
import zlib

import jax
import jax.numpy as jnp


def seed_key(seed: int):
    """A key from any whole number (the driver's seeds pass 2**31)."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF), seed >> 31)


def _leaf(key, name: str, shape, dtype):
    leaf = name.rsplit("/", 1)[-1]
    noise = jax.random.normal(key, shape, jnp.float32)
    if leaf == "word_emb":
        out = noise * shape[-1] ** -0.5
    elif leaf == "w":
        out = noise * (2.0 / (shape[0] + shape[-1])) ** 0.5
    elif leaf == "b":
        out = noise * 0.02
    elif leaf == "scale":
        out = 1.0 + 0.1 * noise
    elif leaf == "bias":
        out = 0.1 * noise
    else:
        raise ValueError(f"no seeded-weight rule for parameter {name!r}")
    return out.astype(dtype)


def as_float32(shapes: dict) -> dict:
    """The same leaves in float32, the type the plain reference computes in."""
    return {k: jax.ShapeDtypeStruct(s.shape, jnp.float32) for k, s in shapes.items()}


@functools.lru_cache(maxsize=8)
def _builder(spec):
    """The jitted maker of the leaves ``spec`` names, kept so that a second
    call in one process (the program's weights, then the reference's) traces
    and compiles nothing again."""
    def build(key):
        return {n: _leaf(jax.random.fold_in(key, zlib.crc32(n.encode()) & 0x7FFFFFFF),
                         n, shape, dtype) for n, shape, dtype in spec}

    return jax.jit(build)


def make_weights(shapes: dict, seed: int) -> dict:
    """{name: array} for ``shapes`` ({name: ShapeDtypeStruct}); a leaf's key
    depends on the seed and its name only, not on which other leaves exist."""
    spec = tuple((n, tuple(shapes[n].shape), jnp.dtype(shapes[n].dtype).name)
                 for n in sorted(shapes))
    return _builder(spec)(seed_key(seed))
