"""Seeded weights, made on the device, that the reference can remake.

Every leaf is drawn from its own key, ``fold_in`` of the run's key with
a hash of the leaf's name, and a stacked leaf layer by layer from
``fold_in`` of that with the layer index.  The values are integers drawn
uniformly from [-2^15, 2^15), times a power of two, rounded to the
served dtype: integer draws and an exact scaling, so the program's
weights and the reference's copy of one layer are bit for bit the same
wherever either is made.  The spread is ``1/sqrt(fan_in)`` (1 for the
embedding), as a uniform distribution; norm scales are ones.  Fan-in is
the contraction size of one layer's leaf: ``shape[-2]`` for a leaf of
two or more axes, so an expert leaf ``(E, d, f)`` gets ``1/sqrt(d)``,
and ``shape[0]`` for a 1-D leaf.  For a 2-D leaf that is the program's
own init (``dense_init`` in ``repro.models.layers``); for a 3-D leaf
that init takes ``shape[0]``, the number of experts.
"""
from __future__ import annotations

import math
import zlib
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

HALF = 1 << 15


def run_key(seed: int) -> jax.Array:
    """A key from any whole number up to 64 bits."""
    seed = int(seed)
    if seed < 0 or seed >= 1 << 64:
        raise ValueError(f"seed {seed} outside [0, 2**64)")
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, seed >> 32)


def leaf_key(key: jax.Array, name: str) -> jax.Array:
    return jax.random.fold_in(key, zlib.crc32(name.encode()) & 0x7FFFFFFF)


def _scale(name: str, shape: Tuple[int, ...]) -> float:
    """The power of two that gives the draw the init's spread."""
    fan_in = shape[-2] if len(shape) >= 2 else shape[0]
    std = 1.0 if name == "embed" else 1.0 / math.sqrt(fan_in)
    return 2.0 ** round(math.log2(std * math.sqrt(3.0) / HALF))


def draw(key: jax.Array, name: str, shape: Tuple[int, ...], dtype,
         layer: Optional[jax.Array] = None) -> jax.Array:
    """One layer's leaf ``name`` of ``shape`` (per layer, without the
    stacking axis), in ``dtype``."""
    if name.endswith("scale"):
        return jnp.ones(shape, dtype)
    k = leaf_key(key, name)
    if layer is not None:
        k = jax.random.fold_in(k, layer)
    ints = jax.random.randint(k, shape, -HALF, HALF, jnp.int32)
    return (ints.astype(jnp.float32) * _scale(name, shape)).astype(dtype)


def leaf_name(path) -> Tuple[str, bool]:
    """(name, stacked) of a program parameter path: the group's stacked
    leaves drop their ``stack/group/b<i>`` prefix and draw per layer."""
    parts = [str(getattr(p, "key", getattr(p, "idx", p))) for p in path]
    if parts[:2] == ["stack", "group"]:
        return "/".join(parts[3:]), True
    return "/".join(parts), False


def program_params(model, seed: int):
    """The program's parameter tree for ``model``, drawn from ``seed`` in
    one jitted call on the default device."""
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))

    def make(key):
        def one(path, sd):
            name, stacked = leaf_name(path)
            if not stacked:
                return draw(key, name, sd.shape, sd.dtype)
            layers = jnp.arange(sd.shape[0], dtype=jnp.int32)
            return jax.vmap(lambda l: draw(key, name, sd.shape[1:],
                                           sd.dtype, l))(layers)
        return jax.tree_util.tree_map_with_path(one, shapes)

    return jax.jit(make)(run_key(seed))
