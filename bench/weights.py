"""Seeded random weights for a configuration, in the tree the served model
takes, made on the device in one jitted call.

The layout (which leaf holds which matrix, in which axis order) is the
program's, written down by the configuration's family
(``families/<model_type>.py``, ``layout``); ``harness.check_layout``
compares it with the model's own abstract tree before anything is
installed.  The values are the benchmark's: the reference reads these same
arrays and nothing the program made.
"""

from __future__ import annotations

import json
from typing import Tuple

import jax
import jax.numpy as jnp

import spec


def _is_spec(x) -> bool:
    return isinstance(x, tuple) and len(x) == 3 and isinstance(x[1], str)


def seed_key(seed: int) -> jax.Array:
    """A key from any whole number, including seeds past 32 bits."""
    seed = int(seed) & (2**64 - 1)
    key = jax.random.key(jnp.uint32(seed & 0xFFFFFFFF))
    return jax.random.fold_in(key, jnp.uint32(seed >> 32))


def _leaf(key, desc: Tuple, dtype):
    shape, kind, std = desc
    x = jax.random.normal(key, shape, jnp.float32) * std
    if kind == "norm":
        x = x + 1.0
    return x.astype(dtype)


_MAKERS: dict = {}


def maker(cj: dict):
    """A jitted ``key -> tree``: every leaf from its own fold of the key,
    in the configuration's dtype.  One per configuration and process."""
    name = json.dumps(cj, sort_keys=True)
    if name not in _MAKERS:
        _MAKERS[name] = _maker(cj)
    return _MAKERS[name]


def _maker(cj: dict):
    tree = spec.family(cj).layout(cj)
    dtype = jnp.dtype(cj["torch_dtype"])
    leaves, treedef = jax.tree.flatten(tree, is_leaf=_is_spec)

    @jax.jit
    def make(key):
        return jax.tree.unflatten(treedef, [
            _leaf(jax.random.fold_in(key, i), desc, dtype)
            for i, desc in enumerate(leaves)])

    return make


def make(cj: dict, seed: int, step: int = 1):
    """The weights of publish ``step`` for ``seed``, on the device."""
    return jax.block_until_ready(
        maker(cj)(jax.random.fold_in(seed_key(seed), step)))
