"""Seeded random weights for a configuration, in the tree the served model
takes, made on the device in one jitted call.

The layout (which leaf holds which matrix, in which axis order) is the
program's; ``harness.check_layout`` compares it with the model's own
abstract tree before anything is installed.  The values are the
benchmark's: the reference reads these same arrays and nothing the program
made.
"""

from __future__ import annotations

import json
from typing import Dict, Tuple

import jax
import jax.numpy as jnp

BIAS_STD = 0.2
NORM_STD = 0.05


def dims(cj: dict) -> Dict[str, int]:
    d, h = int(cj["hidden_size"]), int(cj["num_attention_heads"])
    v = int(cj["vocab_size"])
    return {"d": d, "h": h, "kv": int(cj["num_key_value_heads"]),
            "hd": int(cj.get("head_dim") or d // h),
            "f": int(cj["intermediate_size"]), "v": v,
            "vp": -(-v // 256) * 256,  # the program pads the vocab rows
            "layers": int(cj["num_hidden_layers"]),
            "tied": bool(cj["tie_word_embeddings"]),
            "bias": bool(cj.get("qkv_bias", False))}


def layout(cj: dict) -> dict:
    """path tree of (shape, kind, std): kind is 'normal', 'norm' or
    'bias'."""
    n = dims(cj)
    d, h, kv, hd, f, L, vp = (n["d"], n["h"], n["kv"], n["hd"], n["f"],
                              n["layers"], n["vp"])
    attn = {"wq": ((L, d, h, hd), "normal", d ** -0.5),
            "wk": ((L, d, kv, hd), "normal", d ** -0.5),
            "wv": ((L, d, kv, hd), "normal", d ** -0.5),
            "wo": ((L, h, hd, d), "normal", (h * hd) ** -0.5)}
    if n["bias"]:
        attn.update({"bq": ((L, h, hd), "bias", BIAS_STD),
                     "bk": ((L, kv, hd), "bias", BIAS_STD),
                     "bv": ((L, kv, hd), "bias", BIAS_STD)})
    block = {"ln1": ((L, d), "norm", NORM_STD), "attn": attn,
             "ln2": ((L, d), "norm", NORM_STD),
             "mlp": {"w1": ((L, d, f), "normal", d ** -0.5),
                     "w2": ((L, f, d), "normal", f ** -0.5),
                     "w3": ((L, d, f), "normal", d ** -0.5)}}
    tree = {"embed": ((vp, d), "normal", d ** -0.5),
            "final_norm": ((d,), "norm", NORM_STD),
            "pattern": {"00_attn": block}}
    if not n["tied"]:
        tree["lm_head"] = ((d, vp), "normal", d ** -0.5)
    return tree


def _is_spec(x) -> bool:
    return isinstance(x, tuple) and len(x) == 3 and isinstance(x[1], str)


def seed_key(seed: int) -> jax.Array:
    """A key from any whole number, including seeds past 32 bits."""
    seed = int(seed) & (2**64 - 1)
    key = jax.random.key(jnp.uint32(seed & 0xFFFFFFFF))
    return jax.random.fold_in(key, jnp.uint32(seed >> 32))


def _leaf(key, spec: Tuple, dtype):
    shape, kind, std = spec
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
    tree = layout(cj)
    dtype = jnp.dtype(cj["torch_dtype"])
    leaves, treedef = jax.tree.flatten(tree, is_leaf=_is_spec)

    @jax.jit
    def make(key):
        return jax.tree.unflatten(treedef, [
            _leaf(jax.random.fold_in(key, i), spec, dtype)
            for i, spec in enumerate(leaves)])

    return make


def make(cj: dict, seed: int, step: int = 1):
    """The weights of publish ``step`` for ``seed``, on the device."""
    return jax.block_until_ready(
        maker(cj)(jax.random.fold_in(seed_key(seed), step)))
