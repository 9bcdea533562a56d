"""Jitted public wrappers for the Pallas kernels.

``use_pallas`` in an ``ArchConfig`` routes the model's attention / SSD
compute through these.  ``interpret`` is explicit and defaults to False,
which compiles Mosaic for the TPU; the CPU tests pass ``interpret=True``.
There is no backend-based fallback: a kernel that cannot compile for the
device fails loudly rather than running in the interpreter.

The attention wrapper exposes a custom VJP whose backward pass recomputes
through the pure-jnp reference — flash-style forward memory behavior with a
numerically-identical backward (kernelizing the backward is a further perf
iteration; see EXPERIMENTS.md §Perf).
"""

from __future__ import annotations

import functools

import jax

from .flash_attention import flash_attention
from .ref import attention_ref
from .ssd_scan import ssd_scan


@functools.partial(jax.custom_vjp,
                   nondiff_argnums=(3, 4, 5, 6))
def attention(q, k, v, causal: bool = True, window: int = 0,
              softcap: float = 0.0, interpret: bool = False):
    return flash_attention(q, k, v, causal=causal, window=window,
                           softcap=softcap, interpret=interpret)


def _attn_fwd(q, k, v, causal, window, softcap, interpret):
    return attention(q, k, v, causal, window, softcap, interpret), (q, k, v)


def _attn_bwd(causal, window, softcap, interpret, res, g):
    q, k, v = res
    _, vjp = jax.vjp(
        lambda q_, k_, v_: attention_ref(q_, k_, v_, causal=causal,
                                         window=window, softcap=softcap),
        q, k, v)
    return vjp(g)


attention.defvjp(_attn_fwd, _attn_bwd)


def ssd(x, da, b_mat, c_mat, *, chunk: int = 256,
        interpret: bool = False):
    """Chunked SSD scan: (y (B,S,H,P) f32, final_state (B,H,P,N) f32)."""
    return ssd_scan(x, da, b_mat, c_mat, chunk=chunk, interpret=interpret)
