"""Core layers: RMSNorm, RoPE, GQA attention (full / sliding-window / cross),
and gated MLPs.

The attention *reference path* is a memory-efficient chunked implementation
(scan over query chunks — flash-style memory behavior at the XLA level) so
that 32k-token prefills fit HBM without a kernel; the Pallas flash kernel
(``repro.kernels``) replaces it on real TPUs via ``cfg.use_pallas``.

All activations carry logical-axis sharding constraints so that bodies lower
identically whether inside the full model or standalone (roofline tool).
"""

from __future__ import annotations

import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from .config import ArchConfig
from .params import ParamDef
from .scan import instrumented_scan
from .sharding import AX0, Ax, constrain

NEG_INF = -2.0**30  # large-but-finite: avoids NaN from all-masked rows


# ---------------------------------------------------------------------------
# norms
# ---------------------------------------------------------------------------

def rmsnorm_def(d: int, dtype: str) -> ParamDef:
    return ParamDef(shape=(d,), axes=("embed",), dtype=dtype, init="ones")


def rmsnorm(x: jax.Array, scale: jax.Array, eps: float) -> jax.Array:
    dtype = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    x = x * jax.lax.rsqrt(var + eps)
    return (x * scale.astype(jnp.float32)).astype(dtype)


# ---------------------------------------------------------------------------
# rotary position embedding
# ---------------------------------------------------------------------------

def rope_frequencies(head_dim: int, theta: float) -> jax.Array:
    exponents = jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim
    return 1.0 / (theta ** exponents)  # (head_dim/2,)


def apply_rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: (..., S, n, head_dim); positions: broadcastable to (..., S)."""
    head_dim = x.shape[-1]
    freqs = rope_frequencies(head_dim, theta)
    angles = positions[..., None].astype(jnp.float32) * freqs  # (..., S, hd/2)
    angles = angles[..., None, :]                              # (..., S, 1, hd/2)
    sin, cos = jnp.sin(angles), jnp.cos(angles)
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# attention parameter defs
# ---------------------------------------------------------------------------

def attention_defs(cfg: ArchConfig, *, cross: bool = False) -> Dict[str, ParamDef]:
    d, hd = cfg.d_model, cfg.resolved_head_dim
    h, kv = cfg.num_heads, cfg.num_kv_heads
    dt = cfg.dtype
    defs: Dict[str, ParamDef] = {
        "wq": ParamDef((d, h, hd), ("embed", "heads", "head_dim"), dt),
        "wk": ParamDef((d, kv, hd), ("embed", "kv_heads", "head_dim"), dt),
        "wv": ParamDef((d, kv, hd), ("embed", "kv_heads", "head_dim"), dt),
        "wo": ParamDef((h, hd, d), ("heads", "head_dim", "embed"), dt),
    }
    if cfg.qkv_bias and not cross:
        defs["bq"] = ParamDef((h, hd), ("heads", "head_dim"), dt, init="zeros")
        defs["bk"] = ParamDef((kv, hd), ("kv_heads", "head_dim"), dt, init="zeros")
        defs["bv"] = ParamDef((kv, hd), ("kv_heads", "head_dim"), dt, init="zeros")
    return defs


def _project_qkv(
    params: Dict[str, jax.Array],
    xq: jax.Array,
    xkv: jax.Array,
    cfg: ArchConfig,
    q_positions: jax.Array,
    kv_positions: Optional[jax.Array],
    *,
    rope: bool = True,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    q = jnp.einsum("bsd,dhk->bshk", xq, params["wq"])
    k = jnp.einsum("bsd,dhk->bshk", xkv, params["wk"])
    v = jnp.einsum("bsd,dhk->bshk", xkv, params["wv"])
    if "bq" in params:
        q = q + params["bq"]
        k = k + params["bk"]
        v = v + params["bv"]
    # constrain BEFORE rope as well as after: otherwise GSPMD propagation
    # invents partial shardings for the projection outputs and pays
    # full-replication reshards at the rope split/concat ops.
    q = constrain(q, "batch", "seq", "heads", "head_dim")
    k = constrain(k, "batch", "seq", "kv_heads", "head_dim")
    v = constrain(v, "batch", "seq", "kv_heads", "head_dim")
    if rope:
        q = apply_rope(q, q_positions, cfg.rope_theta)
        if kv_positions is not None:
            k = apply_rope(k, kv_positions, cfg.rope_theta)
    q = constrain(q, "batch", "seq", "heads", "head_dim")
    k = constrain(k, "batch", "seq", "kv_heads", "head_dim")
    v = constrain(v, "batch", "seq", "kv_heads", "head_dim")
    return q, k, v


def _softcap(scores: jax.Array, cap: float) -> jax.Array:
    if cap <= 0:
        return scores
    return cap * jnp.tanh(scores / cap)


# ---------------------------------------------------------------------------
# chunked (memory-efficient) attention — the XLA reference path
# ---------------------------------------------------------------------------

def _attend_chunk(
    q: jax.Array,          # (B, Cq, KV, G, hd) one query chunk, grouped
    k: jax.Array,          # (B, KV, S, hd), head-major as the cache is
    v: jax.Array,          # (B, KV, S, hd)
    q_start: jax.Array,    # global position of the chunk's first query:
                           # scalar, or (B,) when every batch row sits at its
                           # own position (continuous-batching decode)
    *,
    causal: bool,
    window: int,
    softcap: float,
    kv_valid_len: Optional[jax.Array],   # scalar or (B,)
) -> jax.Array:
    scale = 1.0 / math.sqrt(q.shape[-1])
    scores = jnp.einsum("bqngk,bnsk->bngqs", q, k).astype(jnp.float32) * scale
    scores = _softcap(scores, softcap)
    s_len = k.shape[2]
    q_start = jnp.asarray(q_start)
    # q_pos: (q,) for a shared scalar start, (B, q) for per-row starts
    q_pos = q_start[..., None] + jnp.arange(q.shape[1])
    k_pos = jnp.arange(s_len)
    mask = jnp.ones(q_pos.shape + (s_len,), dtype=bool)
    if causal:
        mask &= q_pos[..., None] >= k_pos
    if window > 0:
        mask &= q_pos[..., None] - k_pos < window
    if kv_valid_len is not None:
        valid = jnp.asarray(kv_valid_len)
        if valid.ndim:                       # (B,) per-row valid prefixes
            mask = mask & (k_pos < valid[:, None, None])
        else:
            mask = mask & (k_pos < valid)
    if mask.ndim == 3:                       # (B, q, s) → (B, 1, 1, q, s)
        mask = mask[:, None, None, :, :]
    scores = jnp.where(mask, scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    return jnp.einsum("bngqs,bnsk->bqngk", probs, v)


def multi_head_attention(
    params: Dict[str, jax.Array],
    x: jax.Array,
    cfg: ArchConfig,
    *,
    causal: bool = True,
    window: int = 0,
    xkv: Optional[jax.Array] = None,
    rope: bool = True,
    q_chunk: Optional[int] = None,
) -> jax.Array:
    """Full-sequence attention (training / prefill).

    GQA: queries grouped as (KV, G) so each KV head serves G query heads.
    Scans over query chunks so peak score memory is O(q_chunk · S).
    """
    b, s, _ = x.shape
    kv_src = xkv if xkv is not None else x
    positions = jnp.arange(s)
    kv_positions = None if xkv is not None else positions
    q, k, v = _project_qkv(
        params, x, kv_src, cfg, positions, kv_positions, rope=rope and xkv is None
    )
    h, kvh, hd = cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim
    g = h // kvh
    q = q.reshape(b, s, kvh, g, hd)
    kv_axes = ("batch", "kv_heads", "seq", "head_dim")
    k = constrain(k.transpose(0, 2, 1, 3), *kv_axes)
    v = constrain(v.transpose(0, 2, 1, 3), *kv_axes)

    if cfg.use_pallas and xkv is None:
        from repro.kernels.ops import attention as pallas_attention

        qh = q.reshape(b, s, h, hd).transpose(0, 2, 1, 3)
        out = pallas_attention(qh, k, v, causal, window, cfg.attn_softcap)
        out = out.transpose(0, 2, 1, 3)
        out = constrain(out, "batch", "seq", "heads", "head_dim")
        y = jnp.einsum("bshk,hkd->bsd", out, params["wo"])
        return constrain(y, "batch", "seq", "embed")

    chunk = min(q_chunk or cfg.attn_q_chunk, s)
    softcap = cfg.attn_softcap
    if s % chunk != 0:
        chunk = s  # irregular sizes: single chunk (smoke tests)

    if chunk == s:
        out = _attend_chunk(
            q, k, v, jnp.int32(0),
            causal=causal, window=window, softcap=softcap, kv_valid_len=None,
        )
    else:
        n_chunks = s // chunk
        q_chunks = q.reshape(b, n_chunks, chunk, kvh, g, hd).transpose(1, 0, 2, 3, 4, 5)

        def body(carry, xs):
            k_, v_ = carry
            idx, q_c = xs
            o = _attend_chunk(
                q_c, k_, v_, idx * chunk,
                causal=causal, window=window, softcap=softcap, kv_valid_len=None,
            )
            return carry, o

        kv_ax = Ax(kv_axes)
        _, outs = instrumented_scan(
            body,
            (k, v),
            (jnp.arange(n_chunks), q_chunks),
            name="attn_q_chunks",
            logical_axes=(
                (kv_ax, kv_ax),
                (AX0, Ax(("batch", None, "kv_heads", "q_per_kv",
                          "head_dim"))),
            ),
        )
        out = outs.transpose(1, 0, 2, 3, 4, 5).reshape(b, s, kvh, g, hd)

    out = out.reshape(b, s, h, hd)
    out = constrain(out, "batch", "seq", "heads", "head_dim")
    y = jnp.einsum("bshk,hkd->bsd", out, params["wo"])
    return constrain(y, "batch", "seq", "embed")


# ---------------------------------------------------------------------------
# int8 KV-cache quantization (per token × head absmax)
# ---------------------------------------------------------------------------

def kv_quantize(x: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """x: (..., hd) float → (int8 values, f32 scale over the last axis)."""
    absmax = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1, keepdims=True)
    scale = jnp.maximum(absmax / 127.0, 1e-8)
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / scale), -127, 127)
    return q.astype(jnp.int8), scale[..., 0]


def kv_dequantize(q: jax.Array, scale: jax.Array, dtype) -> jax.Array:
    return (q.astype(jnp.float32) * scale[..., None]).astype(dtype)


# ---------------------------------------------------------------------------
# attention over a head-major KV cache (decode steps and prefill chunks)
# ---------------------------------------------------------------------------

def _cache_put(cache: jax.Array, new: jax.Array, lead: Tuple,
               position: jax.Array) -> jax.Array:
    """Write ``new`` (B, KV, C, ...) into the head-major ``cache``
    (*lead, B, KV, S, ...) at sequence offset ``position``.  A (B,)
    ``position`` writes one row per batch entry (C == 1) with a scatter at
    (row, head, position), in which a row at ``S`` is out of bounds and
    drops."""
    new = new.astype(cache.dtype)
    if position.ndim:
        b, kv = new.shape[:2]
        rows = jnp.arange(b, dtype=jnp.int32)[:, None]
        heads = jnp.arange(kv, dtype=jnp.int32)[None, :]
        return cache.at[lead + (rows, heads, position[:, None])].set(
            new[:, :, 0], mode="drop")
    start = lead + (0, 0, position) + (0,) * (new.ndim - 3)
    return jax.lax.dynamic_update_slice(
        cache, new.reshape((1,) * len(lead) + new.shape), start)


def cached_attention(
    params: Dict[str, jax.Array],
    x: jax.Array,              # (B, C, d)
    cache_k: jax.Array,        # (*lead, B, KV, S_max, hd) — bf16 or int8
    cache_v: jax.Array,
    position: jax.Array,       # scalar int, or (B,) per-row positions
    cfg: ArchConfig,
    *,
    layer: Optional[jax.Array] = None,
    window: int = 0,
    cross: bool = False,
    k_scale: Optional[jax.Array] = None,   # (*lead, B, KV, S_max) — int8
    v_scale: Optional[jax.Array] = None,
) -> Tuple[jax.Array, jax.Array, jax.Array,
           Optional[jax.Array], Optional[jax.Array]]:
    """Attention of ``C`` new tokens at positions ``[position,
    position+C)`` over a KV cache, writing their K/V into it first.

    The cache is head-major, (B, KV, S_max, hd), the layout the attention
    dots read.  With ``layer`` it is the whole stack of a pattern block's
    caches, (L, B, KV, S_max, hd): the new rows are written in place at
    ``layer`` and attention reads that layer's slice, so a layer loop that
    carries the stack copies no cache.  With ``k_scale`` the caches are
    int8 (per token × head absmax), dequantized on read.

    ``position`` may be a (B,) vector for continuous batching (C == 1),
    where each batch row decodes at its own offset.  A per-row position of
    ``S_max`` is a write-proof sentinel: the row write drops and the row's
    output is ignored, which lets a fixed-slot engine run free slots
    through the same jitted step.  For a scalar ``position`` the chunk
    must stay in bounds (``position + C <= S_max``); rows past a prompt's
    end are masked by causality and overwritten before they enter the
    valid prefix.  For cross-attention the cache is the encoder/vision
    projection and is not written."""
    b, c, _ = x.shape
    position = jnp.asarray(position, dtype=jnp.int32)
    lead = () if layer is None else (layer,)
    q = jnp.einsum("bsd,dhk->bshk", x, params["wq"])
    if "bq" in params:
        q = q + params["bq"]
    if cross:
        valid_len = None
    else:
        positions = (position[:, None] if position.ndim
                     else position + jnp.arange(c, dtype=jnp.int32)[None, :])
        q = apply_rope(q, positions, cfg.rope_theta)
        k_new = jnp.einsum("bsd,dhk->bshk", x, params["wk"])
        v_new = jnp.einsum("bsd,dhk->bshk", x, params["wv"])
        if "bk" in params:
            k_new = k_new + params["bk"]
            v_new = v_new + params["bv"]
        k_new = apply_rope(k_new, positions, cfg.rope_theta)
        k_new = k_new.transpose(0, 2, 1, 3)               # (B, KV, C, hd)
        v_new = v_new.transpose(0, 2, 1, 3)
        if k_scale is not None:
            k_new, ks_new = kv_quantize(k_new)
            v_new, vs_new = kv_quantize(v_new)
            k_scale = _cache_put(k_scale, ks_new, lead, position)
            v_scale = _cache_put(v_scale, vs_new, lead, position)
        cache_k = _cache_put(cache_k, k_new, lead, position)
        cache_v = _cache_put(cache_v, v_new, lead, position)
        valid_len = position + c

    k_eff, v_eff = cache_k[lead], cache_v[lead]
    if k_scale is not None:
        k_eff = kv_dequantize(k_eff, k_scale[lead], x.dtype)
        v_eff = kv_dequantize(v_eff, v_scale[lead], x.dtype)
    cache_axes = ("cache_batch", "kv_heads", "cache_seq", "head_dim")
    k_eff, v_eff = constrain(k_eff, *cache_axes), constrain(v_eff, *cache_axes)

    h, kvh, hd = cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim
    g = h // kvh
    q = q.reshape(b, c, kvh, g, hd)
    out = _attend_chunk(
        q, k_eff, v_eff, jnp.int32(0) if cross else position,
        causal=not cross, window=0 if cross else window,
        softcap=cfg.attn_softcap, kv_valid_len=valid_len,
    )
    out = out.reshape(b, c, h, hd)
    y = jnp.einsum("bshk,hkd->bsd", out, params["wo"])
    return constrain(y, "batch", "seq", "embed"), cache_k, cache_v, \
        k_scale, v_scale


def prefill_kv(
    params: Dict[str, jax.Array],
    x: jax.Array,
    cfg: ArchConfig,
    cache_len: int,
) -> Tuple[jax.Array, jax.Array]:
    """Project K/V for a whole prompt into a fresh head-major cache
    (B, KV, cache_len, hd)."""
    b, s, _ = x.shape
    positions = jnp.arange(s)
    k = jnp.einsum("bsd,dhk->bshk", x, params["wk"])
    v = jnp.einsum("bsd,dhk->bshk", x, params["wv"])
    if "bk" in params:
        k = k + params["bk"]
        v = v + params["bv"]
    k = apply_rope(k, positions, cfg.rope_theta)
    pad = [(0, 0), (0, 0), (0, cache_len - s), (0, 0)]
    return (jnp.pad(k.transpose(0, 2, 1, 3), pad),
            jnp.pad(v.transpose(0, 2, 1, 3), pad))


# ---------------------------------------------------------------------------
# MLP
# ---------------------------------------------------------------------------

def mlp_defs(cfg: ArchConfig, d_ff: Optional[int] = None) -> Dict[str, ParamDef]:
    d, f, dt = cfg.d_model, d_ff or cfg.d_ff, cfg.dtype
    defs = {
        "w1": ParamDef((d, f), ("embed", "mlp"), dt),
        "w2": ParamDef((f, d), ("mlp", "embed"), dt),
    }
    if cfg.act in ("silu", "geglu"):  # gated variants need a third matrix
        defs["w3"] = ParamDef((d, f), ("embed", "mlp"), dt)
    return defs


def _activate(x: jax.Array, act: str) -> jax.Array:
    if act in ("silu",):
        return jax.nn.silu(x)
    if act in ("gelu", "geglu"):
        return jax.nn.gelu(x, approximate=True)
    raise ValueError(f"unknown activation {act!r}")


def mlp(params: Dict[str, jax.Array], x: jax.Array, act: str) -> jax.Array:
    h = jnp.einsum("bsd,df->bsf", x, params["w1"])
    h = _activate(h, act)
    if "w3" in params:
        h = h * jnp.einsum("bsd,df->bsf", x, params["w3"])
    h = constrain(h, "batch", "seq", "mlp")
    y = jnp.einsum("bsf,fd->bsd", h, params["w2"])
    return constrain(y, "batch", "seq", "embed")
