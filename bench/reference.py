"""The plain reference: the Qwen2 forward pass in float32, written from the
published architecture, with no cache, no batching and no code of the
program.

    x = embed[tokens]
    per layer:  x += o(attn(rope(q(n1(x)) + bq), rope(k(n1(x)) + bk),
                             v(n1(x)) + bv))       # causal, GQA
                x += w2(silu(w1(n2(x))) * w3(n2(x)))
    logits = head(norm(x))                          # head = embed^T if tied

RMSNorm is ``w * x / sqrt(mean(x^2) + eps)``; rope rotates the two halves
of each head (``rotate_half``) with frequencies ``theta^(-2i/hd)``.

It teacher-forces packed sequences (prompt followed by the served tokens)
and returns logits only at the rows whose next token was served.  It runs
after the measured window, layer by layer (a scan over the stacked
weights), in blocks of query rows for attention and of token rows for the
MLP, so that it fits beside the weights on one chip.

Every matrix product is float32 to float32 rounding without a float32 copy
of a weight: the weights are bfloat16 (or float8 in the control), so each
is exact in bfloat16, and the float32 activation is split into three
bfloat16 parts whose products are exact and summed in float32.  Attention
scores and their weighted sum use ``Precision.HIGHEST``.

The control (``quantize``) is this same reference with every matrix in
float8 e4m3, scaled per output column: the step below the configuration's
bfloat16.
"""

from __future__ import annotations

import math
from functools import partial
from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

import weights

F32, BF16 = jnp.float32, jnp.bfloat16
HIGHEST = jax.lax.Precision.HIGHEST
Q_BLOCK = 256      # query rows per attention block
MLP_BLOCK = 512    # token rows per MLP block
WANT_MAX = 1024    # logits rows computed per pass
FP8_MAX = 448.0    # largest finite float8 e4m3 value

# matrices quantized in the control; each output column has its own scale
_MATRICES = frozenset({"wq", "wk", "wv", "wo", "w1", "w2", "w3"})


# ---------------------------------------------------------------------------
# packing the sample
# ---------------------------------------------------------------------------

def pack(seqs: Sequence[Tuple[Sequence[int], Sequence[int]]], rows: int):
    """Lay (prompt, served) pairs end to end in ``rows`` token rows.
    Returns tokens, positions, segment ids (-1 for padding), the rows whose
    logits predict a served token, and those tokens."""
    toks = np.zeros(rows, np.int32)
    pos = np.zeros(rows, np.int32)
    seg = np.full(rows, -1, np.int32)
    want, target = [], []
    r = 0
    for j, (prompt, served) in enumerate(seqs):
        inp = list(prompt) + list(served)[:-1]
        t = len(inp)
        if r + t > rows:
            raise ValueError(f"sample needs more than {rows} rows")
        toks[r:r + t] = inp
        pos[r:r + t] = np.arange(t)
        seg[r:r + t] = j
        want.extend(r + len(prompt) - 1 + i for i in range(len(served)))
        target.extend(served)
        r += t
    if len(want) > WANT_MAX:
        raise ValueError(f"sample serves more than {WANT_MAX} tokens")
    n = len(want)
    want = np.array(want + [0] * (WANT_MAX - n), np.int32)
    target = np.array(list(target) + [0] * (WANT_MAX - n), np.int32)
    return toks, pos, seg, want, target, n


# ---------------------------------------------------------------------------
# float32 pieces
# ---------------------------------------------------------------------------

def _split3(x):
    x1 = x.astype(BF16)
    r = x - x1.astype(F32)
    x2 = r.astype(BF16)
    x3 = (r - x2.astype(F32)).astype(BF16)
    return x1, x2, x3


def _mm(x, w, scale=None):
    """float32 ``x @ w`` for a ``w`` that is exact in bfloat16."""
    w = w.astype(BF16)
    y = sum(jnp.dot(p, w, preferred_element_type=F32) for p in _split3(x))
    return y if scale is None else y * scale


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * w.astype(F32)


def _rope(x, pos, theta):
    hd = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=F32) / hd))
    ang = pos.astype(F32)[:, None] * inv
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attend(q, k, v, pos, seg, window):
    """Causal attention within each packed sequence, ``Q_BLOCK`` query rows
    at a time against the ``window`` rows before the block's end."""
    rows, h, hd = q.shape
    kvh = k.shape[1]
    g = h // kvh

    def pad(a, val):
        return jnp.concatenate(
            [jnp.full((window,) + a.shape[1:], val, a.dtype), a])

    kp, vp, posp, segp = pad(k, 0), pad(v, 0), pad(pos, 0), pad(seg, -1)

    def block(i):
        s0 = i * Q_BLOCK
        qb = jax.lax.dynamic_slice_in_dim(q, s0, Q_BLOCK)
        qpos = jax.lax.dynamic_slice_in_dim(pos, s0, Q_BLOCK)
        qseg = jax.lax.dynamic_slice_in_dim(seg, s0, Q_BLOCK)
        # padded row r + window holds row r: rows s0+Q-window .. s0+Q-1
        kb = jax.lax.dynamic_slice_in_dim(kp, s0 + Q_BLOCK, window)
        vb = jax.lax.dynamic_slice_in_dim(vp, s0 + Q_BLOCK, window)
        kpos = jax.lax.dynamic_slice_in_dim(posp, s0 + Q_BLOCK, window)
        kseg = jax.lax.dynamic_slice_in_dim(segp, s0 + Q_BLOCK, window)
        qg = qb.reshape(Q_BLOCK, kvh, g, hd)
        s = jnp.einsum("qkgd,skd->kgqs", qg, kb,
                       precision=HIGHEST) / math.sqrt(hd)
        mask = ((kseg[None, :] == qseg[:, None]) & (qseg[:, None] >= 0)
                & (kpos[None, :] <= qpos[:, None]))
        s = jnp.where(mask, s, -1e30)
        p = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("kgqs,skd->qkgd", p, vb, precision=HIGHEST)
        return o.reshape(Q_BLOCK, h * hd)

    out = jax.lax.map(block, jnp.arange(rows // Q_BLOCK))
    return out.reshape(rows, h * hd)


# ---------------------------------------------------------------------------
# the forward pass
# ---------------------------------------------------------------------------

def _layer(x, lw, ls, pos, seg, *, n, eps, theta, window):
    d, h, kv, hd = n["d"], n["h"], n["kv"], n["hd"]
    rows = x.shape[0]
    s = ls if ls is not None else {}
    a = lw["attn"]
    xn = _rms(x, lw["ln1"], eps)
    q = _mm(xn, a["wq"].reshape(d, h * hd), s.get("wq"))
    k = _mm(xn, a["wk"].reshape(d, kv * hd), s.get("wk"))
    v = _mm(xn, a["wv"].reshape(d, kv * hd), s.get("wv"))
    if "bq" in a:
        q = q + a["bq"].reshape(-1).astype(F32)
        k = k + a["bk"].reshape(-1).astype(F32)
        v = v + a["bv"].reshape(-1).astype(F32)
    q = _rope(q.reshape(rows, h, hd), pos, theta)
    k = _rope(k.reshape(rows, kv, hd), pos, theta)
    o = _attend(q, k, v.reshape(rows, kv, hd), pos, seg, window)
    x = x + _mm(o, a["wo"].reshape(h * hd, d), s.get("wo"))

    m = lw["mlp"]
    xn = _rms(x, lw["ln2"], eps)
    blk = min(MLP_BLOCK, rows)

    def mlp(xb):
        up = jax.nn.silu(_mm(xb, m["w1"], s.get("w1"))) \
            * _mm(xb, m["w3"], s.get("w3"))
        return _mm(up, m["w2"], s.get("w2"))

    y = jax.lax.map(mlp, xn.reshape(rows // blk, blk, d))
    return x + y.reshape(rows, d)


@partial(jax.jit, static_argnames=("cfg_key", "window"))
def _forward(params, scales, toks, pos, seg, want, *, cfg_key, window):
    cj = dict(cfg_key)
    n = weights.dims(cj)
    eps, theta = float(cj["rms_norm_eps"]), float(cj["rope_theta"])
    sc = scales or {}
    x = params["embed"][toks].astype(F32)
    if "embed" in sc:
        x = x * sc["embed"][toks][:, None]
    blockp = params["pattern"]["00_attn"]
    blocks = sc.get("pattern")

    def body(x, xs):
        lw, ls = xs
        return _layer(x, lw, ls, pos, seg, n=n, eps=eps, theta=theta,
                      window=window), None

    x, _ = jax.lax.scan(body, x, (blockp, blocks))
    hidden = _rms(x[want], params["final_norm"], eps)
    if n["tied"]:
        logits = _mm(hidden, params["embed"].T, sc.get("embed"))
    else:
        logits = _mm(hidden, params["lm_head"], sc.get("lm_head"))
    return logits[:, :n["v"]]


def forward(params, cj: dict, packed, window: int, scales=None):
    """Logits (WANT_MAX, vocab) in float32 at the packed sample's wanted
    rows.  ``window`` is at least the longest sequence plus one block."""
    toks, pos, seg, want = (jnp.asarray(a) for a in packed[:4])
    key = tuple(sorted((k, v) for k, v in cj.items()
                       if not isinstance(v, (dict, list))))
    return _forward(params, scales, toks, pos, seg, want, cfg_key=key,
                    window=window)


def window_rows(max_len: int) -> int:
    return -(-max_len // Q_BLOCK) * Q_BLOCK + Q_BLOCK


# ---------------------------------------------------------------------------
# the control: float8 weights
# ---------------------------------------------------------------------------

@partial(jax.jit, static_argnames=("axis",), donate_argnums=(0,))
def _fp8(w, axis):
    amax = jnp.max(jnp.abs(w.astype(F32)), axis=axis)
    scale = jnp.maximum(amax, 1e-30) / FP8_MAX
    s = jnp.expand_dims(scale, axis)
    q = jnp.clip(w.astype(F32) / s, -FP8_MAX, FP8_MAX)
    return q.astype(jnp.float8_e4m3fn), scale


def quantize(params: dict):
    """Replace every matrix of ``params`` by float8 e4m3 with a scale per
    output column, leaf by leaf so the peak stays near the weights' size.
    Returns (params in float8, the scales tree for ``forward``)."""
    blk = params["pattern"]["00_attn"]
    scales = {"pattern": {"attn": {}, "mlp": {}}}
    for group in ("attn", "mlp"):
        for name in list(blk[group]):
            if name not in _MATRICES:
                continue
            w = blk[group][name]
            shape = w.shape
            if name == "wo":   # (L, h, hd, d): the input axis is h*hd
                w = w.reshape(shape[0], shape[1] * shape[2], shape[3])
            q, s = _fp8(w, 1)
            blk[group][name] = q.reshape(shape)
            scales["pattern"][group][name] = s.reshape(shape[0], -1)
    # embedding rows double as the tied head's output columns
    params["embed"], scales["embed"] = _fp8(params["embed"], 1)
    if "lm_head" in params:
        params["lm_head"], scales["lm_head"] = _fp8(params["lm_head"], 0)
    flat = {}
    for group in ("attn", "mlp"):
        flat.update(scales["pattern"][group])
    scales["pattern"] = flat
    return params, scales


# ---------------------------------------------------------------------------
# readings
# ---------------------------------------------------------------------------

@jax.jit
def _scores(logits, target):
    top = jnp.max(logits, -1)
    return top, jnp.take_along_axis(logits, target[:, None], -1)[:, 0], \
        jnp.argmax(logits, -1)


def token_gaps(logits, target) -> Tuple[np.ndarray, np.ndarray]:
    """Per wanted row: how far the served token's reference logit lies
    below the reference's best, and whether it is the reference's argmax."""
    top, got, arg = (np.asarray(a) for a in _scores(logits, target))
    return top - got, arg == np.asarray(target)


def control_gaps(ref_logits, ctrl_logits) -> np.ndarray:
    """Per wanted row: the gap of the token the control puts first."""
    arg = jnp.argmax(ctrl_logits, -1)
    return token_gaps(ref_logits, arg)[0]


def request_gap(g1: np.ndarray, g2: Optional[np.ndarray],
                served_by: Optional[int]) -> float:
    """The widest gap of one request.  ``served_by`` is 1 or 2 when every
    token came from that weight step, None when the request was in flight
    across the swap: then the best split into a prefix on step 1 and a
    suffix on step 2 counts (weights change only between iterations)."""
    if served_by == 2:
        return float(g2.max(initial=0.0))
    if served_by == 1 or g2 is None:
        return float(g1.max(initial=0.0))
    return min(max(float(g1[:k].max(initial=0.0)),
                   float(g2[k:].max(initial=0.0)))
               for k in range(len(g1) + 1))


def split_rows(values: np.ndarray, lengths: List[int]) -> List[np.ndarray]:
    out, r = [], 0
    for n in lengths:
        out.append(values[r:r + n])
        r += n
    return out
