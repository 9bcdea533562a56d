"""The Qwen2 family (``model_type`` ``qwen2``): dense GQA attention with a
bias on q, k and v, a gated SiLU MLP, RMSNorm and rope; Qwen1.5 is the
same architecture.  Everything the benchmark knows of the family is here:
how its configuration maps onto the program's, its weight tree, its plain
reference and the work of each call.

The reference is the Qwen2 forward pass in float32, written from the
published architecture, with no cache, no batching and no code of the
program:

    x = embed[tokens]
    per layer:  x += o(attn(rope(q(n1(x)) + bq), rope(k(n1(x)) + bk),
                             v(n1(x)) + bv))       # causal, GQA
                x += w2(silu(w1(n2(x))) * w3(n2(x)))
    logits = head(norm(x))                          # head = embed^T if tied

RMSNorm is ``w * x / sqrt(mean(x^2) + eps)``; rope rotates the two halves
of each head (``rotate_half``) with frequencies ``theta^(-2i/hd)``.  It
runs layer by layer (a scan over the stacked weights), in blocks of query
rows for attention and of token rows for the MLP.

The work counts are those the algorithm needs (``work.py``): weights and
cache entries at 2 bytes (bfloat16); a matrix product of m x k by k x n
counts 2mkn operations; norms, rope, softmax and biases are left out as
small.
"""

from __future__ import annotations

from typing import Dict

import jax

from reference import F32, MLP_BLOCK, _attend, _mm, _rms, _rope

BIAS_STD = 0.2
NORM_STD = 0.05
BYTES = 2


# ---------------------------------------------------------------------------
# the program's configuration, from the configuration file
# ---------------------------------------------------------------------------

KEYMAP = {"hidden_size": "d_model", "num_attention_heads": "num_heads",
          "num_key_value_heads": "num_kv_heads", "head_dim": "head_dim",
          "intermediate_size": "d_ff", "vocab_size": "vocab_size",
          "num_hidden_layers": "pattern_repeats",
          "tie_word_embeddings": "tie_embeddings", "rope_theta": "rope_theta",
          "rms_norm_eps": "norm_eps", "torch_dtype": "dtype",
          "qkv_bias": "qkv_bias"}


def arch(cj: dict, base) -> Dict[str, tuple]:
    """``{config key: (ArchConfig field, value)}`` for the registered
    ``base``, which has to be a plain attention stack."""
    from repro.models.config import ATTN

    if base.pattern != (ATTN,) or base.tail:
        raise ValueError(f"{base.name}: only a plain attention stack maps "
                         f"onto num_hidden_layers")
    return {k: (KEYMAP[k], cj[k]) for k in KEYMAP if k in cj}


# ---------------------------------------------------------------------------
# the weight tree
# ---------------------------------------------------------------------------

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


# matrices quantized in the control, by leaf name, with their input axes:
# each output column has its own scale.  Embedding rows double as the tied
# head's output columns.
MATRICES = {"wq": (-3,), "wk": (-3,), "wv": (-3,), "wo": (-3, -2),
            "w1": (-2,), "w2": (-2,), "w3": (-2,),
            "embed": (-1,), "lm_head": (-2,)}


# ---------------------------------------------------------------------------
# the forward pass
# ---------------------------------------------------------------------------

def _layer(x, lw, ls, pos, seg, *, n, eps, theta, window):
    d, h, kv, hd = n["d"], n["h"], n["kv"], n["hd"]
    rows = x.shape[0]
    s = ls if ls is not None else {}
    a, sa = lw["attn"], s.get("attn", {})
    xn = _rms(x, lw["ln1"], eps)
    q = _mm(xn, a["wq"].reshape(d, h * hd), sa.get("wq"))
    k = _mm(xn, a["wk"].reshape(d, kv * hd), sa.get("wk"))
    v = _mm(xn, a["wv"].reshape(d, kv * hd), sa.get("wv"))
    if "bq" in a:
        q = q + a["bq"].reshape(-1).astype(F32)
        k = k + a["bk"].reshape(-1).astype(F32)
        v = v + a["bv"].reshape(-1).astype(F32)
    q = _rope(q.reshape(rows, h, hd), pos, theta)
    k = _rope(k.reshape(rows, kv, hd), pos, theta)
    o = _attend(q, k, v.reshape(rows, kv, hd), pos, seg, window)
    x = x + _mm(o, a["wo"].reshape(h * hd, d), sa.get("wo"))

    m, sm = lw["mlp"], s.get("mlp", {})
    xn = _rms(x, lw["ln2"], eps)
    blk = min(MLP_BLOCK, rows)

    def mlp(xb):
        up = jax.nn.silu(_mm(xb, m["w1"], sm.get("w1"))) \
            * _mm(xb, m["w3"], sm.get("w3"))
        return _mm(up, m["w2"], sm.get("w2"))

    y = jax.lax.map(mlp, xn.reshape(rows // blk, blk, d))
    return x + y.reshape(rows, d)


def forward(params, scales, toks, pos, seg, want, cj, window):
    """float32 logits at the ``want`` rows of the packed tokens."""
    n = dims(cj)
    eps, theta = float(cj["rms_norm_eps"]), float(cj["rope_theta"])
    sc = scales or {}
    x = params["embed"][toks].astype(F32)
    if "embed" in sc:
        x = x * sc["embed"][toks][:, None]
    blockp = params["pattern"]["00_attn"]
    blocks = sc.get("pattern", {}).get("00_attn")

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


# ---------------------------------------------------------------------------
# the work of each call
# ---------------------------------------------------------------------------

def layer_matmul_params(n: Dict[str, int]) -> int:
    """Weights a token multiplies by in one layer: q, k, v, o and the gated
    MLP's three matrices."""
    d, h, kv, hd, f = n["d"], n["h"], n["kv"], n["hd"], n["f"]
    return d * h * hd + 2 * d * kv * hd + h * hd * d + 3 * d * f


def prefill(n: Dict[str, int], tokens: int, offset: int,
            final: bool) -> Dict[str, float]:
    """One prefill chunk of ``tokens`` real prompt tokens at positions
    ``offset .. offset+tokens-1``; ``final`` chunks produce one logits row."""
    L, d, h, kv, hd, v = (n["layers"], n["d"], n["h"], n["kv"], n["hd"],
                          n["v"])
    p = layer_matmul_params(n)
    keys = tokens * offset + tokens * (tokens + 1) // 2  # causal pairs
    flops = L * (2 * tokens * p + 4 * h * hd * keys)
    head = v * d if final else 0
    flops += 2 * head
    kv_rows = offset + tokens  # prefix read, chunk written
    nbytes = BYTES * (L * p + head + tokens * d
                      + L * 2 * kv * hd * kv_rows)
    return {"flops": float(flops), "bytes": float(nbytes)}


def decode(n: Dict[str, int], live: int, context_rows: int
           ) -> Dict[str, float]:
    """One decode step over ``live`` slots whose contexts, the new token
    included, hold ``context_rows`` cache rows in all."""
    L, d, h, kv, hd, v = (n["layers"], n["d"], n["h"], n["kv"], n["hd"],
                          n["v"])
    p = layer_matmul_params(n)
    flops = L * (2 * live * p + 4 * h * hd * context_rows) + 2 * live * v * d
    nbytes = BYTES * (L * p + v * d + live * d
                      + L * 2 * kv * hd * context_rows)
    return {"flops": float(flops), "bytes": float(nbytes)}


def call_work(n: Dict[str, int], call: tuple) -> Dict[str, float]:
    """Work of one recorded call: ``(t, "prefill", tokens, offset, final)``
    or ``(t, "decode", live, context_rows)``."""
    if call[1] == "prefill":
        return prefill(n, call[2], call[3], call[4])
    return decode(n, call[2], call[3])
