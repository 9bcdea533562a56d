"""A test-only model family (``model_type`` ``toy_moe``): one leading
dense layer, then layers whose MLP is routed experts.  It exists to show
that a family with two kinds of layer, leaves Qwen2 lacks (``router``, a
stacked expert axis) and work that depends on routing joins the benchmark
by this file alone.

    per layer:  x += o(attn(rope(q(n1(x))), rope(k(n1(x))), v(n1(x))))
    dense:      x += w2(silu(w1(n2(x))) * w3(n2(x)))
    experts:    p = softmax(n2(x) @ router);  g = p on the top k, else 0
                x += sum_e g_e * w2_e(silu(w1_e(n2(x))) * w3_e(n2(x)))
    logits = lm_head(norm(x))
"""

from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp

from reference import F32, _attend, _mm, _rms, _rope

NORM_STD = 0.05
BYTES = 2

KEYMAP = {"hidden_size": "d_model", "num_attention_heads": "num_heads",
          "num_key_value_heads": "num_kv_heads", "intermediate_size": "d_ff",
          "moe_intermediate_size": "moe_d_ff",
          "n_routed_experts": "num_experts",
          "num_experts_per_tok": "experts_per_token",
          "vocab_size": "vocab_size", "rope_theta": "rope_theta",
          "rms_norm_eps": "norm_eps", "torch_dtype": "dtype"}


def arch(cj: dict, base) -> Dict[str, tuple]:
    over = {k: (KEYMAP[k], cj[k]) for k in KEYMAP if k in cj}
    over["num_hidden_layers"] = (
        "pattern_repeats",
        int(cj["num_hidden_layers"]) - int(cj["first_k_dense_replace"]))
    return over


def dims(cj: dict) -> Dict[str, int]:
    d, h = int(cj["hidden_size"]), int(cj["num_attention_heads"])
    dense = int(cj["first_k_dense_replace"])
    return {"d": d, "h": h, "kv": int(cj["num_key_value_heads"]),
            "hd": d // h, "f": int(cj["intermediate_size"]),
            "fe": int(cj["moe_intermediate_size"]),
            "experts": int(cj["n_routed_experts"]),
            "k": int(cj["num_experts_per_tok"]),
            "v": int(cj["vocab_size"]), "dense": dense,
            "moe": int(cj["num_hidden_layers"]) - dense}


def _attn_layout(L, n):
    d, h, kv, hd = n["d"], n["h"], n["kv"], n["hd"]
    return {"wq": ((L, d, h, hd), "normal", d ** -0.5),
            "wk": ((L, d, kv, hd), "normal", d ** -0.5),
            "wv": ((L, d, kv, hd), "normal", d ** -0.5),
            "wo": ((L, h, hd, d), "normal", (h * hd) ** -0.5)}


def layout(cj: dict) -> dict:
    n = dims(cj)
    d, f, fe, E, v = n["d"], n["f"], n["fe"], n["experts"], n["v"]
    L0, L1 = n["dense"], n["moe"]
    dense = {"ln1": ((L0, d), "norm", NORM_STD), "attn": _attn_layout(L0, n),
             "ln2": ((L0, d), "norm", NORM_STD),
             "mlp": {"w1": ((L0, d, f), "normal", d ** -0.5),
                     "w2": ((L0, f, d), "normal", f ** -0.5),
                     "w3": ((L0, d, f), "normal", d ** -0.5)}}
    moe = {"ln1": ((L1, d), "norm", NORM_STD), "attn": _attn_layout(L1, n),
           "ln2": ((L1, d), "norm", NORM_STD),
           "moe": {"router": ((L1, d, E), "normal", d ** -0.5),
                   "w1": ((L1, E, d, fe), "normal", d ** -0.5),
                   "w2": ((L1, E, fe, d), "normal", fe ** -0.5),
                   "w3": ((L1, E, d, fe), "normal", d ** -0.5)}}
    return {"embed": ((v, d), "normal", d ** -0.5),
            "final_norm": ((d,), "norm", NORM_STD),
            "lm_head": ((d, v), "normal", d ** -0.5),
            "pattern": {"00_dense": dense, "01_moe": moe}}


# the router stays in bfloat16 in the control
MATRICES = {"wq": (-3,), "wk": (-3,), "wv": (-3,), "wo": (-3, -2),
            "w1": (-2,), "w2": (-2,), "w3": (-2,),
            "embed": (-1,), "lm_head": (-2,)}


def _attention(x, a, sa, pos, seg, n, eps, theta, window, ln):
    d, h, kv, hd = n["d"], n["h"], n["kv"], n["hd"]
    rows = x.shape[0]
    xn = _rms(x, ln, eps)
    q = _rope(_mm(xn, a["wq"].reshape(d, h * hd), sa.get("wq"))
              .reshape(rows, h, hd), pos, theta)
    k = _rope(_mm(xn, a["wk"].reshape(d, kv * hd), sa.get("wk"))
              .reshape(rows, kv, hd), pos, theta)
    v = _mm(xn, a["wv"].reshape(d, kv * hd), sa.get("wv"))
    o = _attend(q, k, v.reshape(rows, kv, hd), pos, seg, window)
    return x + _mm(o, a["wo"].reshape(h * hd, d), sa.get("wo"))


def _mlp(x, w1, w2, w3, s1, s2, s3):
    return _mm(jax.nn.silu(_mm(x, w1, s1)) * _mm(x, w3, s3), w2, s2)


def forward(params, scales, toks, pos, seg, want, cj, window):
    n = dims(cj)
    eps, theta = float(cj["rms_norm_eps"]), float(cj["rope_theta"])
    sc = scales or {}
    blocks = sc.get("pattern", {})
    x = params["embed"][toks].astype(F32)
    if "embed" in sc:
        x = x * sc["embed"][toks][:, None]

    def dense(x, xs):
        lw, ls = xs
        s = ls or {}
        x = _attention(x, lw["attn"], s.get("attn", {}), pos, seg, n, eps,
                       theta, window, lw["ln1"])
        m, sm = lw["mlp"], s.get("mlp", {})
        return x + _mlp(_rms(x, lw["ln2"], eps), m["w1"], m["w2"], m["w3"],
                        sm.get("w1"), sm.get("w2"), sm.get("w3")), None

    def experts(x, xs):
        lw, ls = xs
        s = ls or {}
        x = _attention(x, lw["attn"], s.get("attn", {}), pos, seg, n, eps,
                       theta, window, lw["ln1"])
        m, sm = lw["moe"], s.get("moe", {})
        xn = _rms(x, lw["ln2"], eps)
        p = jax.nn.softmax(_mm(xn, m["router"]), -1)
        kth = jax.lax.top_k(p, n["k"])[0][:, -1:]
        gate = jnp.where(p >= kth, p, 0.0)
        y = 0.0
        for e in range(n["experts"]):
            part = [sm[w][e] if w in sm else None for w in ("w1", "w2", "w3")]
            y = y + gate[:, e:e + 1] * _mlp(xn, m["w1"][e], m["w2"][e],
                                            m["w3"][e], *part)
        return x + y, None

    x, _ = jax.lax.scan(dense, x, (params["pattern"]["00_dense"],
                                   blocks.get("00_dense")))
    x, _ = jax.lax.scan(experts, x, (params["pattern"]["01_moe"],
                                     blocks.get("01_moe")))
    hidden = _rms(x[want], params["final_norm"], eps)
    return _mm(hidden, params["lm_head"], sc.get("lm_head"))


def experts_reached(n: Dict[str, int], tokens: int) -> float:
    """Expected distinct experts that ``tokens`` tokens reach when each
    routes to ``k`` of them evenly: the expert weights a call reads."""
    E, k = n["experts"], n["k"]
    return E * (1.0 - (1.0 - k / E) ** tokens)


def call_work(n: Dict[str, int], call: tuple) -> Dict[str, float]:
    d, h, kv, hd, v = n["d"], n["h"], n["kv"], n["hd"], n["v"]
    L = n["dense"] + n["moe"]
    if call[1] == "prefill":
        tokens, offset, final = call[2], call[3], call[4]
        keys = tokens * offset + tokens * (tokens + 1) // 2
        rows, heads = offset + tokens, v if final else 0
    else:
        tokens, keys = call[2], call[3]
        rows, heads = keys, tokens * v
    attn = 2 * d * h * hd + 2 * d * kv * hd
    expert = 3 * d * n["fe"]
    flops = (2 * tokens * (L * attn + n["dense"] * 3 * d * n["f"]
                           + n["moe"] * (d * n["experts"]
                                         + n["k"] * expert))
             + L * 4 * h * hd * keys + 2 * heads * d)
    nbytes = BYTES * (L * attn + n["dense"] * 3 * d * n["f"]
                      + n["moe"] * (d * n["experts"]
                                    + experts_reached(n, tokens) * expert)
                      + (v * d if heads else 0)
                      + L * 2 * kv * hd * rows)
    return {"flops": float(flops), "bytes": float(nbytes)}
