"""The pieces of the comparison: a request across a weight swap, the
sample, and the reference against a plain loop."""

from types import SimpleNamespace

import numpy as np
import pytest

import compare
import reference


@pytest.mark.parametrize("served_by, want", [(1, 0.0), (2, 5.0),
                                             (None, 0.0)])
def test_a_request_may_switch_steps_once(served_by, want):
    g1 = np.array([0.0, 0.0, 0.0])   # step 1 serves every token
    g2 = np.array([0.0, 5.0, 5.0])   # step 2 only the first
    assert reference.request_gap(g1, g2, served_by) == want


def test_a_switch_back_to_the_old_step_is_not_accepted():
    g1 = np.array([4.0, 0.0, 0.0])
    g2 = np.array([0.0, 4.0, 4.0])
    assert reference.request_gap(g1, g2, None) == 4.0


def req(plen, n, by=1):
    return SimpleNamespace(prompt=[1] * plen, tokens=[2] * n, served_by=by)


def test_the_sample_holds_the_longest_and_fits_its_packs():
    done = [req(100, 10) for _ in range(50)] + [req(900, 60)]
    packs = compare.pick(done, 7, 2048)
    flat = [r for p in packs for r in p]
    assert flat[0] is done[-1]
    assert all(sum(len(r.prompt) + len(r.tokens) - 1 for r in p) <= 2048
               for p in packs)
    assert len(packs) <= compare.MAX_PACKS
    assert sum(len(r.tokens) for r in flat) >= compare.TARGET_TOKENS


def test_pack_marks_the_rows_that_predict_served_tokens():
    toks, pos, seg, want, target, n = reference.pack(
        [([5, 6, 7], [8, 9]), ([1], [2])], 16)
    assert list(toks[:6]) == [5, 6, 7, 8, 1, 0]
    assert list(pos[:5]) == [0, 1, 2, 3, 0] and seg[5] == -1
    assert n == 3 and list(want[:3]) == [2, 3, 4]
    assert list(target[:3]) == [8, 9, 2]


def test_reference_matches_a_plain_loop():
    """The blocked, packed reference equals a per-sequence loop written
    with plain float32 matrix products."""
    import jax

    import weights

    cj = {"hidden_size": 64, "num_attention_heads": 4,
          "num_key_value_heads": 2, "intermediate_size": 96,
          "vocab_size": 300, "num_hidden_layers": 2,
          "tie_word_embeddings": True, "rope_theta": 10000.0,
          "rms_norm_eps": 1e-6, "torch_dtype": "bfloat16", "qkv_bias": True}
    params = weights.make(cj, 3)
    seqs = [([3, 4, 5, 6, 7], [8, 9]), ([10, 11], [12, 13, 14])]
    packed = reference.pack(seqs, 512)
    got = np.asarray(reference.forward(params, cj, packed, 256))[:packed[5]]

    p = jax.tree.map(lambda a: np.asarray(a, np.float32), params)
    blk = p["pattern"]["00_attn"]

    def rms(x, w):
        return x / np.sqrt((x * x).mean(-1, keepdims=True) + 1e-6) * w

    def rope(x, pos):
        hd = x.shape[-1]
        inv = 1.0 / 10000.0 ** (np.arange(0, hd, 2) / hd)
        a = pos[:, None] * inv
        c, s = np.cos(a)[:, None], np.sin(a)[:, None]
        x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
        return np.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], -1)

    rows = []
    for prompt, served in seqs:
        t = np.array(prompt + served[:-1])
        x = p["embed"][t]
        pos = np.arange(len(t))
        for layer in range(2):
            a = {k: v[layer] for k, v in blk["attn"].items()}
            xn = rms(x, blk["ln1"][layer])
            q = rope(np.einsum("sd,dhk->shk", xn, a["wq"]) + a["bq"], pos)
            k = rope(np.einsum("sd,dhk->shk", xn, a["wk"]) + a["bk"], pos)
            v = np.einsum("sd,dhk->shk", xn, a["wv"]) + a["bv"]
            k, v = np.repeat(k, 2, 1), np.repeat(v, 2, 1)
            s = np.einsum("qhk,shk->hqs", q, k) / np.sqrt(16)
            s = np.where(np.tril(np.ones((len(t),) * 2, bool)), s, -np.inf)
            w = np.exp(s - s.max(-1, keepdims=True))
            w /= w.sum(-1, keepdims=True)
            o = np.einsum("hqs,shk->qhk", w, v)
            x = x + np.einsum("qhk,hkd->qd", o, a["wo"])
            m = {k: v[layer] for k, v in blk["mlp"].items()}
            xn = rms(x, blk["ln2"][layer])
            h = xn @ m["w1"]
            x = x + (h / (1 + np.exp(-h)) * (xn @ m["w3"])) @ m["w2"]
        logits = rms(x, p["final_norm"]) @ p["embed"][:300].T
        rows.extend(logits[len(prompt) - 1 + i] for i in range(len(served)))
    np.testing.assert_allclose(got, np.stack(rows), rtol=2e-4, atol=2e-4)
