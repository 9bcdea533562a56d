"""Block-level numerical validation against oracles."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models import get_config
from repro.models.moe import moe_defs, moe_ffn, moe_reference
from repro.models.params import initialize
from repro.models.ssm import ssd_chunked, ssd_reference
from repro.models.xlstm import (mlstm_chunked, mlstm_decode_step,
                                mlstm_reference)


@pytest.mark.parametrize("chunk", [8, 16, 64])
def test_mlstm_chunked_matches_sequential(chunk):
    B, S, H, P = 2, 64, 3, 8
    ks = jax.random.split(jax.random.key(0), 5)
    q = jax.random.normal(ks[0], (B, S, H, P))
    k = jax.random.normal(ks[1], (B, S, H, P))
    v = jax.random.normal(ks[2], (B, S, H, P))
    ir = jax.random.normal(ks[3], (B, S, H)) * 2
    fr = jax.random.normal(ks[4], (B, S, H)) * 2 + 1
    out, _ = mlstm_chunked(q, k, v, ir, fr, chunk)
    ref = mlstm_reference(q, k, v, ir, fr)
    np.testing.assert_allclose(out, ref, rtol=2e-4, atol=2e-4)


def test_mlstm_state_handoff_prefill_to_decode():
    """Chunked prefill state continues exactly into single-token steps."""
    B, S, H, P = 1, 64, 2, 8
    ks = jax.random.split(jax.random.key(1), 5)
    q = jax.random.normal(ks[0], (B, S, H, P))
    k = jax.random.normal(ks[1], (B, S, H, P))
    v = jax.random.normal(ks[2], (B, S, H, P))
    ir = jax.random.normal(ks[3], (B, S, H))
    fr = jax.random.normal(ks[4], (B, S, H)) + 1
    ref = mlstm_reference(q, k, v, ir, fr)
    out1, st = mlstm_chunked(q[:, :48], k[:, :48], v[:, :48],
                             ir[:, :48], fr[:, :48], 16)
    outs = [out1]
    c, n, m = st
    for t in range(48, 64):
        o, (c, n, m) = mlstm_chunked(q[:, t:t+1], k[:, t:t+1], v[:, t:t+1],
                                     ir[:, t:t+1], fr[:, t:t+1], 1, (c, n, m))
        outs.append(o)
    np.testing.assert_allclose(jnp.concatenate(outs, axis=1), ref,
                               rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("chunk", [8, 64])
def test_ssd_chunked_matches_quadratic(chunk):
    B, S, H, P, N = 2, 64, 3, 8, 8
    ks = jax.random.split(jax.random.key(2), 5)
    x = jax.random.normal(ks[0], (B, S, H, P))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, S, H)))
    a = -jnp.exp(jax.random.normal(ks[2], (H,)))
    bm = jax.random.normal(ks[3], (B, S, N))
    cm = jax.random.normal(ks[4], (B, S, N))
    y, _ = ssd_chunked(x, dt, a, bm, cm, chunk)
    ref = ssd_reference(x, dt, a, bm, cm)
    np.testing.assert_allclose(y, ref, rtol=2e-3, atol=2e-3)


def test_moe_exact_at_high_capacity():
    """Gather-dispatch MoE == dense-masked oracle when nothing overflows."""
    cfg = get_config("kimi-k2-1t-a32b").reduced(capacity_factor=8.0)
    params = initialize(jax.random.key(3), moe_defs(cfg))
    x = jax.random.normal(jax.random.key(4), (2, 16, cfg.d_model))
    out, aux = moe_ffn(params, x, cfg)
    ref = moe_reference(params, x, cfg)
    np.testing.assert_allclose(out, ref, rtol=2e-4, atol=2e-4)
    assert 0.5 < float(aux) < 4.0  # aux loss near 1 for near-uniform routing


def test_moe_grouped_dispatch_matches_reference():
    """Per-group (EP-aligned) dispatch == dense oracle at high capacity."""
    import dataclasses

    cfg = get_config("kimi-k2-1t-a32b").reduced(capacity_factor=8.0)
    cfg_g = dataclasses.replace(cfg, moe_dispatch_groups=4)
    params = initialize(jax.random.key(3), moe_defs(cfg))
    x = jax.random.normal(jax.random.key(4), (2, 16, cfg.d_model))
    ref = moe_reference(params, x, cfg)
    out, aux = moe_ffn(params, x, cfg_g)
    np.testing.assert_allclose(out, ref, rtol=2e-4, atol=2e-4)
    out0, aux0 = moe_ffn(params, x, cfg)
    np.testing.assert_allclose(float(aux), float(aux0), rtol=1e-5)


def test_kv_cache_int8_roundtrip():
    from repro.models.layers import kv_dequantize, kv_quantize

    x = jax.random.normal(jax.random.key(0), (2, 7, 3, 16)) * 5.0
    q, s = kv_quantize(x)
    assert q.dtype == jnp.int8 and s.shape == (2, 7, 3)
    back = kv_dequantize(q, s, jnp.float32)
    np.testing.assert_allclose(back, x, atol=float(jnp.abs(x).max()) / 100)


def test_moe_capacity_drop_is_bounded():
    """At cf=1.0 some tokens drop, but output stays finite and close-ish."""
    cfg = get_config("kimi-k2-1t-a32b").reduced(capacity_factor=1.0)
    params = initialize(jax.random.key(5), moe_defs(cfg))
    x = jax.random.normal(jax.random.key(6), (2, 32, cfg.d_model))
    out, _ = moe_ffn(params, x, cfg)
    assert bool(jnp.isfinite(out).all())


# one case per block kind the decode step serves, and the int8 cache:
# case -> (architecture, overrides of its reduced configuration, atol
# against the float32 forward pass, which keeps K/V unrounded)
DECODE_CASES = {
    "attn": ("qwen2-0.5b", {}, 2e-2),
    "local": ("gemma2-9b", {}, 2e-2),
    "moe": ("kimi-k2-1t-a32b", {"capacity_factor": 8.0}, 2e-2),
    "shared_attn-mamba2": ("zamba2-7b", {}, 2e-2),
    "cross": ("llama-3.2-vision-11b", {}, 2e-2),
    "mlstm-slstm": ("xlstm-350m", {}, 2e-2),
    "int8": ("qwen2-0.5b", {"kv_cache_dtype": "int8"}, 0.3),
}


@pytest.mark.parametrize("case", sorted(DECODE_CASES))
def test_decode_rows_write_in_place_and_match_forward(case):
    """Per-row decode steps over a slot state, one slot at the ``max_len``
    free-slot sentinel: each live row's logits match the forward pass over
    its whole sequence, each step writes exactly one cache row per live
    slot in every layer and none of the sentinel's, and a scalar-position
    step over the slot alone gives the same logits and state."""
    from repro.models import Model
    from repro.models.params import is_def

    arch, over, atol = DECODE_CASES[case]
    cfg = get_config(arch).reduced(**over)
    model = Model(cfg)
    params = model.init_params(jax.random.key(0))
    M, T, starts = 24, 4, (18, 11)  # gemma2's window (16) lies inside
    seqs = [jax.random.randint(jax.random.key(1 + r), (1, p + T), 0,
                               cfg.vocab_size) for r, p in enumerate(starts)]
    enc = cfg.encoder_seq or cfg.vision_seq
    fronts = [jax.random.normal(jax.random.key(10 + r),
                                (1, enc, cfg.d_model)) if enc else None
              for r in range(len(starts))]
    forward = jax.jit(model.forward)
    prefill = jax.jit(model.prefill, static_argnums=2)
    want = [forward(params, s, f)[0][0] for s, f in zip(seqs, fronts)]
    slots = [prefill(params, s[:, :p], M, f)[1]
             for s, p, f in zip(seqs, starts, fronts)]
    slots.append(model.init_decode_state(1, M))   # the free slot

    def stack(tree_list):
        out = {"pattern": jax.tree.map(lambda *l: jnp.concatenate(l, 1),
                                       *[t["pattern"] for t in tree_list])}
        if "tail" in tree_list[0]:
            out["tail"] = jax.tree.map(lambda *l: jnp.concatenate(l, 0),
                                       *[t["tail"] for t in tree_list])
        return out

    defs = jax.tree.leaves(model.decode_state_defs(len(slots), M),
                           is_leaf=is_def)
    step = jax.jit(model.decode_step)
    state, singles = stack(slots), slots[:-1]
    for i in range(T):
        pos = [p + i for p in starts] + [M]
        toks = jnp.array([[int(s[0, p])] for s, p in zip(seqs, pos)] + [[0]],
                         jnp.int32)
        logits, new = step(params, state, toks, jnp.array(pos, jnp.int32))
        for r in range(len(starts)):
            np.testing.assert_allclose(
                np.asarray(logits[r, 0, :cfg.vocab_size]),
                np.asarray(want[r][pos[r], :cfg.vocab_size]),
                rtol=2e-2, atol=atol)
            one, singles[r] = step(params, singles[r], toks[r:r + 1],
                                   jnp.int32(pos[r]))
            np.testing.assert_allclose(np.asarray(one[0]),
                                       np.asarray(logits[r]),
                                       rtol=1e-4, atol=1e-4)
        for d, old, cur in zip(defs, jax.tree.leaves(state),
                               jax.tree.leaves(new)):
            changed = np.asarray(old != cur)
            if "frames" in d.axes:            # cross K/V: never written
                assert not changed.any()
            if "cache_seq" not in d.axes:     # recurrent state: every slot
                continue
            keep = [d.axes.index(a)
                    for a in ("layers", "cache_batch", "cache_seq")
                    if a in d.axes]
            rows = changed.any(axis=tuple(a for a in range(changed.ndim)
                                          if a not in keep))
            expect = np.zeros_like(rows)
            for r in range(len(starts)):
                expect[..., r, pos[r]] = True
            np.testing.assert_array_equal(rows, expect)
        state = new
    for r, single in enumerate(singles):
        mine = {"pattern": jax.tree.map(lambda l: l[:, r:r + 1],
                                        state["pattern"])}
        if "tail" in state:
            mine["tail"] = jax.tree.map(lambda l: l[r:r + 1], state["tail"])
        for got, one in zip(jax.tree.leaves(single), jax.tree.leaves(mine)):
            np.testing.assert_allclose(np.asarray(got, np.float32),
                                       np.asarray(one, np.float32),
                                       rtol=1e-4, atol=1e-4)
