"""Model assembly: any ``ArchConfig`` → parameter specs + three lowerable
entry points (train forward/loss, prefill, decode step).

The layer stack is ``pattern × pattern_repeats`` followed by ``tail``.  The
repeated pattern is executed with one ``instrumented_scan`` over stacked
parameters (HLO size O(|pattern|), roofline-correctable trip counts); tail
blocks are unrolled.  Every block kind provides three modes:

  * ``seq``      — full-sequence forward (training),
  * ``prefill``  — full-sequence forward that also emits the decode state,
  * ``decode``   — new tokens over the decode state (a decode step, or a
                   prefill chunk for continuous batching).

Scan bodies take all tensors through carry/xs (no tracer closures — required
by the roofline tool, see ``models/scan.py``): shared zamba2 weights, encoder
context, the MoE aux-loss accumulator and the decode position ride the carry.
The decode state rides the carry too, whole: each layer writes its rows in
place at its own index, so the state is never copied per layer.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from . import ssm, xlstm
from .config import (
    ATTN, CROSS, DENSE, LOCAL, MAMBA2, MLSTM, MOE, SHARED_ATTN, SLSTM,
    ArchConfig,
)
from .layers import (
    attention_defs, cached_attention, mlp, mlp_defs, multi_head_attention,
    prefill_kv, rmsnorm, rmsnorm_def,
)
from .moe import moe_defs, moe_ffn
from .params import ParamDef, abstract, axes_tree, initialize, is_def, specs
from .scan import instrumented_scan
from .sharding import AX0, Ax, constrain

PyTree = Any


def padded_vocab(cfg: ArchConfig) -> int:
    """Vocab padded to a multiple of 256 so embedding/logit tables shard over
    any model-axis size ≤ 256 (Megatron-style vocab padding)."""
    return -(-cfg.vocab_size // 256) * 256


# ---------------------------------------------------------------------------
# per-block parameter definitions
# ---------------------------------------------------------------------------

def _block_defs(kind: str, cfg: ArchConfig) -> Dict[str, PyTree]:
    d, dt = cfg.d_model, cfg.dtype
    ln = lambda: rmsnorm_def(d, dt)  # noqa: E731
    if kind in (ATTN, LOCAL, DENSE):
        defs = {"ln1": ln(), "attn": attention_defs(cfg), "ln2": ln(),
                "mlp": mlp_defs(cfg)}
        if cfg.post_block_norm:
            defs["post1"] = ln()
            defs["post2"] = ln()
        return defs
    if kind == MOE:
        return {"ln1": ln(), "attn": attention_defs(cfg), "ln2": ln(),
                "moe": moe_defs(cfg)}
    if kind == MAMBA2:
        return {"ln1": ln(), "mamba": ssm.mamba2_defs(cfg)}
    if kind == SLSTM:
        return {"ln1": ln(), "slstm": xlstm.slstm_defs(cfg)}
    if kind == MLSTM:
        return {"ln1": ln(), "mlstm": xlstm.mlstm_defs(cfg)}
    if kind == SHARED_ATTN:
        # weights live in the shared tree; per-application norms only
        return {"ln1": ln(), "ln2": ln()}
    if kind == CROSS:
        return {"ln1": ln(), "attn": attention_defs(cfg), "lnx": ln(),
                "xattn": attention_defs(cfg, cross=True), "ln2": ln(),
                "mlp": mlp_defs(cfg)}
    raise ValueError(f"unknown block kind {kind!r}")


def _stack_defs(tree: PyTree, n: int) -> PyTree:
    return jax.tree.map(
        lambda p: ParamDef((n,) + p.shape, ("layers",) + p.axes, p.dtype,
                           p.init, p.scale),
        tree,
        is_leaf=is_def,
    )


def _pattern_names(pattern) -> Tuple[str, ...]:
    return tuple(f"{i:02d}_{kind}" for i, kind in enumerate(pattern))


# ---------------------------------------------------------------------------
# decode-state definitions (zeros)
# ---------------------------------------------------------------------------

def _block_state_defs(kind: str, cfg: ArchConfig, batch: int, max_len: int):
    """Zero decode state of one block.  Attention caches are head-major,
    (B, KV, rows, hd), the layout the attention dots read."""
    hd = cfg.resolved_head_dim
    kv = cfg.num_kv_heads
    axes = ("cache_batch", "kv_heads", "cache_seq", "head_dim")

    def cache(rows, dtype, axes=axes):
        return ParamDef((batch, kv, rows, hd), axes, dtype, init="zeros")

    if kind in (ATTN, LOCAL, DENSE, MOE, SHARED_ATTN):
        kdt = cfg.kv_cache_dtype
        out = {"k": cache(max_len, kdt), "v": cache(max_len, kdt)}
        if kdt == "int8":
            for name in ("ks", "vs"):
                out[name] = ParamDef((batch, kv, max_len), axes[:3],
                                     "float32", init="zeros")
        return out
    if kind == CROSS:
        enc = cfg.encoder_seq or cfg.vision_seq
        frames = ("cache_batch", "kv_heads", "frames", "head_dim")
        return {"k": cache(max_len, cfg.dtype), "v": cache(max_len, cfg.dtype),
                "ck": cache(enc, cfg.dtype, frames),
                "cv": cache(enc, cfg.dtype, frames)}
    if kind == MAMBA2:
        di, n, h, p = (cfg.ssm_d_inner, cfg.ssm_state, cfg.ssm_heads,
                       cfg.ssm_head_dim)
        return {
            "conv": ParamDef((batch, cfg.ssm_conv - 1, di + 2 * n),
                             ("cache_batch", None, "mlp"), cfg.dtype,
                             init="zeros"),
            "ssm": ParamDef((batch, h, p, n),
                            ("cache_batch", "ssm_heads", None, None),
                            "float32", init="zeros"),
        }
    if kind == MLSTM:
        di = 2 * cfg.d_model
        h = cfg.num_heads
        p = di // h
        return {
            "c": ParamDef((batch, h, p, p), ("cache_batch", "heads", None, None),
                          "float32", init="zeros"),
            "n": ParamDef((batch, h, p), ("cache_batch", "heads", None),
                          "float32", init="zeros"),
            "m": ParamDef((batch, h), ("cache_batch", "heads"),
                          "float32", init="neg_inf"),
        }
    if kind == SLSTM:
        h = cfg.num_heads
        p = cfg.d_model // h
        leaf = lambda init: ParamDef(  # noqa: E731
            (batch, h, p), ("cache_batch", "heads", None), "float32", init=init)
        return {"c": leaf("zeros"), "n": leaf("zeros"), "h": leaf("zeros"),
                "m": leaf("neg_inf")}
    raise ValueError(kind)


def init_state_leaf(d: ParamDef) -> jax.Array:
    if d.init == "neg_inf":
        return jnp.full(d.shape, -jnp.inf, jnp.dtype(d.dtype))
    return jnp.zeros(d.shape, jnp.dtype(d.dtype))


# ---------------------------------------------------------------------------
# block application
# ---------------------------------------------------------------------------

@dataclass
class Ctx:
    """Non-parameter context threaded through scan carries."""
    shared: Optional[Dict] = None      # zamba2 shared attn+mlp weights
    enc: Optional[jax.Array] = None    # encoder / vision context (B, T, d)
    position: Optional[jax.Array] = None  # decode position: scalar or (B,)
    layer: Optional[jax.Array] = None  # pattern layer of a stacked state


def _attn_mlp_seq(bp, x, cfg, *, window=0, moe_block=False, ctx: Ctx,
                  shared=False):
    eps = cfg.norm_eps
    ap = ctx.shared["attn"] if shared else bp["attn"]
    h = multi_head_attention(ap, rmsnorm(x, bp["ln1"], eps), cfg,
                             causal=True, window=window)
    if cfg.post_block_norm:
        h = rmsnorm(h, bp["post1"], eps)
    x = x + h
    aux = jnp.float32(0)
    if moe_block:
        h, aux = moe_ffn(bp["moe"], rmsnorm(x, bp["ln2"], eps), cfg)
    else:
        mp = ctx.shared["mlp"] if shared else bp["mlp"]
        h = mlp(mp, rmsnorm(x, bp["ln2"], eps), cfg.act)
    if cfg.post_block_norm:
        h = rmsnorm(h, bp["post2"], eps)
    return x + h, aux


def block_seq(kind: str, bp, x, cfg: ArchConfig, ctx: Ctx):
    """Full-sequence block application.  Returns (x, aux_loss)."""
    eps = cfg.norm_eps
    if kind in (ATTN, DENSE):
        return _attn_mlp_seq(bp, x, cfg, ctx=ctx)
    if kind == LOCAL:
        return _attn_mlp_seq(bp, x, cfg, window=cfg.sliding_window, ctx=ctx)
    if kind == MOE:
        return _attn_mlp_seq(bp, x, cfg, moe_block=True, ctx=ctx)
    if kind == SHARED_ATTN:
        return _attn_mlp_seq(bp, x, cfg, ctx=ctx, shared=True)
    if kind == MAMBA2:
        return x + ssm.mamba2_forward(bp["mamba"], rmsnorm(x, bp["ln1"], eps),
                                      cfg), jnp.float32(0)
    if kind == SLSTM:
        return x + xlstm.slstm_forward(bp["slstm"], rmsnorm(x, bp["ln1"], eps),
                                       cfg), jnp.float32(0)
    if kind == MLSTM:
        return x + xlstm.mlstm_forward(bp["mlstm"], rmsnorm(x, bp["ln1"], eps),
                                       cfg), jnp.float32(0)
    if kind == CROSS:
        x = x + multi_head_attention(bp["attn"], rmsnorm(x, bp["ln1"], eps),
                                     cfg, causal=True)
        x = x + multi_head_attention(bp["xattn"], rmsnorm(x, bp["lnx"], eps),
                                     cfg, causal=False, xkv=ctx.enc)
        return x + mlp(bp["mlp"], rmsnorm(x, bp["ln2"], eps), cfg.act), \
            jnp.float32(0)
    raise ValueError(kind)


def block_prefill(kind: str, bp, x, cfg: ArchConfig, ctx: Ctx, max_len: int):
    """Sequence forward + decode-state construction.  Returns (x, state, aux)."""
    eps = cfg.norm_eps
    if kind in (ATTN, LOCAL, DENSE, MOE, SHARED_ATTN):
        ap = ctx.shared["attn"] if kind == SHARED_ATTN else bp["attn"]
        xin = rmsnorm(x, bp["ln1"], eps)
        k, v = prefill_kv(ap, xin, cfg, max_len)
        y, aux = block_seq(kind, bp, x, cfg, ctx)
        if cfg.kv_cache_dtype == "int8":
            from .layers import kv_quantize

            k8, ks = kv_quantize(k)
            v8, vs = kv_quantize(v)
            return y, {"k": k8, "v": v8, "ks": ks, "vs": vs}, aux
        return y, {"k": k, "v": v}, aux
    if kind == CROSS:
        xin = rmsnorm(x, bp["ln1"], eps)
        k, v = prefill_kv(bp["attn"], xin, cfg, max_len)
        enc = ctx.enc
        ck = jnp.einsum("bsd,dhk->bhsk", enc, bp["xattn"]["wk"])
        cv = jnp.einsum("bsd,dhk->bhsk", enc, bp["xattn"]["wv"])
        y, aux = block_seq(kind, bp, x, cfg, ctx)
        return y, {"k": k, "v": v, "ck": ck.astype(k.dtype),
                   "cv": cv.astype(v.dtype)}, aux
    if kind == MAMBA2:
        xin = rmsnorm(x, bp["ln1"], eps)
        y, state = ssm.mamba2_sequence(bp["mamba"], xin, cfg, init_state=None)
        # conv tail: the last K−1 post-activation conv inputs
        di, n = cfg.ssm_d_inner, cfg.ssm_state
        proj = jnp.einsum("bsd,de->bse", xin, bp["mamba"]["in_proj"])
        xbc = proj[..., di:2 * di + 2 * n]
        km1 = cfg.ssm_conv - 1
        conv = xbc[:, -km1:, :]
        pad = km1 - conv.shape[1]
        if pad > 0:
            conv = jnp.pad(conv, ((0, 0), (pad, 0), (0, 0)))
        return x + y, {"conv": conv.astype(jnp.dtype(cfg.dtype)),
                       "ssm": state}, jnp.float32(0)
    if kind == MLSTM:
        xin = rmsnorm(x, bp["ln1"], eps)
        y, (c, nn, m) = xlstm.mlstm_sequence(bp["mlstm"], xin, cfg, state=None)
        return x + y, {"c": c, "n": nn, "m": m}, jnp.float32(0)
    if kind == SLSTM:
        xin = rmsnorm(x, bp["ln1"], eps)
        y, (c, nn, hh, m) = xlstm.slstm_sequence(bp["slstm"], xin, cfg,
                                                 state=None)
        return x + y, {"c": c, "n": nn, "h": hh, "m": m}, jnp.float32(0)
    raise ValueError(kind)


def _layer_state(st, layer):
    """A block's state for one layer: the slice at ``layer`` of a stacked
    pattern state, or a tail block's own state (``layer`` None)."""
    return st if layer is None else jax.tree.map(lambda l: l[layer], st)


def _put_layer_state(st, new, layer):
    """Write one layer's new state back at ``layer`` of the stacked state."""
    if layer is None:
        return new
    return jax.tree.map(
        lambda l, n: jax.lax.dynamic_update_index_in_dim(
            l, n.astype(l.dtype), layer, 0), st, new)


def _self_attention(ap, xin, st, cfg: ArchConfig, ctx: Ctx, window=0):
    h, k, v, ks, vs = cached_attention(
        ap, xin, st["k"], st["v"], ctx.position, cfg, layer=ctx.layer,
        window=window, k_scale=st.get("ks"), v_scale=st.get("vs"))
    new_st = {**st, "k": k, "v": v}
    if ks is not None:
        new_st["ks"], new_st["vs"] = ks, vs
    return h, new_st


# block kinds whose decode state can be built incrementally, chunk by chunk,
# into a pre-allocated cache.  Recurrent kinds (mamba2/xlstm) carry conv/
# hidden tails that this path does not stitch across chunk boundaries.
CHUNKABLE_KINDS = (ATTN, LOCAL, DENSE, MOE, SHARED_ATTN)


def block_decode(kind: str, bp, x, st, cfg: ArchConfig, ctx: Ctx):
    """New tokens over a block's decode state.  x: (B, C, d), C == 1 unless
    the kind is in CHUNKABLE_KINDS (a prefill chunk); ``ctx.position`` is the
    first token's position.  ``st`` is the block's whole stacked state when
    ``ctx.layer`` is set: the block writes its layer's state in place and
    returns the stack.  Returns (x, new_state)."""
    eps = cfg.norm_eps
    if kind in CHUNKABLE_KINDS:
        ap = ctx.shared["attn"] if kind == SHARED_ATTN else bp["attn"]
        window = cfg.sliding_window if kind == LOCAL else 0
        h, st = _self_attention(ap, rmsnorm(x, bp["ln1"], eps), st, cfg, ctx,
                                window)
        if cfg.post_block_norm:
            h = rmsnorm(h, bp["post1"], eps)
        x = x + h
        if kind == MOE:
            h, _ = moe_ffn(bp["moe"], rmsnorm(x, bp["ln2"], eps), cfg)
        else:
            mp = ctx.shared["mlp"] if kind == SHARED_ATTN else bp["mlp"]
            h = mlp(mp, rmsnorm(x, bp["ln2"], eps), cfg.act)
        if cfg.post_block_norm:
            h = rmsnorm(h, bp["post2"], eps)
        return x + h, st
    if kind == CROSS:
        h, st = _self_attention(bp["attn"], rmsnorm(x, bp["ln1"], eps), st,
                                cfg, ctx)
        x = x + h
        h, _, _, _, _ = cached_attention(
            bp["xattn"], rmsnorm(x, bp["lnx"], eps), st["ck"], st["cv"],
            ctx.position, cfg, layer=ctx.layer, cross=True)
        x = x + h
        return x + mlp(bp["mlp"], rmsnorm(x, bp["ln2"], eps), cfg.act), st
    cur = _layer_state(st, ctx.layer)
    xin = rmsnorm(x, bp["ln1"], eps)
    if kind == MAMBA2:
        y, conv, ssm_st = ssm.mamba2_decode_step(
            bp["mamba"], xin, cur["conv"], cur["ssm"], cfg)
        new = {"conv": conv, "ssm": ssm_st}
    elif kind == MLSTM:
        y, (c, nn, m) = xlstm.mlstm_decode_step(
            bp["mlstm"], xin, (cur["c"], cur["n"], cur["m"]), cfg)
        new = {"c": c, "n": nn, "m": m}
    elif kind == SLSTM:
        y, (c, nn, hh, m) = xlstm.slstm_decode_step(
            bp["slstm"], xin, (cur["c"], cur["n"], cur["h"], cur["m"]), cfg)
        new = {"c": c, "n": nn, "h": hh, "m": m}
    else:
        raise ValueError(kind)
    return x + y, _put_layer_state(st, new, ctx.layer)


# ---------------------------------------------------------------------------
# the model
# ---------------------------------------------------------------------------

class Model:
    """Stateless model functions for one architecture."""

    def __init__(self, cfg: ArchConfig):
        self.cfg = cfg.validate()
        self.pattern_names = _pattern_names(cfg.pattern)
        self.tail_names = tuple(
            f"t{i:02d}_{kind}" for i, kind in enumerate(cfg.tail))
        self.has_shared = SHARED_ATTN in set(cfg.pattern) | set(cfg.tail)
        self.has_moe = MOE in set(cfg.pattern) | set(cfg.tail)

    # --------------------------------------------- roofline logical axes
    def _unit_axes(self):
        cfg = self.cfg
        return {name: axes_tree(_block_defs(kind, cfg))
                for name, kind in zip(self.pattern_names, cfg.pattern)}

    def _state_axes(self):
        cfg = self.cfg
        unit = {name: _block_state_defs(kind, cfg, 1, 1)
                for name, kind in zip(self.pattern_names, cfg.pattern)}
        return axes_tree(_stack_defs(unit, 1))

    def _shared_axes(self):
        if not self.has_shared:
            return AX0
        return axes_tree({"attn": attention_defs(self.cfg),
                          "mlp": mlp_defs(self.cfg)})

    def _enc_axes(self, have_enc: bool):
        return Ax(("batch", None, "embed")) if have_enc else AX0

    # ------------------------------------------------------------ parameters
    def param_defs(self) -> Dict[str, PyTree]:
        cfg = self.cfg
        vp = padded_vocab(cfg)
        defs: Dict[str, PyTree] = {
            "embed": ParamDef((vp, cfg.d_model), ("vocab", "embed"),
                              cfg.dtype, init="embed",
                              scale=cfg.d_model ** -0.5),
            "final_norm": rmsnorm_def(cfg.d_model, cfg.dtype),
        }
        unit = {name: _block_defs(kind, cfg)
                for name, kind in zip(self.pattern_names, cfg.pattern)}
        defs["pattern"] = _stack_defs(unit, cfg.pattern_repeats)
        if cfg.tail:
            defs["tail"] = {name: _block_defs(kind, cfg)
                            for name, kind in zip(self.tail_names, cfg.tail)}
        if self.has_shared:
            defs["shared"] = {"attn": attention_defs(cfg),
                              "mlp": mlp_defs(cfg)}
        if not cfg.tie_embeddings:
            defs["lm_head"] = ParamDef((cfg.d_model, vp), ("embed", "vocab"),
                                       cfg.dtype)
        if cfg.is_encoder_decoder:
            enc_unit = {f"e00_{ATTN}": _block_defs(ATTN, cfg)}
            defs["encoder"] = {
                "pattern": _stack_defs(enc_unit, cfg.encoder_layers),
                "norm": rmsnorm_def(cfg.d_model, cfg.dtype),
            }
        return defs

    def abstract_params(self):
        return abstract(self.param_defs())

    def param_specs(self):
        return specs(self.param_defs())

    def init_params(self, key: jax.Array):
        return initialize(key, self.param_defs())

    # ---------------------------------------------------------------- embed
    def _embed(self, params, tokens):
        x = jnp.take(params["embed"], tokens, axis=0)
        return constrain(x, "batch", "seq", "embed")

    def _logits(self, params, x):
        cfg = self.cfg
        x = rmsnorm(x, params["final_norm"], cfg.norm_eps)
        if cfg.tie_embeddings:
            logits = jnp.einsum("bsd,vd->bsv", x, params["embed"])
        else:
            logits = jnp.einsum("bsd,dv->bsv", x, params["lm_head"])
        logits = logits.astype(jnp.float32)
        if cfg.logit_softcap > 0:
            logits = cfg.logit_softcap * jnp.tanh(logits / cfg.logit_softcap)
        # mask vocab padding
        vp = logits.shape[-1]
        if vp != cfg.vocab_size:
            mask = jnp.arange(vp) < cfg.vocab_size
            logits = jnp.where(mask, logits, -1e30)
        return constrain(logits, "batch", "seq", "vocab")

    # -------------------------------------------------------------- encoder
    def encode(self, params, frames: jax.Array) -> jax.Array:
        """Bidirectional encoder over stub frame embeddings (B, T, d)."""
        cfg = self.cfg
        enc = params["encoder"]
        name = f"e00_{ATTN}"

        def body(carry, bp_slice):
            x, aux = carry
            bp = bp_slice[name]
            h = multi_head_attention(bp["attn"],
                                     rmsnorm(x, bp["ln1"], cfg.norm_eps),
                                     cfg, causal=False)
            x = x + h
            x = x + mlp(bp["mlp"], rmsnorm(x, bp["ln2"], cfg.norm_eps),
                        cfg.act)
            return (x, aux), None

        # NB: encoder frames keep seq unsharded — frame counts (1500) are
        # not divisible by the model axis, unlike decoder token counts.
        (x, _), _ = instrumented_scan(
            body, (frames, jnp.float32(0)), enc["pattern"],
            name="encoder_layers",
            logical_axes=((Ax(("batch", None, "embed")), AX0),
                          axes_tree({name: _block_defs(ATTN, cfg)})))
        return rmsnorm(x, enc["norm"], cfg.norm_eps)

    def _context(self, params, frontend: Optional[jax.Array]) -> Ctx:
        cfg = self.cfg
        enc = None
        if cfg.is_encoder_decoder:
            assert frontend is not None, "encoder-decoder arch needs frames"
            enc = self.encode(params, frontend)
        elif cfg.vision_seq:
            assert frontend is not None, "vlm arch needs patch embeddings"
            enc = frontend
        shared = params.get("shared") if self.has_shared else None
        return Ctx(shared=shared, enc=enc)

    # -------------------------------------------------------------- forward
    def forward(self, params, tokens, frontend=None):
        """Training / scoring forward.  Returns (logits, aux_loss)."""
        cfg = self.cfg
        ctx = self._context(params, frontend)
        x = self._embed(params, tokens)
        kinds = dict(zip(self.pattern_names, cfg.pattern))

        def unit(x, bp_slice, shared, enc, aux):
            c = Ctx(shared=shared, enc=enc)
            for name in self.pattern_names:
                x, a = block_seq(kinds[name], bp_slice[name], x, cfg, c)
                aux = aux + a
            return x, aux

        if cfg.remat == "block":
            unit = jax.checkpoint(unit)
        elif cfg.remat == "dots":
            # save matmul outputs, recompute only cheap elementwise ops in
            # the backward pass — trades HBM for a ~25% FLOP reduction
            unit = jax.checkpoint(
                unit,
                policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable)

        def body(carry, bp_slice):
            x, shared, enc, aux = carry
            x, aux = unit(x, bp_slice, shared, enc, aux)
            return (x, shared, enc, aux), None

        shared0 = ctx.shared if ctx.shared is not None else jnp.float32(0)
        enc0 = ctx.enc if ctx.enc is not None else jnp.float32(0)
        (x, _, _, aux), _ = instrumented_scan(
            body, (x, shared0, enc0, jnp.float32(0)), params["pattern"],
            name="pattern_layers",
            logical_axes=((Ax(("batch", "seq", "embed")), self._shared_axes(),
                           self._enc_axes(ctx.enc is not None), AX0),
                          self._unit_axes()))
        for name, kind in zip(self.tail_names, cfg.tail):
            x, a = block_seq(kind, params["tail"][name], x, cfg, ctx)
            aux = aux + a
        return self._logits(params, x), aux

    # ----------------------------------------------------------------- loss
    def loss_fn(self, params, batch):
        """Next-token cross entropy.  batch: {tokens, labels[, frontend]}."""
        cfg = self.cfg
        logits, aux = self.forward(params, batch["tokens"],
                                   batch.get("frontend"))
        labels = batch["labels"]
        logz = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, labels[..., None],
                                   axis=-1)[..., 0]
        mask = (labels >= 0).astype(jnp.float32)
        denom = jnp.maximum(mask.sum(), 1.0)
        ce = jnp.sum((logz - gold) * mask) / denom
        total = ce + cfg.router_aux_weight * aux
        return total, {"ce": ce, "aux": aux,
                       "ppl_log": ce}

    # ---------------------------------------------------------- decode state
    def decode_state_defs(self, batch: int, max_len: int) -> Dict[str, PyTree]:
        cfg = self.cfg
        unit = {name: _block_state_defs(kind, cfg, batch, max_len)
                for name, kind in zip(self.pattern_names, cfg.pattern)}
        out = {"pattern": _stack_defs(unit, cfg.pattern_repeats)}
        if cfg.tail:
            out["tail"] = {
                name: _block_state_defs(kind, cfg, batch, max_len)
                for name, kind in zip(self.tail_names, cfg.tail)}
        return out

    def init_decode_state(self, batch: int, max_len: int):
        return jax.tree.map(init_state_leaf, self.decode_state_defs(batch, max_len),
                            is_leaf=is_def)

    # -------------------------------------------------------------- prefill
    def prefill(self, params, tokens, max_len: int, frontend=None):
        """Process the whole prompt; returns (last-position logits, state)."""
        cfg = self.cfg
        ctx = self._context(params, frontend)
        x = self._embed(params, tokens)
        kinds = dict(zip(self.pattern_names, cfg.pattern))

        def body(carry, bp_slice):
            x, shared, enc, aux = carry
            c = Ctx(shared=None if isinstance(shared, jax.Array) else shared,
                    enc=None if (isinstance(enc, jax.Array) and enc.ndim == 0)
                    else enc)
            states = {}
            for name in self.pattern_names:
                x, st, a = block_prefill(kinds[name], bp_slice[name], x, cfg,
                                         c, max_len)
                states[name] = st
                aux = aux + a
            return (x, shared, enc, aux), states

        shared0 = ctx.shared if ctx.shared is not None else jnp.float32(0)
        enc0 = ctx.enc if ctx.enc is not None else jnp.float32(0)
        (x, _, _, aux), states = instrumented_scan(
            body, (x, shared0, enc0, jnp.float32(0)), params["pattern"],
            name="prefill_layers",
            logical_axes=((Ax(("batch", "seq", "embed")), self._shared_axes(),
                           self._enc_axes(ctx.enc is not None), AX0),
                          self._unit_axes()))
        out = {"pattern": states}
        if cfg.tail:
            tail_states = {}
            for name, kind in zip(self.tail_names, cfg.tail):
                x, st, _ = block_prefill(kind, params["tail"][name], x, cfg,
                                         ctx, max_len)
                tail_states[name] = st
            out["tail"] = tail_states
        logits = self._logits(params, x[:, -1:, :])
        return logits, out

    # ------------------------------------------------------ chunked prefill
    @property
    def supports_chunked_prefill(self) -> bool:
        """True iff every block kind can prefill incrementally into a
        pre-allocated decode state (continuous batching needs this)."""
        kinds = set(self.cfg.pattern) | set(self.cfg.tail)
        return kinds <= set(CHUNKABLE_KINDS)

    def prefill_chunk(self, params, state, tokens, offset):
        """Incremental prefill for continuous batching: run ``tokens``
        (B, C) int32 at global positions ``[offset, offset+C)``, writing
        K/V into the given decode state.  Shapes are fixed by (B, C), so
        one jitted call serves prompts of any length; the chunk write must
        stay within the state's ``max_len``.  Returns (logits (B, C, V),
        new_state)."""
        cfg = self.cfg
        if not self.supports_chunked_prefill:
            bad = sorted((set(cfg.pattern) | set(cfg.tail))
                         - set(CHUNKABLE_KINDS))
            raise NotImplementedError(
                f"chunked prefill unsupported for block kinds {bad}")
        return self._cached_step(params, state, tokens, offset)

    # --------------------------------------------------------------- decode
    def decode_step(self, params, state, tokens, position, frontend=None):
        """One decode step.  tokens: (B, 1) int32; position: scalar int32,
        or (B,) int32 for continuous batching (each row at its own offset;
        a row position of ``max_len`` is a write-proof free-slot sentinel).
        Returns (logits (B,1,V), new_state)."""
        # NOTE: for enc-dec decode the cross K/V already live in the state;
        # no encoder pass here.
        return self._cached_step(params, state, tokens, position)

    def _cached_step(self, params, state, tokens, position):
        """Run ``tokens`` through every block over the decode state.  The
        stacked pattern state rides in the layer loop's carry and each
        layer writes its rows in place, so no layer's state is sliced out
        of the scan's inputs and no second state is collected as its
        outputs."""
        cfg = self.cfg
        shared = params.get("shared") if self.has_shared else None
        x = self._embed(params, tokens)
        position = jnp.asarray(position, jnp.int32)
        kinds = dict(zip(self.pattern_names, cfg.pattern))

        def body(carry, xs):
            x, shared, pos, states = carry
            layer, bp_slice = xs
            c = Ctx(shared=None if isinstance(shared, jax.Array) else shared,
                    position=pos, layer=layer)
            states = dict(states)
            for name in self.pattern_names:
                x, states[name] = block_decode(kinds[name], bp_slice[name], x,
                                               states[name], cfg, c)
            return (x, shared, pos, states), None

        shared0 = shared if shared is not None else jnp.float32(0)
        layers = jnp.arange(cfg.pattern_repeats, dtype=jnp.int32)
        (x, _, _, pattern), _ = instrumented_scan(
            body, (x, shared0, position, state["pattern"]),
            (layers, params["pattern"]), name="decode_layers",
            logical_axes=((Ax(("batch", "seq", "embed")), self._shared_axes(),
                           AX0, self._state_axes()),
                          (AX0, self._unit_axes())))
        out = {"pattern": pattern}
        if cfg.tail:
            ctx = Ctx(shared=shared, position=position)
            tail = dict(state["tail"])
            for name, kind in zip(self.tail_names, cfg.tail):
                x, tail[name] = block_decode(kind, params["tail"][name], x,
                                             tail[name], cfg, ctx)
            out["tail"] = tail
        return self._logits(params, x), out
