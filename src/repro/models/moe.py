"""Mixture-of-Experts FFN with capacity-based gather dispatch.

TPU-native dispatch (see DESIGN.md hardware-adaptation notes): instead of the
GShard one-hot dispatch einsum — whose (tokens × experts × capacity) tensors
dominate compiled FLOPs and would wreck the MODEL_FLOPS/HLO_FLOPs ratio — we

  1. route: top-k experts per token (router in fp32),
  2. per-expert token selection: top-C over the (experts, tokens) score
     matrix ⇒ an (E, C) int32 gather index (C = tokens·k/E · capacity_factor),
  3. gather tokens to (E, C, d), run the expert FFN as one batched einsum
     (MXU-shaped), and
  4. scatter-add back weighted by gate probabilities.

FLOPs are proportional to actual expert compute (k·cf × dense-equivalent);
the only O(E·T) object is the fp32 routing matrix, which shards over
(experts→model, tokens→data).  Exact (vs the dense reference in
``moe_reference``) whenever no token overflows capacity.
"""

from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from .config import ArchConfig
from .layers import _activate
from .params import ParamDef
from .sharding import constrain


def moe_defs(cfg: ArchConfig) -> Dict[str, ParamDef]:
    d, e, f, dt = cfg.d_model, cfg.num_experts, cfg.moe_d_ff or cfg.d_ff, cfg.dtype
    # expert tensors use their own logical d_model axis ("expert_embed") so
    # their 2-D (experts×data) sharding is controllable independently of the
    # dense params' FSDP axis (rule dedup would otherwise couple them).
    defs = {
        "router": ParamDef((d, e), ("embed", "experts"), "float32", scale=0.1),
        "w1": ParamDef((e, d, f), ("experts", "expert_embed", "expert_mlp"), dt),
        "w2": ParamDef((e, f, d), ("experts", "expert_mlp", "expert_embed"), dt),
    }
    if cfg.act == "silu":
        defs["w3"] = ParamDef((e, d, f), ("experts", "expert_embed", "expert_mlp"), dt)
    if cfg.num_shared_experts:
        fs = f * cfg.num_shared_experts
        defs["shared_w1"] = ParamDef((d, fs), ("embed", "mlp"), dt)
        defs["shared_w2"] = ParamDef((fs, d), ("mlp", "embed"), dt)
        if cfg.act == "silu":
            defs["shared_w3"] = ParamDef((d, fs), ("embed", "mlp"), dt)
    return defs


def _router_probs(
    params: Dict[str, jax.Array], x: jax.Array, cfg: ArchConfig
) -> jax.Array:
    # NB: no x.astype(f32) — that materializes a full fp32 copy of the token
    # array, which GSPMD then reshards at 2× the bytes (measured: 28 GiB of
    # fp32 all-gathers per layer on kimi-k2).  Mixed-precision einsum with a
    # fp32 accumulator gives the same numerics for the router.
    logits = jnp.einsum("td,de->te", x, params["router"],
                        preferred_element_type=jnp.float32)
    return jax.nn.softmax(logits, axis=-1)  # (T, E)


def moe_ffn(
    params: Dict[str, jax.Array],
    x: jax.Array,          # (B, S, d)
    cfg: ArchConfig,
    *,
    capacity_factor: Optional[float] = None,
) -> Tuple[jax.Array, jax.Array]:
    """Returns (output, router aux loss).

    Dispatch paths (``cfg.moe_dispatch_groups``):
      0/1 — global GShard-style top-C gather dispatch (baseline);
      g>1 — per-group routing aligned with the data axis;
      -1  — shard_map expert parallelism: explicit all_to_all dispatch,
            per-shard capacity, per-layer expert-weight all-gather (ZeRO)
            — the §Perf winner for large MoE (see EXPERIMENTS.md).
    """
    g = cfg.moe_dispatch_groups
    if g == -1:
        from .sharding import _state

        mesh = getattr(_state, "mesh", None)
        if mesh is not None and "model" in mesh.axis_names \
                and cfg.num_experts % mesh.shape["model"] == 0:
            return _moe_ffn_shard_map(params, x, cfg,
                                      capacity_factor or cfg.capacity_factor)
        g = 0  # no mesh (smoke tests): fall through to the global path
    if g > 1 and (x.shape[0] * x.shape[1]) % g == 0:
        return _moe_ffn_grouped(params, x, cfg, g,
                                capacity_factor or cfg.capacity_factor)
    b, s, d = x.shape
    t = b * s
    e, k = cfg.num_experts, cfg.experts_per_token
    xt = x.reshape(t, d)
    xt = constrain(xt, "batch", "embed")

    probs = _router_probs(params, xt, cfg)                       # (T, E)
    probs = constrain(probs, "batch", "experts")
    gate_vals, gate_idx = jax.lax.top_k(probs, k)                # (T, k)
    gate_vals = gate_vals / jnp.clip(
        gate_vals.sum(-1, keepdims=True), 1e-9
    )  # renormalize over selected experts

    # load-balance aux loss (Switch-style): E · Σ_e fraction_e · prob_e
    sel_onehot = jax.nn.one_hot(gate_idx, e, dtype=jnp.float32)  # (T, k, E)
    token_frac = sel_onehot.sum(axis=(0, 1)) / (t * k)
    prob_frac = probs.mean(axis=0)
    aux = e * jnp.sum(token_frac * prob_frac)

    cf = capacity_factor or cfg.capacity_factor
    capacity = max(1, min(t, int(t * k * cf / e) + 1))

    # per-expert selection scores: prob if the expert was chosen, else -inf
    chosen = sel_onehot.sum(axis=1)                               # (T, E) 0/1
    combine = (gate_vals[:, :, None] * sel_onehot).sum(axis=1)    # (T, E)
    sel_scores = jnp.where(chosen > 0, probs, -jnp.inf).T         # (E, T)
    sel_scores = constrain(sel_scores, "experts", "batch")
    top_scores, token_idx = jax.lax.top_k(sel_scores, capacity)   # (E, C)
    valid = jnp.isfinite(top_scores)                              # dropped?
    token_idx = jnp.where(valid, token_idx, 0)

    xs = jnp.take(xt, token_idx.reshape(-1), axis=0)
    xs = xs.reshape(e, capacity, d)
    xs = constrain(xs, "experts", None, "embed")

    h = jnp.einsum("ecd,edf->ecf", xs, params["w1"])
    h = _activate(h, cfg.act)
    if "w3" in params:
        h = h * jnp.einsum("ecd,edf->ecf", xs, params["w3"])
    h = constrain(h, "experts", None, "expert_mlp")
    ys = jnp.einsum("ecf,efd->ecd", h, params["w2"])              # (E, C, d)

    # combine: weight by gate prob, zero dropped slots, scatter-add
    w = jnp.take_along_axis(combine.T, token_idx, axis=1)         # (E, C)
    ys = ys * (w * valid).astype(ys.dtype)[..., None]
    out = jnp.zeros((t, d), ys.dtype).at[token_idx.reshape(-1)].add(
        ys.reshape(-1, d)
    )
    out = constrain(out, "batch", "embed")

    if cfg.num_shared_experts:
        hs = jnp.einsum("td,df->tf", xt, params["shared_w1"])
        hs = _activate(hs, cfg.act)
        if "shared_w3" in params:
            hs = hs * jnp.einsum("td,df->tf", xt, params["shared_w3"])
        out = out + jnp.einsum("tf,fd->td", hs, params["shared_w2"])

    return out.reshape(b, s, d), aux.astype(jnp.float32)


def _moe_ffn_grouped(
    params: Dict[str, jax.Array],
    x: jax.Array,          # (B, S, d)
    cfg: ArchConfig,
    g: int,
    cf: float,
) -> Tuple[jax.Array, jax.Array]:
    """Expert-parallel dispatch: tokens are routed *within* g groups that
    align with the data mesh axis, so the token gather/scatter is
    shard-local; only the dispatched (E, C, d) copies cross the mesh (the
    EP all-to-all), never the full (T, d) token array.

    Semantics: identical routing, but capacity is enforced *per group*
    (standard per-device capacity in EP systems) — exact vs the dense
    reference whenever no group overflows.
    """
    b, s, d = x.shape
    t = b * s
    tl = t // g
    e, k = cfg.num_experts, cfg.experts_per_token
    xg = x.reshape(g, tl, d)
    xg = constrain(xg, "batch", None, "embed")

    logits = jnp.einsum("gtd,de->gte", xg, params["router"],
                        preferred_element_type=jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)              # (g, tl, E)
    probs = constrain(probs, "batch", None, "experts")
    gate_vals, gate_idx = jax.lax.top_k(probs, k)        # (g, tl, k)
    gate_vals = gate_vals / jnp.clip(gate_vals.sum(-1, keepdims=True), 1e-9)

    sel_onehot = jax.nn.one_hot(gate_idx, e, dtype=jnp.float32)  # (g,tl,k,E)
    token_frac = sel_onehot.sum(axis=(0, 1, 2)) / (t * k)
    prob_frac = probs.mean(axis=(0, 1))
    aux = e * jnp.sum(token_frac * prob_frac)

    capacity = max(1, min(tl, int(tl * k * cf / e) + 1))
    chosen = sel_onehot.sum(axis=2)                      # (g, tl, E)
    combine = (gate_vals[..., None] * sel_onehot).sum(axis=2)  # (g, tl, E)
    sel_scores = jnp.where(chosen > 0, probs, -jnp.inf)  # (g, tl, E)
    sel_scores = sel_scores.swapaxes(1, 2)               # (g, E, tl)
    sel_scores = constrain(sel_scores, "batch", "experts", None)
    top_scores, token_idx = jax.lax.top_k(sel_scores, capacity)  # (g,E,C)
    valid = jnp.isfinite(top_scores)
    token_idx = jnp.where(valid, token_idx, 0)
    token_idx = constrain(token_idx, "batch", None, None)

    # shard-local gather: (g, E·C, d), g stays on the data axis
    xs = jnp.take_along_axis(
        xg, token_idx.reshape(g, e * capacity)[..., None], axis=1)
    xs = constrain(xs, "batch", None, "embed")
    xs = xs.reshape(g, e, capacity, d).swapaxes(0, 1)    # (E, g, C, d)
    xs = constrain(xs, "experts", "batch", None, "embed")

    h = jnp.einsum("egcd,edf->egcf", xs, params["w1"])
    h = _activate(h, cfg.act)
    if "w3" in params:
        h = h * jnp.einsum("egcd,edf->egcf", xs, params["w3"])
    h = constrain(h, "experts", "batch", None, "expert_mlp")
    ys = jnp.einsum("egcf,efd->egcd", h, params["w2"])   # (E, g, C, d)

    w = jnp.take_along_axis(combine.swapaxes(1, 2), token_idx, axis=2)
    ys = ys * (w.swapaxes(0, 1) * valid.swapaxes(0, 1)).astype(
        ys.dtype)[..., None]
    ys = ys.swapaxes(0, 1)                               # (g, E, C, d)
    out = jnp.zeros((g, tl, d), ys.dtype).at[
        jnp.arange(g)[:, None], token_idx.reshape(g, -1)
    ].add(ys.reshape(g, -1, d))
    out = constrain(out, "batch", None, "embed")

    if cfg.num_shared_experts:
        hs = jnp.einsum("gtd,df->gtf", xg, params["shared_w1"])
        hs = _activate(hs, cfg.act)
        if "shared_w3" in params:
            hs = hs * jnp.einsum("gtd,df->gtf", xg, params["shared_w3"])
        out = out + jnp.einsum("gtf,fd->gtd", hs, params["shared_w2"])

    return out.reshape(b, s, d), aux.astype(jnp.float32)


def _moe_ffn_shard_map(
    params: Dict[str, jax.Array],
    x: jax.Array,          # (B, S, d)
    cfg: ArchConfig,
    cf: float,
) -> Tuple[jax.Array, jax.Array]:
    """Expert parallelism with explicit collectives (shard_map).

    Per (data-row, model-col) chip:
      1. route the chip's own tokens (router weights are replicated, fp32);
      2. per-shard capacity top-C selection and local gather → (E, C, d);
      3. ``all_to_all`` over the model axis → (E/tp, C·tp, d): each chip
         receives its experts' tokens — the only token bytes that move are
         the dispatched copies (k·cf per token), never the full array;
      4. expert weights (stored experts×expert_embed-sharded, ZeRO-style)
         are ``all_gather``-ed over the data axes once per layer;
      5. batched expert FFN, reverse ``all_to_all``, local weighted combine.

    GSPMD's gather/scatter lowering of the same computation produced
    ~57 GiB/layer of fp32 all-reduces (see EXPERIMENTS.md §Perf, kimi-k2
    iterations 1–2); the explicit form moves ~100× less.
    """
    from jax.sharding import PartitionSpec as P

    from .sharding import _state, logical_to_spec

    mesh = _state.mesh
    dp_axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    tp = mesh.shape["model"]
    b, s, d = x.shape
    e, k = cfg.num_experts, cfg.experts_per_token
    e_loc = e // tp

    # the residual stream enters sequence-parallel (seq → model under the
    # train/prefill rules): each model chip routes its own seq slice — the
    # dispatch work itself is model-partitioned, not replicated.
    x_spec = logical_to_spec(("batch", "seq", "embed"))
    if s % tp != 0 or (x_spec[1] is None and tp > 1 and s > 1):
        # no SP available (e.g. odd seq): fall back to batch-only sharding
        x_spec = P(x_spec[0], None, None)
    defs = moe_defs(cfg)
    w_names = ["router", "w1", "w2"] + (["w3"] if "w3" in params else [])
    # router (d×E fp32, ~10 MB) is replicated into the body; expert tensors
    # enter with their stored (experts × expert_embed) sharding.
    w_specs = [P() if n == "router" else logical_to_spec(defs[n].axes)
               for n in w_names]
    w_args = [params[n] for n in w_names]

    def body(xl, router, w1, w2, *rest):
        w3 = rest[0] if rest else None
        bl, sl, _ = xl.shape
        tl = bl * sl
        xt = xl.reshape(tl, d)
        logits = jnp.einsum("td,de->te", xt, router,
                            preferred_element_type=jnp.float32)
        probs = jax.nn.softmax(logits, axis=-1)               # (tl, E)
        gate_vals, gate_idx = jax.lax.top_k(probs, k)
        gate_vals = gate_vals / jnp.clip(gate_vals.sum(-1, keepdims=True),
                                         1e-9)
        sel_onehot = jax.nn.one_hot(gate_idx, e, dtype=jnp.float32)
        token_frac = sel_onehot.sum(axis=(0, 1)) / (tl * k)
        prob_frac = probs.mean(axis=0)
        aux = e * jnp.sum(token_frac * prob_frac)
        mean_axes = dp_axes + (("model",) if x_spec[1] is not None else ())
        aux = jax.lax.pmean(aux, mean_axes) if mean_axes else aux

        capacity = max(1, min(tl, int(tl * k * cf / e) + 1))
        chosen = sel_onehot.sum(axis=1)                       # (tl, E)
        combine = (gate_vals[:, :, None] * sel_onehot).sum(axis=1)
        sel_scores = jnp.where(chosen > 0, probs, -jnp.inf).T  # (E, tl)
        top_scores, token_idx = jax.lax.top_k(sel_scores, capacity)
        valid = jnp.isfinite(top_scores)
        token_idx = jnp.where(valid, token_idx, 0)

        xs = jnp.take(xt, token_idx.reshape(-1), axis=0)
        xs = xs.reshape(e, capacity, d)
        # dispatch: tokens → their experts' chips (model axis)
        xs = jax.lax.all_to_all(xs, "model", split_axis=0, concat_axis=1,
                                tiled=True)                   # (E/tp, C·tp, d)
        # ZeRO weight gather over the data axes (expert_embed-sharded)
        w1f = jax.lax.all_gather(w1, dp_axes, axis=1, tiled=True) \
            if dp_axes else w1                                # (E/tp, d, f)
        w2f = jax.lax.all_gather(w2, dp_axes, axis=2, tiled=True) \
            if dp_axes else w2                                # (E/tp, f, d)
        h = jnp.einsum("ecd,edf->ecf", xs, w1f)
        h = _activate(h, cfg.act)
        if w3 is not None:
            w3f = jax.lax.all_gather(w3, dp_axes, axis=1, tiled=True) \
                if dp_axes else w3
            h = h * jnp.einsum("ecd,edf->ecf", xs, w3f)
        ys = jnp.einsum("ecf,efd->ecd", h, w2f)               # (E/tp, C·tp, d)
        # return: expert outputs → token-owner chips
        ys = jax.lax.all_to_all(ys, "model", split_axis=1, concat_axis=0,
                                tiled=True)                   # (E, C, d)
        w = jnp.take_along_axis(combine.T, token_idx, axis=1)  # (E, C)
        ys = ys * (w * valid).astype(ys.dtype)[..., None]
        out = jnp.zeros((tl, d), ys.dtype).at[
            token_idx.reshape(-1)].add(ys.reshape(-1, d))
        return out.reshape(bl, sl, d), aux

    out, aux = jax.shard_map(
        body, mesh=mesh,
        in_specs=(x_spec, *w_specs),
        out_specs=(x_spec, P()),
        check_vma=False,
    )(x, *w_args)

    if cfg.num_shared_experts:
        bsz, sl, _ = x.shape
        xt = x.reshape(bsz * sl, d)
        hs = jnp.einsum("td,df->tf", xt, params["shared_w1"])
        hs = _activate(hs, cfg.act)
        if "shared_w3" in params:
            hs = hs * jnp.einsum("td,df->tf", xt, params["shared_w3"])
        out = out + jnp.einsum("tf,fd->td", hs,
                               params["shared_w2"]).reshape(bsz, sl, d)
    return out, aux.astype(jnp.float32)


def moe_reference(
    params: Dict[str, jax.Array], x: jax.Array, cfg: ArchConfig
) -> jax.Array:
    """Dense-masked oracle: every expert sees every token, masked combine.
    O(T·E·d·f) — tests only."""
    b, s, d = x.shape
    t = b * s
    xt = x.reshape(t, d)
    probs = _router_probs(params, xt, cfg)
    gate_vals, gate_idx = jax.lax.top_k(probs, cfg.experts_per_token)
    gate_vals = gate_vals / jnp.clip(gate_vals.sum(-1, keepdims=True), 1e-9)
    combine = jnp.zeros_like(probs)
    for j in range(cfg.experts_per_token):
        combine = combine.at[jnp.arange(t), gate_idx[:, j]].add(gate_vals[:, j])
    h = jnp.einsum("td,edf->etf", xt, params["w1"])
    h = _activate(h, cfg.act)
    if "w3" in params:
        h = h * jnp.einsum("td,edf->etf", xt, params["w3"])
    ys = jnp.einsum("etf,efd->etd", h, params["w2"])
    out = jnp.einsum("etd,te->td", ys, combine.astype(ys.dtype))
    if cfg.num_shared_experts:
        hs = jnp.einsum("td,df->tf", xt, params["shared_w1"])
        hs = _activate(hs, cfg.act)
        if "shared_w3" in params:
            hs = hs * jnp.einsum("td,df->tf", xt, params["shared_w3"])
        out = out + jnp.einsum("tf,fd->td", hs, params["shared_w2"])
    return out.reshape(b, s, d)
