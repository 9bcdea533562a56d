"""Pallas TPU chunked-SSD (Mamba-2) scan kernel.

TPU-native adaptation of the SSD algorithm: the (batch, head) grid axes are
parallel; the chunk axis is the innermost (sequential) grid dimension, and
the inter-chunk recurrent state (P × N) lives in VMEM scratch across chunk
steps — the sequential TPU grid replaces the GPU implementation's
inter-block state-passing kernel.  Within a chunk everything is dense
MXU-shaped matmuls:

    y_intra = (L ⊙ (C Bᵀ)) · X            (chunk × chunk quadratic part)
    y_inter = diag(exp(csum)) · C · state   (contribution of entering state)
    state'  = exp(total)·state + Σ_k B_k (decay_k X_k)ᵀ

Inputs are the pre-scaled tensors produced by the Mamba-2 block projection
(see ``repro.models.ssm``): x·Δt, Δt·a (log-decay), B, C.  The final state
is emitted as a second output (written every chunk step; the last write is
the final state), which prefill uses to seed decoding.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ssd_kernel(x_ref, csc_ref, csr_ref, b_ref, c_ref, y_ref, st_ref,
                state_scr, *, chunk: int):
    """One (b, h, ic) grid step.

    x_ref: (1, 1, chunk, P) pre-scaled inputs (x·Δt); csc_ref/csr_ref: the
    chunk-local inclusive cumsum of Δt·a as a column (1, 1, chunk, 1) and as
    a row (1, 1, 1, chunk); b_ref/c_ref: (1, chunk, N); y_ref: (1, 1, chunk,
    P); st_ref: (1, 1, P, N) final-state output; state_scr: (P, N) f32 VMEM.
    """
    ic = pl.program_id(2)

    @pl.when(ic == 0)
    def _init():
        state_scr[...] = jnp.zeros_like(state_scr)

    x = x_ref[0, 0].astype(jnp.float32)                  # (chunk, P)
    csum = csc_ref[0, 0]                                 # (chunk, 1)
    csum_row = csr_ref[0, 0]                             # (1, chunk)
    bm = b_ref[0].astype(jnp.float32)                    # (chunk, N)
    cm = c_ref[0].astype(jnp.float32)                    # (chunk, N)
    total = csum[chunk - 1:, :]                          # (1, 1)

    # L[q, k] = exp(csum_q − csum_k) for q ≥ k (decay from k to q)
    seg = csum - csum_row
    qi = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    ki = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    lmat = jnp.where(qi >= ki, jnp.exp(seg), 0.0)

    scores = jax.lax.dot_general(cm, bm, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
    y_intra = jax.lax.dot_general(lmat * scores, x, (((1,), (0,)), ((), ())),
                                  preferred_element_type=jnp.float32)

    # inter-chunk: entering state contribution + state update
    state = state_scr[...]                               # (P, N)
    y_inter = jax.lax.dot_general(cm, state, (((1,), (1,)), ((), ())),
                                  preferred_element_type=jnp.float32)
    y = y_intra + y_inter * jnp.exp(csum)

    xw = x * jnp.exp(total - csum)                       # (chunk, P)
    new_contrib = jax.lax.dot_general(xw, bm, (((0,), (0,)), ((), ())),
                                      preferred_element_type=jnp.float32)
    # Mosaic cannot broadcast (1, 1) over sublanes and lanes in one op:
    # materialise the decay as a (P, 1) column, then broadcast over lanes
    decay_col = jnp.exp(total + jnp.zeros((state.shape[0], 1), jnp.float32))
    state_scr[...] = state * decay_col + new_contrib

    y_ref[0, 0] = y.astype(y_ref.dtype)
    st_ref[0, 0] = state_scr[...]


@functools.partial(jax.jit,
                   static_argnames=("chunk", "interpret"))
def ssd_scan(
    x: jax.Array,       # (B, S, H, P) pre-scaled inputs (x · Δt)
    da: jax.Array,      # (B, S, H)    per-step log decay (Δt · a)
    b_mat: jax.Array,   # (B, S, N)
    c_mat: jax.Array,   # (B, S, N)
    *,
    chunk: int = 256,
    interpret: bool = False,
):
    """Returns (y (B,S,H,P) f32, final_state (B,H,P,N) f32)."""
    bsz, s, h, p = x.shape
    n = b_mat.shape[-1]
    if s % chunk != 0:
        chunk = s
    nc = s // chunk
    grid = (bsz, h, nc)

    # head axis ahead of the sequence, so every block's last two dims are
    # (chunk, P) / (chunk, 1) / (1, chunk): tile-aligned or the array's own
    xh = x.transpose(0, 2, 1, 3)                         # (B, H, S, P)
    csum = jnp.cumsum(da.astype(jnp.float32).transpose(0, 2, 1)
                      .reshape(bsz, h, nc, chunk), axis=-1).reshape(bsz, h, s)

    kernel = functools.partial(_ssd_kernel, chunk=chunk)
    y, st = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, chunk, p),
                         lambda bi, hi, ci: (bi, hi, ci, 0)),
            pl.BlockSpec((1, 1, chunk, 1),
                         lambda bi, hi, ci: (bi, hi, ci, 0)),
            pl.BlockSpec((1, 1, 1, chunk),
                         lambda bi, hi, ci: (bi, hi, 0, ci)),
            pl.BlockSpec((1, chunk, n),
                         lambda bi, hi, ci: (bi, ci, 0)),
            pl.BlockSpec((1, chunk, n),
                         lambda bi, hi, ci: (bi, ci, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, chunk, p),
                         lambda bi, hi, ci: (bi, hi, ci, 0)),
            pl.BlockSpec((1, 1, p, n),
                         lambda bi, hi, ci: (bi, hi, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bsz, h, s, p), jnp.float32),
            jax.ShapeDtypeStruct((bsz, h, p, n), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((p, n), jnp.float32)],
        interpret=interpret,
    )(xh, csum[..., None], csum[:, :, None, :], b_mat, c_mat)
    return y.transpose(0, 2, 1, 3), st
