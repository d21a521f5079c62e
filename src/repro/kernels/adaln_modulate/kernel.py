"""Fused adaLN-zero modulation — Pallas TPU kernel.

A DiT block conditions every sub-block as `LN(h) * (1 + scale) + shift` and
re-enters the residual stream as `h + gate * branch(h_mod)` (adaLN-zero,
Peebles & Xie 2023). Executed as separate ops that is ~5 elementwise passes
over the (B, T, D) activation per sub-block: the LN reduction, the
normalize, the scale multiply, the shift add, and the gate/residual pair —
each a full HBM round trip when the dispatch boundary pins the schedule
(eager frameworks) and still reduction+elementwise kernel splits under XLA.
At serving time the activation is the whole slot batch, so the modulation is
purely memory-bound, exactly like the solver update (DESIGN.md §4).

Two kernels, each one pass over the activation:

* `adaln_modulate(x, shift, scale)` — LN (no learnable affine, matching
  `models.layers.layernorm({}, x)`) fused with the scale/shift modulation:
  read x once, write the modulated output once. Mean/variance are computed
  in fp32 inside the tile with padded lanes masked, so arbitrary D is
  handled without host-side masking.
* `gate_residual(resid, gate, y)` — `resid + gate * y`, the adaLN-zero gated
  residual re-entry: three reads, one write, no intermediate.

Layout: x/resid/y (B, T, D); shift/scale/gate (B, D) broadcast over tokens,
viewed as (B, 1, D) so each grid row reads a (1, 1, D) block — a TPU block's
last two dims must be (8k, 128k) or span the array, which a (1, D) block of
a (B, D) array does not at B > 1. Grid is (B, T tiles); D lives fully inside
the block (DiT widths are <= a few K lanes, far under VMEM). D is padded to
the 128-lane boundary by ops.py (masked in the LN reduction, garbage lanes
sliced off), T to the token-tile boundary (rows sliced off).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

DEFAULT_BLOCK_T = 128  # token rows per tile; fp32/bf16 sublane-aligned


def _modulate_kernel(x_ref, sh_ref, sc_ref, o_ref, *, d_true, eps):
    x = x_ref[0].astype(jnp.float32)                       # (blk_t, Dp)
    dp = x.shape[-1]
    if dp != d_true:  # masked reduction over the real lanes only
        lane = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
        mask = lane < d_true
        x = jnp.where(mask, x, 0.0)
    mu = jnp.sum(x, axis=-1, keepdims=True) / d_true
    cen = x - mu
    if dp != d_true:
        cen = jnp.where(mask, cen, 0.0)
    var = jnp.sum(cen * cen, axis=-1, keepdims=True) / d_true
    y = cen * jax.lax.rsqrt(var + eps)
    sc = sc_ref[0].astype(jnp.float32)                     # (1, Dp)
    sh = sh_ref[0].astype(jnp.float32)
    o_ref[0] = (y * (1.0 + sc) + sh).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("d_true", "eps", "blk_t",
                                             "interpret"))
def adaln_modulate(x, shift, scale, *, d_true, eps=1e-5,
                   blk_t=DEFAULT_BLOCK_T, interpret=True):
    """x: (B, T, Dp); shift/scale: (B, Dp). T % blk_t == 0 and Dp % 128 == 0
    (pad upstream in ops.py; `d_true` = the unpadded width, the LN reduction
    masks the padding and padded output lanes are garbage to slice off)."""
    B, T, Dp = x.shape
    kernel = functools.partial(_modulate_kernel, d_true=d_true, eps=eps)
    return pl.pallas_call(
        kernel,
        grid=(B, T // blk_t),
        in_specs=[
            pl.BlockSpec((1, blk_t, Dp), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, 1, Dp), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((1, 1, Dp), lambda b, i: (b, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, blk_t, Dp), lambda b, i: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B, T, Dp), x.dtype),
        interpret=interpret,
    )(x, shift.reshape(B, 1, Dp), scale.reshape(B, 1, Dp))


def _gate_res_kernel(r_ref, g_ref, y_ref, o_ref):
    r = r_ref[0].astype(jnp.float32)
    y = y_ref[0].astype(jnp.float32)
    g = g_ref[0].astype(jnp.float32)                       # (1, Dp)
    o_ref[0] = (r + g * y).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("blk_t", "interpret"))
def gate_residual(resid, gate, y, *, blk_t=DEFAULT_BLOCK_T, interpret=True):
    """resid/y: (B, T, Dp); gate: (B, Dp). resid + gate * y in one pass.
    Same padding contract as `adaln_modulate` (no reduction, so padded lanes
    need no masking — their outputs are sliced off upstream)."""
    B, T, Dp = resid.shape
    return pl.pallas_call(
        _gate_res_kernel,
        grid=(B, T // blk_t),
        in_specs=[
            pl.BlockSpec((1, blk_t, Dp), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, 1, Dp), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((1, blk_t, Dp), lambda b, i: (b, i, 0)),
        ],
        out_specs=pl.BlockSpec((1, blk_t, Dp), lambda b, i: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B, T, Dp), resid.dtype),
        interpret=interpret,
    )(resid, gate.reshape(B, 1, Dp), y)
