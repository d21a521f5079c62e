"""Blockwise (flash) causal attention — Pallas TPU kernel.

Online-softmax attention with VMEM tiling for the prefill hot path:
grid = (batch * q_heads, num_q_blocks, num_kv_blocks); the kv axis is the
innermost sequential grid dimension, carrying running (max, denom, acc)
in VMEM scratch. GQA is expressed in the BlockSpec index maps (the kv block
for query head h is head h // group of the K/V operands) — no materialized
head repetition. Causal and sliding-window masks are applied per tile;
fully-masked tiles are skipped with pl.when.

Cross-attention over a padded context (a caption shorter than its buffer)
passes `kv_lens`, one key length per batch row, as a scalar-prefetch
operand: each row masks its keys past its own length, kv blocks wholly past
it are skipped, and their index map repeats the row's last live block so
no DMA fetches them. Without `kv_lens` the call is the plain grid above.

Block sizes default to (128, 128): MXU-aligned, and the working set
(q 128xD + k/v 128xD + fp32 scratch) stays well under the ~16 MB VMEM for
D <= 256.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BLOCK_Q = 128
DEFAULT_BLOCK_K = 128
NEG_INF = -1e30


def _attn_kernel(*refs, scale, causal, window, blk_q, blk_k, kv_len,
                 heads=None):
    """One (row·head, q block, kv block) tile. With `heads` set, the first
    ref holds the per-row key lengths (scalar prefetch)."""
    if heads is None:
        q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr = refs
    else:
        lens_ref, q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr = refs
        kv_len = lens_ref[pl.program_id(0) // heads]
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    q_start = qi * blk_q
    k_start = ki * blk_k
    # skip tiles entirely above the causal diagonal / outside the window
    run = jnp.bool_(True)
    if causal:
        run = jnp.logical_and(run, k_start <= q_start + blk_q - 1)
    if window is not None:
        run = jnp.logical_and(run, k_start + blk_k - 1 > q_start - window)
    if heads is not None:
        run = jnp.logical_and(run, k_start < kv_len)

    @pl.when(run)
    def _body():
        q = q_ref[0].astype(jnp.float32)            # (blk_q, d)
        k = k_ref[0].astype(jnp.float32)            # (blk_k, d)
        v = v_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ()))) * scale
        qpos = q_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        ok = kpos < kv_len
        if causal:
            ok = jnp.logical_and(ok, kpos <= qpos)
        if window is not None:
            ok = jnp.logical_and(ok, kpos > qpos - window)
        s = jnp.where(ok, s, NEG_INF)
        m_prev = m_scr[:]
        m_cur = jnp.maximum(m_prev, jnp.max(s, axis=1))
        p = jnp.exp(s - m_cur[:, None])
        alpha = jnp.exp(m_prev - m_cur)
        l_scr[:] = l_scr[:] * alpha + jnp.sum(p, axis=1)
        acc_scr[:] = acc_scr[:] * alpha[:, None] + jax.lax.dot(p, v)
        m_scr[:] = m_cur

    @pl.when(ki == nk - 1)
    def _finish():
        denom = jnp.maximum(l_scr[:], 1e-30)
        o_ref[0] = (acc_scr[:] / denom[:, None]).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "blk_q", "blk_k", "interpret",
                     "kv_len"))
def flash_attention(q, k, v, kv_lens=None, *, causal=True, window=None,
                    blk_q=DEFAULT_BLOCK_Q, blk_k=DEFAULT_BLOCK_K,
                    interpret=True, kv_len=None):
    """q: (B, Hq, Sq, D); k, v: (B, Hkv, Skv, D); Hq % Hkv == 0.

    Sq must be a multiple of blk_q and Skv of blk_k (pad upstream in ops.py;
    kv_len = the unpadded key length, padded keys are masked). `kv_lens`,
    int32 (B,) with 1 <= kv_lens[b] <= kv_len, gives each batch row its own
    key length in place of `kv_len`.
    """
    B, Hq, Sq, D = q.shape
    Hkv, Skv = k.shape[1], k.shape[2]
    kv_len = kv_len if kv_len is not None else Skv
    group = Hq // Hkv
    scale = 1.0 / math.sqrt(D)
    grid = (B * Hq, Sq // blk_q, Skv // blk_k)

    def q_map(bh, qi, ki):
        return (bh, qi, 0)

    def kv_map(bh, qi, ki):
        b, h = bh // Hq, bh % Hq
        return (b * Hkv + h // group, ki, 0)

    kernel = functools.partial(
        _attn_kernel, scale=scale, causal=causal, window=window,
        blk_q=blk_q, blk_k=blk_k, kv_len=kv_len)
    args = (q.reshape(B * Hq, Sq, D), k.reshape(B * Hkv, Skv, D),
            v.reshape(B * Hkv, Skv, D))
    scratch = [pltpu.VMEM((blk_q,), jnp.float32),
               pltpu.VMEM((blk_q,), jnp.float32),
               pltpu.VMEM((blk_q, D), jnp.float32)]
    out_shape = jax.ShapeDtypeStruct((B * Hq, Sq, D), q.dtype)
    if kv_lens is not None:
        def lens_kv_map(bh, qi, ki, lens):
            # past the row's last live block, stay on it: the kernel skips
            # the tile and an unchanged block index fetches nothing
            live = jnp.maximum((lens[bh // Hq] - 1) // blk_k, 0)
            return kv_map(bh, qi, jnp.minimum(ki, live))

        out = pl.pallas_call(
            functools.partial(kernel, heads=Hq),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=1,
                grid=grid,
                in_specs=[
                    pl.BlockSpec((1, blk_q, D),
                                 lambda bh, qi, ki, lens: q_map(bh, qi, ki)),
                    pl.BlockSpec((1, blk_k, D), lens_kv_map),
                    pl.BlockSpec((1, blk_k, D), lens_kv_map),
                ],
                out_specs=pl.BlockSpec(
                    (1, blk_q, D), lambda bh, qi, ki, lens: q_map(bh, qi, ki)),
                scratch_shapes=scratch),
            out_shape=out_shape,
            interpret=interpret,
        )(kv_lens.astype(jnp.int32), *args)
    else:
        out = pl.pallas_call(
            kernel,
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, blk_q, D), q_map),
                pl.BlockSpec((1, blk_k, D), kv_map),
                pl.BlockSpec((1, blk_k, D), kv_map),
            ],
            out_specs=pl.BlockSpec((1, blk_q, D), q_map),
            out_shape=out_shape,
            scratch_shapes=scratch,
            interpret=interpret,
        )(*args)
    return out.reshape(B, Hq, Sq, D)
