"""Blockwise (flash) attention — Pallas TPU kernel.

The grid is (batch * q_heads / heads, num_q_blocks, num_kv_blocks): one
step takes `heads` heads of one batch row against one q block and one kv
block. GQA is expressed in the BlockSpec index maps (query head h reads kv
head h // group of the K/V operands) — no materialized head repetition.

Where all of a row's keys fit one kv block, a step runs one plain softmax
over them. Otherwise the kv axis is the innermost sequential grid dimension,
carrying the running (max, denom, acc) of an online softmax in VMEM scratch;
causal and sliding-window masks are applied per tile, and fully-masked
tiles are skipped with pl.when.

The MXU takes q, k and v in their own dtype and accumulates in float32
(bf16 products are exact in float32); the scores, max, exp and sums stay
float32, and the probabilities enter the PV dot in v's dtype, as the
model's own attention casts its softmax.

Cross-attention over a padded context (a caption shorter than its buffer)
passes `kv_lens`, one key length per batch row, as a scalar-prefetch
operand: each row masks its keys past its own length; with several kv
blocks, those wholly past it are skipped, and their index map repeats the
row's last live block so no DMA fetches them.

`tile_plan` picks the blocks from the call's shapes and dtype, never from a
model's name. Keys that fit the budgets below take one kv block, their
length rounded up to the dtype's sublane multiple (no padding to 128); the
q block is as large as a step's score and VMEM budgets allow, in as few
equal blocks as cover the queries; a step takes more heads of its row until
it holds WORK_PER_STEP scores or its blocks fill IO_BYTES. Causal and
sliding-window calls, and keys past the budgets, take online-softmax tiles
of up to 512 so whole tiles can be skipped.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
LANES = 128
SCORES_PER_STEP = 1 << 20   # f32 scores of one head in a step: 4 MiB
IO_BYTES = 8 << 20          # a step's q, k, v and out blocks, double-buffered
WORK_PER_STEP = 1 << 21     # score elements a step gathers heads up to
ONLINE_BLOCK = 512          # largest q and kv tile of the online softmax


def _round_up(n, m):
    return -(-n // m) * m


def _even_block(n, cap, mult):
    """The block, a multiple of `mult` and at most `cap` (itself a
    multiple), that covers n in as few equal blocks as possible."""
    return _round_up(-(-n // -(-n // cap)), mult)


def tile_plan(Sq, Skv, D, Hq, Hkv, dtype, causal, window):
    """(blk_q, blk_k, heads): the q and kv blocks and the heads of one row
    that a grid step takes, from the call's shapes alone."""
    itemsize = jnp.dtype(dtype).itemsize
    sub = 32 // itemsize     # rows of one (sublane, 128-lane) VMEM tile
    row_bytes = 4 * _round_up(D, LANES) * itemsize

    def io_bytes(heads, blk_q, blk_k):
        return heads * (blk_q + blk_k) * row_bytes

    keys = _round_up(Skv, sub)
    if (causal or window is not None or keys * LANES > SCORES_PER_STEP
            or io_bytes(1, LANES, keys) > IO_BYTES):
        blk_k = _even_block(Skv, ONLINE_BLOCK, LANES if Skv > ONLINE_BLOCK
                            else sub)
        return _even_block(Sq, ONLINE_BLOCK, sub), blk_k, 1
    cap = min(SCORES_PER_STEP // keys, IO_BYTES // row_bytes - keys)
    blk_q = _even_block(Sq, cap // sub * sub, sub)
    group = Hq // Hkv
    ok = [h for h in range(1, Hq + 1)
          if Hq % h == 0 and (h == 1 or h % group == 0)
          and io_bytes(h, blk_q, keys) <= IO_BYTES]
    heads = next((h for h in ok if h * blk_q * keys >= WORK_PER_STEP), ok[-1])
    return blk_q, keys, heads


def _softmax_kernel(*refs, scale, causal, window, blk_q, kv_len, heads,
                    group, row_heads, per_row):
    """One step with every key in one block: a plain softmax for each of
    `heads` heads. With `per_row`, the first ref holds the per-row key
    lengths (scalar prefetch)."""
    if per_row:
        lens_ref, q_ref, k_ref, v_ref, o_ref = refs
        kv_len = lens_ref[pl.program_id(0) * heads // row_heads]
    else:
        q_ref, k_ref, v_ref, o_ref = refs
    blk_k = k_ref.shape[1]
    kpos = jax.lax.broadcasted_iota(jnp.int32, (1, blk_k), 1)
    qpos = pl.program_id(1) * blk_q + jax.lax.broadcasted_iota(
        jnp.int32, (blk_q, 1), 0)
    masks = []
    if per_row or kv_len < blk_k:
        masks.append(kpos < kv_len)
    if causal:
        masks.append(kpos <= qpos)
    if window is not None:
        masks.append(kpos > qpos - window)
    ok = functools.reduce(jnp.logical_and, masks) if masks else None
    for j in range(heads):
        v = v_ref[j // group]
        s = jax.lax.dot_general(q_ref[j], k_ref[j // group],
                                (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        if ok is not None:
            s = jnp.where(ok, s, NEG_INF)
        # scaled after the max is taken: the same ops, masked or not
        p = jnp.exp((s - jnp.max(s, axis=1, keepdims=True)) * scale)
        l = jnp.sum(p, axis=1, keepdims=True)
        o = jax.lax.dot(p.astype(v.dtype), v,
                        preferred_element_type=jnp.float32)
        o_ref[j] = (o / l).astype(o_ref.dtype)


def _online_kernel(*refs, scale, causal, window, blk_q, blk_k, kv_len,
                   row_heads, per_row):
    """One (row·head, q block, kv block) tile of the online softmax. With
    `per_row`, the first ref holds the per-row key lengths."""
    if per_row:
        lens_ref, q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr = refs
        kv_len = lens_ref[pl.program_id(0) // row_heads]
    else:
        q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr = refs
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
    if per_row:
        run = jnp.logical_and(run, k_start < kv_len)

    @pl.when(run)
    def _body():
        v = v_ref[0]
        s = jax.lax.dot_general(q_ref[0], k_ref[0], (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
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
        acc_scr[:] = acc_scr[:] * alpha[:, None] + jax.lax.dot(
            p.astype(v.dtype), v, preferred_element_type=jnp.float32)
        m_scr[:] = m_cur

    @pl.when(ki == nk - 1)
    def _finish():
        denom = jnp.maximum(l_scr[:], 1e-30)
        o_ref[0] = (acc_scr[:] / denom[:, None]).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "blk_q", "blk_k", "heads",
                     "interpret", "kv_len"))
def flash_attention(q, k, v, kv_lens=None, *, causal=True, window=None,
                    blk_q, blk_k, heads=1, interpret=True, kv_len=None):
    """q: (B, Hq, Sq, D); k, v: (B, Hkv, Skv, D); Hq % Hkv == 0.

    Sq must be a multiple of blk_q and Skv of blk_k (pad upstream in ops.py;
    kv_len = the unpadded key length, padded keys are masked). `heads`
    divides Hq and is 1 or a multiple of the GQA group; more than one head
    a step needs a single kv block. `kv_lens`, int32 (B,) with
    1 <= kv_lens[b] <= kv_len, gives each batch row its own key length in
    place of `kv_len`.
    """
    B, Hq, Sq, D = q.shape
    Hkv, Skv = k.shape[1], k.shape[2]
    kv_len = kv_len if kv_len is not None else Skv
    group = Hq // Hkv
    nk = Skv // blk_k
    if heads > 1 and (nk > 1 or Hq % heads or heads % group):
        raise ValueError(f"{heads} heads a step needs one kv block and "
                         f"whole GQA groups of one row (Hq {Hq}, Hkv {Hkv}, "
                         f"{nk} kv blocks)")
    kv_heads = max(heads // group, 1)
    per_row = kv_lens is not None
    grid = (B * Hq // heads, Sq // blk_q, nk)

    def q_map(bh, qi, ki, *_):
        return (bh, qi, 0)

    def kv_map(bh, qi, ki, *lens):
        if lens:
            # past the row's last live block, stay on it: the kernel skips
            # the tile and an unchanged block index fetches nothing
            live = jnp.maximum((lens[0][bh * heads // Hq] - 1) // blk_k, 0)
            ki = jnp.minimum(ki, live)
        return (bh * heads // group // kv_heads, ki, 0)

    common = dict(scale=1.0 / math.sqrt(D), causal=causal, window=window,
                  blk_q=blk_q, kv_len=kv_len, row_heads=Hq, per_row=per_row)
    if nk == 1:
        kernel = functools.partial(_softmax_kernel, heads=heads, group=group,
                                   **common)
        scratch = []
    else:
        kernel = functools.partial(_online_kernel, blk_k=blk_k, **common)
        scratch = [pltpu.VMEM((blk_q,), jnp.float32),
                   pltpu.VMEM((blk_q,), jnp.float32),
                   pltpu.VMEM((blk_q, D), jnp.float32)]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=int(per_row),
        grid=grid,
        in_specs=[pl.BlockSpec((heads, blk_q, D), q_map),
                  pl.BlockSpec((kv_heads, blk_k, D), kv_map),
                  pl.BlockSpec((kv_heads, blk_k, D), kv_map)],
        out_specs=pl.BlockSpec((heads, blk_q, D), q_map),
        scratch_shapes=scratch)
    args = (q.reshape(B * Hq, Sq, D), k.reshape(B * Hkv, Skv, D),
            v.reshape(B * Hkv, Skv, D))
    if per_row:
        args = (kv_lens.astype(jnp.int32),) + args
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B * Hq, Sq, D), q.dtype),
        interpret=interpret,
    )(*args)
    return out.reshape(B, Hq, Sq, D)
