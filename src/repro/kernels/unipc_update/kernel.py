"""Fused UniPC state update — Pallas TPU kernel.

The UniPC step is x_next = sum_k w_k * term_k over K = order+2 tensors (the
previous state, the anchor model output, and the difference buffer). The
reference implementations execute this as a chain of K pointwise ops, each a
separate kernel launch streaming the full state through HBM: 3K-1 full-state
arrays of traffic (K term reads, K-1 accumulator re-reads, K writes). At
sampling time the state is the entire image/latent batch, so the update is
purely memory-bound. This kernel streams each VMEM tile of all K terms once
and writes the result once — K+1 arrays, a (3K-1)/(K+1)x traffic reduction
(2.3x at the default order-3 K=5, approaching 3x with order; measured in
benchmarks/bench_kernels.py, argument in DESIGN.md §4).

Layout: terms (K, B, N) fp32/bf16 with N = flattened per-sample size,
viewed as (K, B, 1, N) — a TPU block's last two dims must be (8k, 128k) or
span the array, so each sample is one (1, N) lane row; 2D grid (B, N tiles)
so batched states tile directly, no flat copy. TILE is a multiple of 128
lanes; arbitrary N is handled by the boundary tile — Pallas pads the load
and masks the store for blocks that overrun the array, so no host-side
padding of the state is needed. Accumulation is always fp32, also for bf16
terms (DESIGN.md §4.2).

Weights live in SMEM as a (K, W) fp32 table read as scalars: W = 1 for
(K,) weights broadcast over the batch, or W = B for per-slot (K, B) weights
(continuous batching, DESIGN.md §9) — every batch row combines with its
*own* column, which is what lets a heterogeneous slot batch sit at
different rows of the solver table. Same kernel body: the column index just
follows the batch grid coordinate instead of staying at 0.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

TILE = 16 * 128  # lanes per grid step, a multiple of the 128-lane width


def _kernel(w_ref, t_ref, o_ref, *, per_slot):
    # w_ref: (K, W) SMEM scalars; t_ref: (K, 1, 1, TILE); o_ref: (1, 1, TILE)
    col = pl.program_id(0) if per_slot else 0
    acc = jnp.zeros(o_ref.shape[1:], jnp.float32)
    for k in range(t_ref.shape[0]):  # K is static and small (order + 2)
        acc = acc + w_ref[k, col] * t_ref[k, 0].astype(jnp.float32)
    o_ref[0] = acc.astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def fused_combine_batched(terms, weights, interpret: bool = False):
    """terms: (K, B, N) with arbitrary N; weights: (K,) or (K, B). Returns (B, N).

    Grid is (B, tiles of N); the last tile may be a padded remainder whose
    out-of-bounds elements Pallas masks on store. (K,) weights broadcast
    over the batch; (K, B) weights are per-slot — grid row b reads column b.
    """
    K, B, N = terms.shape
    per_slot = weights.ndim == 2
    w = (weights if per_slot else weights.reshape(K, 1)).astype(jnp.float32)
    out = pl.pallas_call(
        functools.partial(_kernel, per_slot=per_slot),
        grid=(B, pl.cdiv(N, TILE)),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((K, 1, 1, TILE), lambda b, i: (0, b, 0, i)),
        ],
        out_specs=pl.BlockSpec((1, 1, TILE), lambda b, i: (b, 0, i)),
        out_shape=jax.ShapeDtypeStruct((B, 1, N), terms.dtype),
        interpret=interpret,
    )(w, terms.reshape(K, B, 1, N))
    return out.reshape(B, N)


def fused_combine_flat(terms, weights, interpret: bool = False):
    """terms: (K, N), arbitrary N; weights: (K,). Returns (N,)."""
    K, N = terms.shape
    return fused_combine_batched(
        terms.reshape(K, 1, N), weights, interpret=interpret
    ).reshape(N)
