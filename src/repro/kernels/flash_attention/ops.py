"""Public wrapper for blockwise attention: backend dispatch + padding.

Three backends, the same explicit policy as ``kernels/unipc_update/ops.py``
(DESIGN.md §5):

* ``"pallas"``    — the compiled Pallas kernel; the production path on TPU.
* ``"interpret"`` — the same kernel under the Pallas interpreter; correct on
  any platform, slow; what CI exercises so the real kernel code runs on CPU.
* ``"jnp"``       — the pure-jnp head-major oracle (`ref.attention`); the
  right default off-TPU. (Head-major (B, H, S, D) batched matmuls make the
  attention-dominated DiT eval ~1.5x faster on CPU than the model's
  seq-major einsum at dit-i256 serving shapes — BENCH_model.json, DESIGN.md
  §11 — so the fallback is a real path, not just a test oracle.)

`select_backend` encodes the policy; `attention` applies it. Callers can pin
a backend explicitly (tests, CI, the `cfg.attention_backend` model knob) or
let the dispatcher choose by platform. Sequence lengths are padded up to
block multiples for the kernel backends: key padding is masked inside the
kernel via kv_len, query padding is sliced off. `kv_lens` gives each batch
row a key length of its own (a caption shorter than its buffer); without
it the kernel is called exactly as before.
"""

from __future__ import annotations

import jax.numpy as jnp

from . import ref
from ..dispatch import (BACKENDS, resolve_backend,  # noqa: F401 (re-export)
                        platform_select as select_backend)
from .kernel import DEFAULT_BLOCK_K, DEFAULT_BLOCK_Q, flash_attention


def attention(q, k, v, *, causal=True, window=None, kv_lens=None,
              backend=None, force_pallas=False, blk_q=DEFAULT_BLOCK_Q,
              blk_k=DEFAULT_BLOCK_K):
    """(B, Hq, Sq, D) x (B, Hkv, Skv, D) -> (B, Hq, Sq, D).

    `kv_lens`: optional int (B,), 1 <= kv_lens[b] <= Skv; row b attends to
    its first kv_lens[b] keys only.

    `backend` pins one of BACKENDS; `force_pallas` (kept for tests and
    benchmarks) means "run the kernel even off-TPU", i.e. compiled on TPU,
    interpreted elsewhere. With neither, `select_backend` chooses by
    platform.
    """
    backend = resolve_backend(backend, force_pallas, select_backend)
    if backend == "jnp":
        return ref.attention(q, k, v, causal=causal, window=window,
                             kv_lens=kv_lens)
    B, Hq, Sq, D = q.shape
    Skv = k.shape[2]
    pq = (-Sq) % blk_q
    pk = (-Skv) % blk_k
    if pq:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, pq), (0, 0)))
    if pk:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pk), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pk), (0, 0)))
    out = flash_attention(q, k, v, kv_lens, causal=causal, window=window,
                          blk_q=blk_q, blk_k=blk_k,
                          interpret=backend == "interpret", kv_len=Skv)
    return out[:, :, :Sq]
