"""Pure-jnp oracle for blockwise attention (GQA, causal, sliding window)."""

import math

import jax
import jax.numpy as jnp


def attention(q, k, v, *, causal=True, window=None, kv_lens=None):
    """q: (B, Hq, Sq, D); k, v: (B, Hkv, Skv, D). fp32 softmax. `kv_lens`
    (B,) masks each batch row's keys past its own length."""
    B, Hq, Sq, D = q.shape
    Hkv, Skv = k.shape[1], k.shape[2]
    group = Hq // Hkv
    qg = q.reshape(B, Hkv, group, Sq, D)
    s = jnp.einsum("bhgqd,bhkd->bhgqk", qg.astype(jnp.float32),
                   k.astype(jnp.float32)) / math.sqrt(D)
    qpos = jnp.arange(Sq)[:, None]
    kpos = jnp.arange(Skv)[None, :]
    ok = jnp.ones((Sq, Skv), bool)
    if causal:
        ok &= kpos <= qpos
    if window is not None:
        ok &= kpos > qpos - window
    ok = ok[None, None, None]
    if kv_lens is not None:
        ok = ok & (kpos < jnp.asarray(kv_lens)[:, None, None, None, None])
    s = jnp.where(ok, s, -1e30)
    w = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhgqk,bhkd->bhgqd", w, v.astype(jnp.float32))
    return o.reshape(B, Hq, Sq, D).astype(q.dtype)
