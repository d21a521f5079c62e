"""Shared neural building blocks (pure-JAX, dict-pytree params).

Conventions:
  params are nested dicts of jnp arrays; init fns take an `rng` and a config;
  apply fns are pure. Weights use einsum contractions so GSPMD propagates the
  logical-axis shardings annotated via parallel.sharding.shard().
"""

from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp

from ..parallel.sharding import shard


def _normal(rng, shape, scale, dtype):
    return (scale * jax.random.normal(rng, shape, jnp.float32)).astype(dtype)


def dense_init(rng, d_in, d_out, dtype, scale=None):
    scale = scale if scale is not None else 1.0 / math.sqrt(d_in)
    return _normal(rng, (d_in, d_out), scale, dtype)


def dense_apply(x, w, cfg=None):
    """x (..., K) @ w — the one dense contraction every weight site routes
    through. `w` is either a raw (K, N) array (the unchanged float path) or
    a quant record ``{"qw", "ws"[, "sa"]}`` installed by
    `models.quant.quantize_params`, which routes through the
    kernels/quant_matmul package (DESIGN.md §14). The check is structural
    and static per trace, so unquantized models pay nothing."""
    if isinstance(w, dict):
        from ..kernels.quant_matmul import ops as qmm_ops

        return qmm_ops.quant_matmul(
            x, w["qw"], w["ws"], sa=w.get("sa"),
            backend=getattr(cfg, "quant_backend", None))
    return jnp.einsum("...k,kn->...n", x, w.astype(x.dtype))


# ---------------------------------------------------------------------------
# norms
# ---------------------------------------------------------------------------

def rmsnorm_init(d, dtype):
    return {"w": jnp.ones((d,), dtype)}


def rmsnorm(params, x, eps=1e-5):
    var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True)
    y = x * jax.lax.rsqrt(var + eps).astype(x.dtype)
    return y * params["w"].astype(x.dtype)


def layernorm_init(d, dtype):
    return {"w": jnp.ones((d,), dtype), "b": jnp.zeros((d,), dtype)}


def layernorm(params, x, eps=1e-5):
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    y = ((xf - mu) * jax.lax.rsqrt(var + eps)).astype(x.dtype)
    if params:
        y = y * params["w"].astype(y.dtype) + params["b"].astype(y.dtype)
    return y


def nonparam_ln(params, x, eps=1e-5):
    """OLMo-style non-parametric LayerNorm (no learnable affine)."""
    return layernorm({}, x, eps)


NORMS = {
    "rmsnorm": (rmsnorm_init, rmsnorm),
    "layernorm": (layernorm_init, layernorm),
    "nonparam_ln": (lambda d, dt: {}, nonparam_ln),
}


def make_norm(cfg):
    init, apply = NORMS[cfg.norm]
    return (lambda rng=None: init(cfg.d_model, cfg.weight_dtype)), apply


# ---------------------------------------------------------------------------
# rotary position embeddings
# ---------------------------------------------------------------------------

def rope_freqs(head_dim: int, theta: float):
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))


def apply_rope(x, positions, theta: float):
    """x: (B, S, H, D); positions: (B, S) int32."""
    d = x.shape[-1]
    freqs = rope_freqs(d, theta)  # (D/2,)
    ang = positions[..., None].astype(jnp.float32) * freqs  # (B, S, D/2)
    cos = jnp.cos(ang)[:, :, None, :]
    sin = jnp.sin(ang)[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# MLP (SwiGLU / GELU)
# ---------------------------------------------------------------------------

def mlp_init(rng, cfg, d_ff=None):
    d, f = cfg.d_model, d_ff or cfg.d_ff
    dt = cfg.weight_dtype
    ks = jax.random.split(rng, 3)
    if cfg.act == "swiglu":
        return {
            "w_gate": dense_init(ks[0], d, f, dt),
            "w_up": dense_init(ks[1], d, f, dt),
            "w_down": dense_init(ks[2], f, d, dt, scale=1.0 / math.sqrt(f)),
        }
    return {
        "w_up": dense_init(ks[1], d, f, dt),
        "w_down": dense_init(ks[2], f, d, dt, scale=1.0 / math.sqrt(f)),
    }


def mlp_apply(params, x, cfg):
    if cfg.act == "swiglu":
        g = dense_apply(x, params["w_gate"], cfg)
        u = dense_apply(x, params["w_up"], cfg)
        h = jax.nn.silu(g) * u
    else:
        h = jax.nn.gelu(dense_apply(x, params["w_up"], cfg))
    h = shard(h, "batch", "seq", "d_ff")
    return dense_apply(h, params["w_down"], cfg)


# ---------------------------------------------------------------------------
# attention (GQA, causal / bidirectional / sliding-window / cross)
# ---------------------------------------------------------------------------

def attention_init(rng, cfg, d_kv_src: Optional[int] = None):
    d = cfg.d_model
    hq, hkv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    dt = cfg.weight_dtype
    d_src = d_kv_src or d
    ks = jax.random.split(rng, 4)
    p = {
        "wq": dense_init(ks[0], d, hq * hd, dt),
        "wk": dense_init(ks[1], d_src, hkv * hd, dt),
        "wv": dense_init(ks[2], d_src, hkv * hd, dt),
        "wo": dense_init(ks[3], hq * hd, d, dt),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((hq * hd,), dt)
        p["bk"] = jnp.zeros((hkv * hd,), dt)
        p["bv"] = jnp.zeros((hkv * hd,), dt)
    return p


def _proj_qkv(params, x, kv_src, cfg, tap=None):
    hq, hkv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    if tap is not None:  # calibration hook (models/quant.py); None in serving
        tap("qkv", x)
    q = dense_apply(x, params["wq"], cfg)
    k = dense_apply(kv_src, params["wk"], cfg)
    v = dense_apply(kv_src, params["wv"], cfg)
    if "bq" in params:
        q = q + params["bq"].astype(x.dtype)
        k = k + params["bk"].astype(x.dtype)
        v = v + params["bv"].astype(x.dtype)
    B, S = x.shape[:2]
    Skv = kv_src.shape[1]
    return (q.reshape(B, S, hq, hd), k.reshape(B, Skv, hkv, hd),
            v.reshape(B, Skv, hkv, hd))


def sdpa(q, k, v, *, causal, q_positions=None, kv_positions=None,
         sliding_window=None):
    """Grouped-query scaled dot-product attention, pure-jnp path.

    q: (B, Sq, Hq, D); k, v: (B, Skv, Hkv, D) with Hq % Hkv == 0.
    Masks are built from positions so the same code serves prefill (Sq == Skv),
    decode (Sq == 1 against a cache), and cross-attention (causal=False).
    """
    B, Sq, Hq, D = q.shape
    Hkv = k.shape[2]
    group = Hq // Hkv
    qg = q.reshape(B, Sq, Hkv, group, D)
    logits = jnp.einsum("bqhgd,bkhd->bhgqk", qg, k).astype(jnp.float32)
    logits = logits / math.sqrt(D)
    if causal or sliding_window is not None:
        qp = (q_positions if q_positions is not None
              else jnp.arange(Sq))[:, None]           # (Sq, 1)
        kp = (kv_positions if kv_positions is not None
              else jnp.arange(k.shape[1]))[None, :]   # (1, Skv)
        ok = jnp.ones((Sq, k.shape[1]), bool)
        if causal:
            ok &= kp <= qp
        if sliding_window is not None:
            ok &= kp > qp - sliding_window
        logits = jnp.where(ok[None, None, None], logits, -1e30)
    w = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    out = jnp.einsum("bhgqk,bkhd->bqhgd", w, v)
    return out.reshape(B, Sq, Hq, D)


def chunked_sdpa(q, k, v, *, causal, sliding_window=None, chunk=1024):
    """Flash-style attention expressed in XLA: lax.scan over query chunks, so
    the logits footprint is O(chunk * Skv) instead of O(Sq * Skv). Exact (same
    softmax), blockwise — the §Perf memory-bound hillclimb for long prefill
    (EXPERIMENTS.md H3). The Pallas kernel (kernels/flash_attention) is the
    TPU-native version; this path is what the XLA dry-run lowers.

    Arbitrary Sq: a non-multiple tail is handled by padding the queries up to
    the chunk boundary — query rows are independent, the padded rows carry
    real past-the-end positions (a causal pad row attends to everything, its
    softmax stays finite) and their outputs are sliced off."""
    B, Sq, Hq, D = q.shape
    Skv = k.shape[1]
    pad = (-Sq) % chunk
    if pad:
        q = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0)))
    nq = (Sq + pad) // chunk
    qc = jnp.moveaxis(q.reshape(B, nq, chunk, Hq, D), 1, 0)
    kp = jnp.arange(Skv)

    def body(_, inp):
        qi, idx = inp
        qpos = idx * chunk + jnp.arange(chunk)
        out = sdpa(qi, k, v, causal=causal, q_positions=qpos,
                   kv_positions=kp, sliding_window=sliding_window)
        return None, out

    _, outs = jax.lax.scan(body, None, (qc, jnp.arange(nq)))
    return jnp.moveaxis(outs, 0, 1).reshape(B, Sq + pad, Hq, D)[:, :Sq]


def attention_apply(params, x, cfg, *, kv_src=None, causal=True, positions=None,
                    kv_positions=None, sliding_window=None, rope=True,
                    kv_lens=None, tap=None):
    """Full-sequence attention (training / prefill without cache).
    `kv_lens` (B,) masks each row's keys past its own length (a padded
    caption under cross-attention); an output bias `bo` in `params` is
    added after the output projection."""
    kv_src = x if kv_src is None else kv_src
    q, k, v = _proj_qkv(params, x, kv_src, cfg, tap=tap)
    if rope:
        B, S = x.shape[:2]
        pos = positions if positions is not None else jnp.broadcast_to(
            jnp.arange(S), (B, S))
        q = apply_rope(q, pos, cfg.rope_theta)
        kv_pos = kv_positions if kv_positions is not None else jnp.broadcast_to(
            jnp.arange(k.shape[1]), (B, k.shape[1]))
        k = apply_rope(k, kv_pos, cfg.rope_theta)
    q = shard(q, "batch", "seq", "heads", None)
    k = shard(k, "batch", "seq", "kv_heads", None)
    chunk = getattr(cfg, "attention_chunk", 0)
    if chunk and q.shape[1] > chunk:
        # the XLA long-prefill hillclimb; remainder chunks handled by padding
        out = chunked_sdpa(q, k, v, causal=causal,
                           sliding_window=sliding_window, chunk=chunk)
    else:
        # the fast-eval path (DESIGN.md §11): kernels/flash_attention with
        # the explicit pallas|interpret|jnp policy — the Pallas kernel on
        # TPU, the head-major jnp oracle elsewhere (measured faster on CPU
        # than the seq-major sdpa einsum at DiT serving shapes). sdpa stays
        # the decode-path / positions-aware reference.
        from ..kernels.flash_attention import ops as fa_ops

        out = fa_ops.attention(
            q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
            v.transpose(0, 2, 1, 3), causal=causal, window=sliding_window,
            kv_lens=kv_lens, backend=getattr(cfg, "attention_backend", None),
        ).transpose(0, 2, 1, 3)
    B, S = x.shape[:2]
    out = out.reshape(B, S, cfg.num_heads * cfg.head_dim)
    if tap is not None:
        tap("wo", out)
    out = dense_apply(out, params["wo"], cfg)
    if "bo" in params:
        out = out + params["bo"].astype(out.dtype)
    return out


def maybe_remat(body, cfg):
    """Wrap a scan body with activation checkpointing when cfg.remat is set."""
    if getattr(cfg, "remat", False):
        return jax.checkpoint(body, prevent_cse=False)
    return body
