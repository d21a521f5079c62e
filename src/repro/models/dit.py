"""DiT — the paper-native epsilon-network, TPU-adapted (DESIGN.md §7.1: the
paper's UNet checkpoints are CNNs; on TPU the standard diffusion backbone is a
patch transformer with adaLN-zero time conditioning, Peebles & Xie 2023).

Operates on pre-patchified latents (B, patch_tokens, latent_dim); class
conditioning optional (classifier-free guidance drops the class embedding).

Text conditioning (PixArt-alpha, Chen et al. 2023, arXiv:2310.00426), chosen
by the config's sizes (`caption_dim > 0`): one shared adaLN-single
projection of the time embedding plus a learned `scale_shift_table` per
block in place of the per-block adaLN matmul; a cross-attention sub-block
over the projected caption, each row's keys masked past its own caption
length; a fixed 2-D sin-cos position embedding; a bias on every dense
layer; LayerNorm eps from the config. The block scan, `_embed`, `_head`
and the fused adaLN kernels are the class-conditioned DiT's own.

Feature reuse (DESIGN.md §12): `dit_apply_cached` splits the block stack at a
static boundary `cache_block` and carries the deep segment's *residual delta*
as explicit cache state. On a full eval the deep blocks run and the delta is
recorded; on a shallow eval only the first `cache_block` blocks (plus the
final layer) recompute and the cached delta stands in for the deep segment —
the DeepCache observation that deep features drift slowly across adjacent
solver steps, applied to a residual transformer.
"""

from __future__ import annotations

import math
from typing import NamedTuple

import jax
import jax.numpy as jnp

from ..kernels.adaln_modulate import ops as adaln_ops
from ..parallel.sharding import shard
from .layers import attention_apply, attention_init, dense_apply, dense_init


def timestep_embedding(t, dim: int, max_period=10000.0):
    """t: (B,) float in [0, 1]-ish; sinusoidal features then MLP outside."""
    half = dim // 2
    freqs = jnp.exp(-math.log(max_period) * jnp.arange(half) / half)
    ang = t[:, None].astype(jnp.float32) * freqs[None] * 1000.0
    return jnp.concatenate([jnp.cos(ang), jnp.sin(ang)], axis=-1)


def _dit_block_init(rng, cfg):
    ks = jax.random.split(rng, 4)
    d = cfg.d_model
    return {
        "attn": attention_init(ks[0], cfg),
        "w1": dense_init(ks[1], d, cfg.d_ff, cfg.weight_dtype),
        "w2": dense_init(ks[2], cfg.d_ff, d, cfg.weight_dtype,
                         scale=1.0 / math.sqrt(cfg.d_ff)),
        # adaLN-zero: 6 modulation vectors, zero-init
        "ada": jnp.zeros((d, 6 * d), cfg.weight_dtype),
        "ada_b": jnp.zeros((6 * d,), cfg.weight_dtype),
    }


def _text_block_init(rng, cfg):
    ks = jax.random.split(rng, 5)
    d, wd = cfg.d_model, cfg.weight_dtype
    # the config's qkv_bias gives bq, bk, bv; PixArt's output projections
    # have a bias too
    attn, cross = (dict(attention_init(k, cfg), bo=jnp.zeros((d,), wd))
                   for k in ks[:2])
    # the cross branch enters the residual ungated: zero its output at init
    cross["wo"] = jnp.zeros_like(cross["wo"])
    return {
        "attn": attn, "cross": cross,
        "w1": dense_init(ks[2], d, cfg.d_ff, wd),
        "b1": jnp.zeros((cfg.d_ff,), wd),
        "w2": dense_init(ks[3], cfg.d_ff, d, wd,
                         scale=1.0 / math.sqrt(cfg.d_ff)),
        "b2": jnp.zeros((d,), wd),
        "scale_shift_table": (jax.random.normal(ks[4], (6, d))
                              / math.sqrt(d)).astype(wd),
    }


def init_text_dit(cfg, rng):
    """PixArt-alpha's parameter tree (the DiT's names where a weight is the
    same, `_b` for a dense layer's bias)."""
    ks = jax.random.split(rng, cfg.num_layers + 8)
    blocks = [_text_block_init(k, cfg) for k in ks[: cfg.num_layers]]
    d, C, wd = cfg.d_model, cfg.caption_dim, cfg.weight_dtype
    zeros = lambda n: jnp.zeros((n,), wd)
    return {
        "in_proj": dense_init(ks[-1], cfg.latent_dim, d, wd),
        "in_proj_b": zeros(d),
        "t_mlp1": dense_init(ks[-2], 256, d, wd), "t_mlp1_b": zeros(d),
        "t_mlp2": dense_init(ks[-3], d, d, wd), "t_mlp2_b": zeros(d),
        "t_block": dense_init(ks[-4], d, 6 * d, wd, scale=0.02),
        "t_block_b": zeros(6 * d),
        "y_proj1": dense_init(ks[-5], C, d, wd), "y_proj1_b": zeros(d),
        "y_proj2": dense_init(ks[-6], d, d, wd), "y_proj2_b": zeros(d),
        "y_embedding": (jax.random.normal(ks[-7], (cfg.caption_tokens, C))
                        / math.sqrt(C)).astype(wd),
        "blocks": jax.tree.map(lambda *xs: jnp.stack(xs), *blocks),
        "final_table": (jax.random.normal(ks[-8], (2, d))
                        / math.sqrt(d)).astype(wd),
        "out_proj": jnp.zeros((d, cfg.latent_dim), wd),
        "out_proj_b": zeros(cfg.latent_dim),
    }


def init_dit(cfg, rng, num_classes: int = 0):
    if cfg.caption_dim:
        return init_text_dit(cfg, rng)
    ks = jax.random.split(rng, cfg.num_layers + 5)
    blocks = [_dit_block_init(k, cfg) for k in ks[: cfg.num_layers]]
    d = cfg.d_model
    p = {
        "in_proj": dense_init(ks[-1], cfg.latent_dim, d, cfg.weight_dtype),
        "t_mlp1": dense_init(ks[-2], 256, d, cfg.weight_dtype),
        "t_mlp2": dense_init(ks[-3], d, d, cfg.weight_dtype),
        "blocks": jax.tree.map(lambda *xs: jnp.stack(xs), *blocks),
        "final_ada": jnp.zeros((d, 2 * d), cfg.weight_dtype),
        "final_ada_b": jnp.zeros((2 * d,), cfg.weight_dtype),
        "out_proj": jnp.zeros((d, cfg.latent_dim), cfg.weight_dtype),
    }
    if num_classes:
        p["class_embed"] = (0.02 * jax.random.normal(
            ks[-4], (num_classes + 1, d))).astype(cfg.weight_dtype)
    return p


class TextCond(NamedTuple):
    """What a text-conditioned eval conditions on, made once per eval."""
    t: jax.Array       # (B, d) float32 time embedding: the final layer's
    t0: jax.Array      # (B, 6d) float32 adaLN-single: every block's
    y: jax.Array       # (B, caption_tokens, d) projected caption
    y_len: jax.Array   # (B,) int32 caption lengths (the key mask)


def _bias(x, params, name):
    """x + params[name] where the tree has that bias (PixArt's dense
    layers), x itself where it has not (the class-conditioned DiT's)."""
    b = params.get(name)
    return x if b is None else x + b.astype(x.dtype)


def pos_embed_2d(tokens: int, d: int):
    """Fixed 2-D sin-cos position embedding (T, d) of a square patch grid
    (MAE / PixArt `get_2d_sincos_pos_embed`, base size = the grid, so the
    positions are the grid indices): the first d/2 channels embed the
    column, the last d/2 the row, each as [sin, cos] over d/4 frequencies
    10000^(-k/(d/4))."""
    g = math.isqrt(tokens)
    if g * g != tokens or d % 4:
        raise ValueError(f"pos_embed_2d needs a square grid and d % 4 == 0; "
                         f"got {tokens} tokens of {d}")
    omega = 1.0 / 10000.0 ** (jnp.arange(d // 4, dtype=jnp.float32)
                              / (d // 4))
    idx = jnp.arange(tokens)

    def one(pos):
        ang = pos.astype(jnp.float32)[:, None] * omega[None]
        return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)

    return jnp.concatenate([one(idx % g), one(idx // g)], axis=-1)


def _embed(params, cfg, x_t, t, class_ids=None, caption=None,
           caption_len=None):
    """Shared front end: patch projection + the conditioning: an adaLN
    vector (B, d) for a class-conditioned DiT, a `TextCond` for a
    text-conditioned one."""
    B = x_t.shape[0]
    text = bool(cfg.caption_dim)
    t = jnp.broadcast_to(jnp.asarray(t, jnp.float32), (B,))
    x = jnp.einsum("btl,ld->btd", x_t.astype(cfg.activation_dtype),
                   params["in_proj"].astype(cfg.activation_dtype))
    if text:
        x = _bias(x, params, "in_proj_b") + pos_embed_2d(
            x.shape[1], x.shape[2]).astype(x.dtype)
    x = shard(x, "batch", "seq", "d_model")
    c = jax.nn.silu(_bias(jnp.einsum(
        "bf,fd->bd", timestep_embedding(t, 256),
        params["t_mlp1"].astype(jnp.float32)), params, "t_mlp1_b"))
    c = jnp.einsum("bd,de->be", c, params["t_mlp2"].astype(jnp.float32))
    if text:
        c = _bias(c, params, "t_mlp2_b")
        t0 = _bias(jnp.einsum("bd,de->be", jax.nn.silu(c),
                              params["t_block"].astype(jnp.float32)),
                   params, "t_block_b")
        y = _bias(dense_apply(caption.astype(x.dtype), params["y_proj1"]),
                  params, "y_proj1_b")
        y = _bias(dense_apply(jax.nn.gelu(y), params["y_proj2"]), params,
                  "y_proj2_b")
        return x, TextCond(c, t0, y, jnp.asarray(caption_len, jnp.int32))
    if class_ids is not None and "class_embed" in params:
        c = c + params["class_embed"].astype(jnp.float32)[class_ids]
    c = jax.nn.silu(c).astype(x.dtype)
    return x, c


def _block_body(cfg, c, adaln, tap=None):
    """Scan body over the stacked block params (fused adaLN, DESIGN.md §11).
    Every dense site goes through `dense_apply`, so a quantized param tree
    (models/quant.py records) routes through kernels/quant_matmul with no
    change here. `tap` is the calibration hook — None (the default) in every
    serving/training path, a per-site absmax recorder when models/quant.py
    replays the forward unrolled."""

    text = isinstance(c, TextCond)
    eps = cfg.norm_eps

    def body(h, bp):
        if text:
            # adaLN-single: the shared projection plus this block's table
            mod = (c.t0 + bp["scale_shift_table"].reshape(-1).astype(
                jnp.float32)).astype(h.dtype)
        else:
            if tap is not None:
                tap("ada", c)
            mod = dense_apply(c, bp["ada"], cfg) + bp["ada_b"].astype(h.dtype)
        sh1, sc1, g1, sh2, sc2, g2 = jnp.split(mod, 6, axis=-1)
        hn = adaln_ops.modulate(h, sh1, sc1, eps=eps, backend=adaln)
        a = attention_apply(bp["attn"], hn, cfg, causal=False, rope=False,
                            tap=tap)
        h = adaln_ops.gate_residual(h, g1, a, backend=adaln)
        if text:
            # cross-attention over the caption: no norm before, no gate
            h = h + attention_apply(bp["cross"], h, cfg, kv_src=c.y,
                                    causal=False, rope=False,
                                    kv_lens=c.y_len)
        hn = adaln_ops.modulate(h, sh2, sc2, eps=eps, backend=adaln)
        if tap is not None:
            tap("mlp_in", hn)
        y = jax.nn.gelu(_bias(dense_apply(hn, bp["w1"], cfg), bp, "b1"))
        if tap is not None:
            tap("mlp_mid", y)
        y = _bias(dense_apply(y, bp["w2"], cfg), bp, "b2")
        return adaln_ops.gate_residual(h, g2, y, backend=adaln), None

    return body


def _head(params, cfg, x, c, adaln, tap=None):
    """Final adaLN + output projection back to latent width. A text DiT
    modulates from its table plus the time embedding itself."""
    if isinstance(c, TextCond):
        mod = (params["final_table"].astype(jnp.float32)[None]
               + c.t[:, None]).reshape(c.t.shape[0], -1).astype(x.dtype)
    else:
        if tap is not None:
            tap("final_ada", c)
        mod = (dense_apply(c, params["final_ada"], cfg)
               + params["final_ada_b"].astype(x.dtype))
    sh, sc = jnp.split(mod, 2, axis=-1)
    x = adaln_ops.modulate(x, sh, sc, eps=cfg.norm_eps, backend=adaln)
    return _bias(jnp.einsum("btd,dl->btl", x,
                            params["out_proj"].astype(x.dtype)),
                 params, "out_proj_b")


def dit_apply(params, cfg, x_t, t, class_ids=None, caption=None,
              caption_len=None):
    """x_t: (B, T, latent_dim); t: scalar or (B,). Returns eps-hat, same
    shape. A text-conditioned config takes `caption` (B, caption_tokens,
    caption_dim) and `caption_len` (B,) in place of class ids."""
    adaln = getattr(cfg, "adaln_backend", None)
    x, c = _embed(params, cfg, x_t, t, class_ids, caption, caption_len)
    x, _ = jax.lax.scan(_block_body(cfg, c, adaln), x, params["blocks"])
    return _head(params, cfg, x, c, adaln)


def dit_cache_shape(cfg):
    """Per-sample shape of the deep-feature cache (the residual delta of the
    blocks past the cache boundary): one (T, d_model) array per slot."""
    return (cfg.patch_tokens, cfg.d_model)


def dit_apply_cached(params, cfg, x_t, t, class_ids=None, *, cache,
                     reuse=None, cache_block: int):
    """DiT eval with a deep-feature cache at a static block boundary.

    cache: (B, T, d_model) — the deep segment's residual delta
        (x_after_all_blocks − x_after_cache_block) recorded at each sample's
        last full eval. Zero-init is safe: the first eval of a trajectory
        must be a full one (the table's init row always is).
    reuse: scalar or (B,) flag, 1 = shallow eval (reuse the cached delta and
        recompute only the first `cache_block` blocks + the final layer),
        0 = full eval (recompute everything, refresh the cache). None = 0.
    cache_block: static split index k, 1 <= k < num_layers.

    Returns (eps_hat, new_cache). With reuse = 0 everywhere the deep scan
    runs and the output is BIT-IDENTICAL to `dit_apply` at fp32 (the shallow
    and deep block scans chain the same body over the same stacked params).
    The deep segment only executes when some sample in the batch needs a
    full eval (`lax.cond` on the batch-reduced flag), so an all-shallow tick
    pays k blocks instead of num_layers.
    """
    L = int(cfg.num_layers)
    k = int(cache_block)
    if not 1 <= k < L:
        raise ValueError(f"cache_block must be in 1..{L - 1} "
                         f"(num_layers={L}), got {k}")
    adaln = getattr(cfg, "adaln_backend", None)
    x, c = _embed(params, cfg, x_t, t, class_ids)
    body = _block_body(cfg, c, adaln)
    shallow = jax.tree.map(lambda a: a[:k], params["blocks"])
    deep = jax.tree.map(lambda a: a[k:], params["blocks"])
    x_k, _ = jax.lax.scan(body, x, shallow)

    B = x_t.shape[0]
    reuse = (jnp.zeros((B,), jnp.float32) if reuse is None
             else jnp.broadcast_to(jnp.asarray(reuse, jnp.float32), (B,)))
    need_deep = jnp.any(reuse < 0.5)
    x_deep = jax.lax.cond(
        need_deep,
        lambda xk: jax.lax.scan(body, xk, deep)[0],
        lambda xk: xk,  # all-shallow tick: deep blocks skipped entirely
        x_k)
    cache = cache.astype(x_k.dtype)
    r = (reuse > 0.5).reshape((B,) + (1,) * (x_k.ndim - 1))
    # full slots take the freshly computed deep output (exact — never
    # reconstructed through the delta) and refresh their cache; shallow
    # slots approximate it as x_k + cached delta and keep their cache
    x_out = jnp.where(r, x_k + cache, x_deep)
    new_cache = jnp.where(r, cache, x_deep - x_k)
    return _head(params, cfg, x_out, c, adaln), new_cache
