"""Plain float32 reference for a served PixArt-alpha under guided UniPC.

Independent of the system under test: it imports only jax, numpy and the
DiT reference's schedule, time features, mantissa rounding and UniPC plan
(bench/models/dit_ref.py), and follows the published description, not the
program's code.

- Denoiser: PixArt-alpha (Chen et al. 2023, arXiv:2310.00426; the
  checkpoint PixArt-alpha/PixArt-XL-2-512x512). With LN a LayerNorm without
  affine parameters (eps from the configuration, 1e-6 published) and
  t2i_modulate(x, shift, scale) = x (1 + scale) + shift:

      t_emb = MLP(sinusoid_256(t))                    (Linear, SiLU, Linear)
      t0    = Linear(SiLU(t_emb))                     (6 d, once per eval)
      y     = Linear(GELU_tanh(Linear(caption)))      (once per eval)
      x     = Linear(patches) + pos_embed_2d
      each block, with (shift, scale, gate) x 2 = table + t0:
        x += gate_msa * SelfAttn(t2i_modulate(LN(x), shift_msa, scale_msa))
        x += CrossAttn(q = x, kv = y, keys past the caption's length masked)
        x += gate_mlp * MLP(t2i_modulate(LN(x), shift_mlp, scale_mlp))
      final: (shift, scale) = table + t_emb; Linear(t2i_modulate(LN(x), ...))

  Every Linear has a bias; the MLP is d -> d_ff -> d with GELU in its tanh
  form. Every matmul runs at HIGHEST precision (true float32 on a TPU),
  softmax and LayerNorm in float32.
- Guidance: classifier-free, eps = (1 + g) eps(caption) - g eps(null), the
  null caption the learned `y_embedding` under the request's own caption
  length, as PixArt's sampling scripts pair them.
- Sampler: multistep UniPC-p B2(h) in data prediction on the VP linear-beta
  schedule, with the DiT reference's coefficients (`unipc_plan`).

Departures, all taken from the configuration as the repository states it:
the inputs are pre-patchified tokens (the 2x2 patch convolution is the same
linear map), and only the eps half of the published 8 output channels is
computed (the learned-variance half is discarded by an ODE sampler).
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from bench.models.dit_ref import (HI, round_mantissa, time_features,
                                  unipc_plan)


# ---------------------------------------------------------------------------
# weights: names and shapes of the denoiser's parameters
# ---------------------------------------------------------------------------

def param_specs(model: dict) -> dict:
    """{path: (shape, std)} for every weight of the denoiser. Dense layers
    get 1/sqrt(fan_in); biases `bias_std`; the adaLN-single projection a
    gain of its own (zero at a fresh init, which would leave every block's
    modulation at its table); the tables `table_std`; the ungated cross
    branch's output projection `cross_gain` / sqrt(fan_in); the learned
    null caption `null_std`, the scale of a caption's own rows."""
    d, f, L = model["d_model"], model["d_ff"], model["latent_dim"]
    n, hd, H = model["num_layers"], model["head_dim"], model["num_heads"]
    C, Tc = model["caption_dim"], model["caption_tokens"]
    w = model["weights"]
    a = H * hd
    fan = lambda k: 1.0 / math.sqrt(k)
    b = w["bias_std"]
    specs = {
        "backbone/in_proj": ((L, d), fan(L)),
        "backbone/in_proj_b": ((d,), b),
        "backbone/t_mlp1": ((model["time_features"], d),
                            fan(model["time_features"])),
        "backbone/t_mlp1_b": ((d,), b),
        "backbone/t_mlp2": ((d, d), fan(d)),
        "backbone/t_mlp2_b": ((d,), b),
        "backbone/t_block": ((d, 6 * d), w["ada_gain"] / math.sqrt(d)),
        "backbone/t_block_b": ((6 * d,), b),
        "backbone/y_proj1": ((C, d), fan(C)),
        "backbone/y_proj1_b": ((d,), b),
        "backbone/y_proj2": ((d, d), fan(d)),
        "backbone/y_proj2_b": ((d,), b),
        "backbone/y_embedding": ((Tc, C), w["null_std"]),
        "backbone/blocks/w1": ((n, d, f), fan(d)),
        "backbone/blocks/b1": ((n, f), b),
        "backbone/blocks/w2": ((n, f, d), fan(f)),
        "backbone/blocks/b2": ((n, d), b),
        "backbone/blocks/scale_shift_table": ((n, 6, d), w["table_std"]),
        "backbone/final_table": ((2, d), w["table_std"]),
        "backbone/out_proj": ((d, L), fan(d)),
        "backbone/out_proj_b": ((L,), b),
    }
    for branch in ("attn", "cross"):
        out = w["cross_gain"] if branch == "cross" else 1.0
        specs.update({
            f"backbone/blocks/{branch}/wq": ((n, d, a), fan(d)),
            f"backbone/blocks/{branch}/wk": ((n, d, a), fan(d)),
            f"backbone/blocks/{branch}/wv": ((n, d, a), fan(d)),
            f"backbone/blocks/{branch}/wo": ((n, a, d), out * fan(a)),
            f"backbone/blocks/{branch}/bq": ((n, a), b),
            f"backbone/blocks/{branch}/bk": ((n, a), b),
            f"backbone/blocks/{branch}/bv": ((n, a), b),
            f"backbone/blocks/{branch}/bo": ((n, d), b),
        })
    return specs


# ---------------------------------------------------------------------------
# denoiser
# ---------------------------------------------------------------------------

def pos_embed(tokens: int, d: int) -> np.ndarray:
    """MAE's `get_2d_sincos_pos_embed` as PixArt calls it at 512 px (grid
    32, base size 32, interpolation scale 1), in float64: (tokens, d)."""
    g = int(round(math.sqrt(tokens)))
    grid_h = np.arange(g, dtype=np.float64)
    grid_w = np.arange(g, dtype=np.float64)
    grid = np.stack(np.meshgrid(grid_w, grid_h), axis=0).reshape(2, 1, g, g)

    def one_d(dim, pos):
        omega = np.arange(dim // 2, dtype=np.float64) / (dim / 2.0)
        omega = 1.0 / 10000 ** omega
        out = np.einsum("m,d->md", pos.reshape(-1), omega)
        return np.concatenate([np.sin(out), np.cos(out)], axis=1)

    return np.concatenate([one_d(d // 2, grid[0]), one_d(d // 2, grid[1])],
                          axis=1)


def _lin(x, p, name, bits=None):
    """x @ p[name] + p[name + "_b"] (PixArt's dense layers all have one)."""
    q = partial(round_mantissa, bits=bits)
    return jnp.einsum("...k,kn->...n", q(x), q(p[name]),
                      precision=HI) + p[name + "_b"]


def _ln_mod(x, shift, scale, eps):
    """t2i_modulate(LN(x), shift, scale); shift/scale (B, d)."""
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    y = (x - mu) / jnp.sqrt(var + eps)
    return y * (1.0 + scale[:, None]) + shift[:, None]


def _gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(math.sqrt(2.0 / math.pi)
                                     * (x + 0.044715 * x ** 3)))


def _attention(x, ctx, p, heads, head_dim, key_ok=None, bits=None):
    """Multi-head attention of x over ctx with biased projections; `key_ok`
    (B, S_ctx) masks keys out."""
    B, T, _ = x.shape
    S = ctx.shape[1]
    r = partial(round_mantissa, bits=bits)
    pb = {"q": p["wq"], "q_b": p["bq"], "k": p["wk"], "k_b": p["bk"],
          "v": p["wv"], "v_b": p["bv"], "o": p["wo"], "o_b": p["bo"]}
    q = r(_lin(x, pb, "q", bits)).reshape(B, T, heads, head_dim)
    k = r(_lin(ctx, pb, "k", bits)).reshape(B, S, heads, head_dim)
    v = r(_lin(ctx, pb, "v", bits)).reshape(B, S, heads, head_dim)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k, precision=HI) / math.sqrt(head_dim)
    if key_ok is not None:
        s = jnp.where(key_ok[:, None, None, :], s, -jnp.inf)
    a = r(jax.nn.softmax(s, axis=-1))
    o = jnp.einsum("bhqk,bkhd->bqhd", a, v, precision=HI)
    return _lin(o.reshape(B, T, heads * head_dim), pb, "o", bits)


@partial(jax.jit, static_argnames=("heads", "head_dim", "eps", "bits"))
def pixart_eps(params, x, tfeat, captions, caption_lens, *, heads, head_dim,
               eps, bits=None):
    """eps-hat for latents x (B, T, L) at one timestep, each row under its
    caption (B, caption_tokens, caption_dim) of `caption_lens` (B,) tokens.
    `tfeat` (256,) are the sinusoidal timestep features.

    `bits` computes the same network at a lower precision: every matmul
    operand, the conditioning, the attention probabilities, the modulated
    activations and the residual stream rounded to that many mantissa bits
    (accumulation stays float32): the control the benchmark's comparison
    has to fail. None is the reference itself."""
    p = params["backbone"]
    r = partial(round_mantissa, bits=bits)
    B, T, _ = x.shape
    d = p["in_proj"].shape[1]
    h = r(_lin(x, p, "in_proj", bits)
          + jnp.asarray(pos_embed(T, d), jnp.float32))
    t_emb = _lin(jax.nn.silu(_lin(tfeat[None], p, "t_mlp1")), p, "t_mlp2")
    t0 = _lin(jax.nn.silu(t_emb), p, "t_block", bits).reshape(1, 6, d)
    y = r(_lin(r(_gelu_tanh(_lin(captions, p, "y_proj1", bits))), p,
               "y_proj2", bits))
    key_ok = (jnp.arange(captions.shape[1])[None]
              < jnp.asarray(caption_lens)[:, None])

    def block(h, bp):
        mod = r(bp["scale_shift_table"][None] + t0)         # (1, 6, d)
        sh1, sc1, g1, sh2, sc2, g2 = (mod[:, i] for i in range(6))
        hn = r(_ln_mod(h, sh1, sc1, eps))
        a = _attention(hn, hn, bp["attn"], heads, head_dim, bits=bits)
        h = r(h + g1[:, None] * a)
        h = r(h + _attention(h, y, bp["cross"], heads, head_dim, key_ok,
                             bits))
        mlp = {"w1": bp["w1"], "w1_b": bp["b1"], "w2": bp["w2"],
               "w2_b": bp["b2"]}
        m = _lin(r(_gelu_tanh(_lin(r(_ln_mod(h, sh2, sc2, eps)), mlp, "w1",
                                   bits))), mlp, "w2", bits)
        return r(h + g2[:, None] * m), None

    h, _ = jax.lax.scan(block, h, p["blocks"])
    mod = r(p["final_table"][None] + t_emb[:, None])          # (1, 2, d)
    return _lin(r(_ln_mod(h, mod[:, 0], mod[:, 1], eps)), p, "out_proj",
                bits)


# ---------------------------------------------------------------------------
# guided UniPC
# ---------------------------------------------------------------------------

def sample(params, model: dict, schedule: dict, solver: dict, x_T, captions,
           caption_lens, null_caption=None, null_len=None, g=None,
           bits=None):
    """x_0 from x_T (B, T, L) for B requests, each under its caption
    (B, caption_tokens, caption_dim) of `caption_lens` (B,) tokens. With
    `g` (B,) the eval is guided against the learned null caption
    `y_embedding` under each request's own caption length. `null_caption`
    and `null_len` are what fills an empty slot of the program; no request
    is conditioned on them, so they are not used here. `bits` runs the
    denoiser at a lower precision (see `pixart_eps`); the sampler's state
    and combine stay float32."""
    ts, alphas, sigmas, steps = unipc_plan(schedule, solver)
    kw = dict(heads=model["num_heads"], head_dim=model["head_dim"],
              eps=model["norm_eps"], bits=bits)
    B = x_T.shape[0]
    caps = jnp.asarray(captions, jnp.float32)
    lens = jnp.asarray(caption_lens, jnp.int32)
    if g is not None:
        null = jnp.broadcast_to(params["backbone"]["y_embedding"], caps.shape)
        caps = jnp.concatenate([caps, null])
        lens = jnp.concatenate([lens, lens])
        gg = jnp.asarray(g, jnp.float32)[:, None, None]

    def data_pred(x, i):
        tf = jnp.asarray(time_features(float(ts[i]), model["time_features"]))
        if g is None:
            e = pixart_eps(params, x, tf, caps, lens, **kw)
        else:
            ee = pixart_eps(params, jnp.concatenate([x, x]), tf, caps, lens,
                            **kw)
            e = (1.0 + gg) * ee[:B] - gg * ee[B:]
        return (x - np.float32(sigmas[i]) * e) / np.float32(alphas[i])

    x = jnp.asarray(x_T, jnp.float32)
    hist = [data_pred(x, 0)]            # newest first
    for i, st in enumerate(steps, start=1):
        m0 = hist[0]
        base = np.float32(st["x"]) * x + np.float32(st["m0"]) * m0
        x_pred = base
        for w, m in zip(st["pred"], hist[1:]):
            x_pred = x_pred + np.float32(w) * (m - m0)
        if not st["use_corrector"]:
            x = x_pred
            break
        m_t = data_pred(x_pred, i)
        x = base + np.float32(st["corr_new"]) * (m_t - m0)
        for w, m in zip(st["corr"], hist[1:]):
            x = x + np.float32(w) * (m - m0)
        hist = [m_t] + hist[: solver["order"] - 1]
    return x
