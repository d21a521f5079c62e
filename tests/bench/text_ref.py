"""A small text-conditioned reference, the consumer of the `text`
conditioning kind at a caption's published shape: the patch tokens attend
over a projected caption, whose keys past its length are masked.

It has the interface `bench/check.py` drives a configuration's reference
through (`param_specs`, `sample`), and one eval in place of a sampler:
x_0 = x_T + (1 + g) eps(caption) - g eps(null caption).
"""

import math

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST


def param_specs(model: dict) -> dict:
    d, L, c = model["d_model"], model["latent_dim"], model["caption_dim"]
    a = model["num_heads"] * model["head_dim"]
    return {"in_proj": ((L, d), 1 / math.sqrt(L)),
            "cap1": ((c, d), 1 / math.sqrt(c)),
            "cap2": ((d, d), 1 / math.sqrt(d)),
            "wq": ((d, a), 1 / math.sqrt(d)),
            "wk": ((d, a), 1 / math.sqrt(d)),
            "wv": ((d, a), 1 / math.sqrt(d)),
            "wo": ((a, d), 1 / math.sqrt(a)),
            "out_proj": ((d, L), 1 / math.sqrt(d))}


def _mm(x, w):
    return jnp.einsum("...k,kn->...n", x, w, precision=HI)


def eps(params, model: dict, x, captions, lens):
    """Cross-attention of the latent's tokens (B, T, L) over the captions
    (B, C, width), keys at positions >= lens masked."""
    B, T, _ = x.shape
    H, hd = model["num_heads"], model["head_dim"]
    h = _mm(x, params["in_proj"])
    y = _mm(jax.nn.gelu(_mm(captions, params["cap1"]), approximate=True),
            params["cap2"])
    q = _mm(h, params["wq"]).reshape(B, T, H, hd)
    k = _mm(y, params["wk"]).reshape(B, -1, H, hd)
    v = _mm(y, params["wv"]).reshape(B, -1, H, hd)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k, precision=HI) / math.sqrt(hd)
    keep = jnp.arange(k.shape[1])[None, :] < jnp.asarray(lens)[:, None]
    s = jnp.where(keep[:, None, None, :], s, -jnp.inf)
    o = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1), v,
                   precision=HI)
    return _mm(_mm(o.reshape(B, T, H * hd), params["wo"]), params["out_proj"])


def sample(params, model, schedule, solver, x_T, g=None, bits=None, *,
           captions, caption_lens, null_caption, null_len):
    x = jnp.asarray(x_T, jnp.float32)
    e = eps(params, model, x, captions, caption_lens)
    if g is not None:
        B = x.shape[0]
        null = eps(params, model, x, np.broadcast_to(
            null_caption, captions.shape), np.full(B, null_len))
        gg = jnp.asarray(g, jnp.float32)[:, None, None]
        e = (1.0 + gg) * e - gg * null
    return x + e
