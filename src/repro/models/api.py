"""Unified per-family model API.

Every architecture family exposes the same five entry points, so the launcher,
dry-run, tests, and benchmarks are family-agnostic:

    init_params(cfg, rng)                    -> params
    train_loss(cfg)(params, batch, rng)      -> scalar   (objective: 'ar' | 'diffusion')
    prefill(cfg)(params, batch, max_len)     -> (logits, cache)
    decode_step(cfg)(params, cache, tok, pos)-> (logits, cache)
    init_cache(cfg, batch, max_len)          -> cache pytree

`batch` is a dict: tokens/targets always; image_embeds (vlm), audio_embeds
(audio), latents (dit). The diffusion objective implements embedding-space
diffusion-LM (Li et al., 2022-style: learned token latents + eps-loss +
rounding CE) — the vehicle for UniPC on every backbone (DESIGN.md §7.1).
"""

from __future__ import annotations

from typing import Callable, Dict

import jax
import jax.numpy as jnp

from ..configs.base import ModelConfig
from ..diffusion.process import q_sample
from ..diffusion.schedules import VPLinear
from .diffusion_lm import diffusion_lm_apply, init_diffusion_head
from .dit import dit_apply, init_dit
from .layers import dense_init
from . import encdec, hybrid, transformer, vlm


def _backbone_forward(cfg):
    """(params, inputs_embeds, extra) -> (hidden, aux) for diffusion mode."""
    fam = cfg.family
    if fam in ("dense", "moe"):
        return lambda p, e, b: transformer.forward(
            p["backbone"], cfg, None, causal=False, inputs_embeds=e)
    if fam == "ssm":
        return lambda p, e, b: hybrid.mamba_forward(
            p["backbone"], cfg, None, inputs_embeds=e)
    if fam == "hybrid":
        return lambda p, e, b: hybrid.zamba_forward(
            p["backbone"], cfg, None, inputs_embeds=e)
    if fam == "vlm":
        # image conditioning flows through the cross-attn layers as usual
        def f(p, e, b):
            return _vlm_embeds_forward(p["backbone"], cfg, e, b["image_embeds"])
        return f
    if fam == "audio":
        def f(p, e, b):
            return _audio_embeds_forward(p["backbone"], cfg, e, b["audio_embeds"])
        return f
    raise ValueError(fam)


def _vlm_embeds_forward(params, cfg, embeds, image_embeds):
    return vlm._forward_embeds(params, cfg, embeds, image_embeds)


def _audio_embeds_forward(params, cfg, embeds, audio_embeds):
    return encdec._forward_embeds(params, cfg, embeds, audio_embeds)


# ---------------------------------------------------------------------------


def init_params(cfg: ModelConfig, rng):
    fam = cfg.family
    k1, k2, k3 = jax.random.split(rng, 3)
    if fam == "dit":
        return {"backbone": init_dit(
            cfg, k1, num_classes=0 if cfg.caption_dim else 1000)}
    if fam in ("dense", "moe"):
        p = {"backbone": transformer.init_lm(cfg, k1)}
    elif fam == "ssm":
        p = {"backbone": hybrid.init_mamba_lm(cfg, k1)}
    elif fam == "hybrid":
        p = {"backbone": hybrid.init_zamba_lm(cfg, k1)}
    elif fam == "vlm":
        p = {"backbone": vlm.init_vlm(cfg, k1)}
    elif fam == "audio":
        p = {"backbone": encdec.init_encdec(cfg, k1)}
    else:
        raise ValueError(fam)
    if cfg.latent_dim:
        p["diffusion_head"] = init_diffusion_head(cfg, k2)
        p["token_latents"] = dense_init(k3, cfg.vocab_size, cfg.latent_dim,
                                        cfg.weight_dtype, scale=1.0)
    return p


def ar_loss(cfg: ModelConfig) -> Callable:
    fam = cfg.family

    def loss(params, batch, rng):
        bk = params["backbone"]
        if fam in ("dense", "moe"):
            return transformer.lm_loss(bk, cfg, batch["tokens"], batch["targets"])
        if fam == "ssm":
            return hybrid.mamba_lm_loss(bk, cfg, batch["tokens"], batch["targets"])
        if fam == "hybrid":
            return hybrid.zamba_lm_loss(bk, cfg, batch["tokens"], batch["targets"])
        if fam == "vlm":
            return vlm.vlm_loss(bk, cfg, batch["tokens"], batch["targets"],
                                batch["image_embeds"])
        if fam == "audio":
            return encdec.encdec_loss(bk, cfg, batch["tokens"], batch["targets"],
                                      batch["audio_embeds"])
        raise ValueError(fam)

    return loss


def cast_params_for_eval(params, eval_dtype: str):
    """Pre-cast every float param leaf to the serving eval dtype (DESIGN.md
    §11.3) — once, so reduced-precision serving halves the params' HBM reads
    instead of casting at use. Non-float leaves (e.g. int tables) pass
    through. The single definition serves both `launch.sample.build_engine`
    and the model benchmarks, so the benchmarked bf16 mode is exactly the
    shipped one."""
    dt = jnp.dtype(eval_dtype)
    return jax.tree.map(
        lambda a: (a.astype(dt)
                   if jnp.issubdtype(a.dtype, jnp.floating) else a),
        params)


def calibrate_and_quantize(cfg: ModelConfig, params, quant, *, schedule=None,
                           nfe: int = 6, calib_batch: int = 2, seed: int = 0):
    """Quantized serving path (DESIGN.md §14): calibrate + install records.

    `quant` is a tier name from models.quant.QUANT_MODES ("w8a16", "w8a8",
    ...) or a QuantSpec. Weight scales come from per-output-channel absmax
    of the weights themselves; a8 tiers additionally record per-site
    activation absmax over `calib_batch` deterministic reference
    trajectories (same seed -> bit-identical scales). Returns
    (cfg', params', info): cfg' carries the spec (and is what eps_network
    should be built from), params' the quantized tree.
    """
    import dataclasses

    from .quant import calibrate_act_stats, quant_spec, quantize_params

    spec = quant_spec(quant) if isinstance(quant, str) else quant
    stats = None
    if spec.act_bits == 8:
        stats = calibrate_act_stats(cfg, params, schedule=schedule, nfe=nfe,
                                    batch=calib_batch, seed=seed)
    qparams = quantize_params(cfg, params, spec, act_stats=stats)
    cfg = dataclasses.replace(cfg, quant=spec)
    return cfg, qparams, {"spec": spec, "act_stats": stats}


def eps_network(cfg: ModelConfig) -> Callable:
    """(params, x_t (B,S,L), t, batch) -> eps-hat — what UniPC samples from."""
    if cfg.family == "dit":
        return lambda p, x_t, t, batch: dit_apply(
            p["backbone"], cfg, x_t, t, batch.get("class_ids"),
            batch.get("caption"), batch.get("caption_len"))
    fwd = _backbone_forward(cfg)

    def f(params, x_t, t, batch):
        return diffusion_lm_apply(
            params["diffusion_head"],
            lambda e: fwd(params, e, batch), cfg, x_t, t)

    return f


def eps_network_cached(cfg: ModelConfig, cache_block: int) -> Callable:
    """Feature-reuse eps-net (DESIGN.md §12), dit family only:

        (params, x_t, t, batch, cache, reuse) -> (eps-hat, new_cache)

    `cache` is the (B, T, d_model) deep-feature delta state (see
    `dit.dit_apply_cached`); `reuse` the per-sample shallow-eval flag. The
    `cache_block` boundary is static — it is baked into the compiled step
    program, while *which* steps reuse the cache is data (a searched
    per-step table column, `repro.tuning`)."""
    if cfg.family != "dit":
        raise ValueError(f"feature-reuse eval needs the dit family (residual "
                         f"block stack); arch {cfg.arch_id!r} is family "
                         f"{cfg.family!r}")
    from .dit import dit_apply_cached

    def f(params, x_t, t, batch, cache, reuse):
        return dit_apply_cached(params["backbone"], cfg, x_t, t,
                                batch.get("class_ids"), cache=cache,
                                reuse=reuse, cache_block=cache_block)

    return f


def diffusion_loss_fn(cfg: ModelConfig, schedule=None) -> Callable:
    schedule = schedule or VPLinear()
    net = eps_network(cfg)

    def loss(params, batch, rng):
        rng_t, rng_e = jax.random.split(rng)
        if cfg.family == "dit":
            x0 = batch["latents"]
        else:
            x0 = params["token_latents"].astype(cfg.activation_dtype)[batch["tokens"]]
        B = x0.shape[0]
        t = jax.random.uniform(rng_t, (B,), minval=schedule.t_eps,
                               maxval=schedule.T)
        noise = jax.random.normal(rng_e, x0.shape, jnp.float32).astype(x0.dtype)
        x_t = q_sample(schedule, x0, t, noise)
        eps_hat = net(params, x_t, t, batch)
        mse = jnp.mean((eps_hat.astype(jnp.float32)
                        - noise.astype(jnp.float32)) ** 2)
        if cfg.family == "dit":
            return mse
        # rounding loss anchors the latent space (Diffusion-LM), weighted by
        # alpha_t^2: at high noise x0_hat = (x_t - sigma eps)/alpha amplifies
        # the residual by 1/alpha and the unweighted CE is pure variance
        a, s = schedule.alpha_sigma_jax(t)
        bshape = (-1,) + (1,) * (x0.ndim - 1)
        x0_hat = (x_t - s.reshape(bshape) * eps_hat) / a.reshape(bshape)
        logits = jnp.einsum("bsl,vl->bsv", x0_hat.astype(jnp.float32),
                            params["token_latents"].astype(jnp.float32))
        logp = jax.nn.log_softmax(logits, axis=-1)
        ce = -jnp.take_along_axis(logp, batch["tokens"][..., None], -1)
        w = (a * a).reshape((-1,) + (1,) * (ce.ndim - 1))
        ce = jnp.mean(w * ce) / jnp.mean(w)
        return mse + ce

    return loss


def train_loss(cfg: ModelConfig, objective: str = "ar") -> Callable:
    return ar_loss(cfg) if objective == "ar" else diffusion_loss_fn(cfg)


# ---------------------------------------------------------------------------
# serving
# ---------------------------------------------------------------------------

def init_cache(cfg: ModelConfig, batch: int, max_len: int):
    fam = cfg.family
    if fam in ("dense", "moe"):
        return transformer.init_cache(cfg, batch, max_len)
    if fam == "ssm":
        return hybrid.init_mamba_cache(cfg, batch, max_len)
    if fam == "hybrid":
        return hybrid.init_zamba_cache(cfg, batch, max_len)
    if fam == "vlm":
        return vlm.init_vlm_cache(cfg, batch, max_len)
    if fam == "audio":
        cache = None  # built by prefill; specs via prefill lowering
        raise ValueError("audio cache comes from encdec_prefill")
    raise ValueError(fam)


def prefill_fn(cfg: ModelConfig) -> Callable:
    fam = cfg.family

    def f(params, batch, max_len):
        bk = params["backbone"]
        if fam in ("dense", "moe"):
            return transformer.prefill(bk, cfg, batch["tokens"], max_len)
        if fam == "ssm":
            return hybrid.mamba_prefill(bk, cfg, batch["tokens"], max_len)
        if fam == "hybrid":
            return hybrid.zamba_prefill(bk, cfg, batch["tokens"], max_len)
        if fam == "vlm":
            return vlm.vlm_prefill(bk, cfg, batch["tokens"],
                                   batch["image_embeds"], max_len)
        if fam == "audio":
            return encdec.encdec_prefill(bk, cfg, batch["tokens"],
                                         batch["audio_embeds"], max_len)
        raise ValueError(fam)

    return f


def decode_fn(cfg: ModelConfig) -> Callable:
    fam = cfg.family

    def f(params, cache, token, pos):
        bk = params["backbone"]
        if fam in ("dense", "moe"):
            return transformer.decode_step(bk, cfg, cache, token, pos)
        if fam == "ssm":
            return hybrid.mamba_decode_step(bk, cfg, cache, token, pos)
        if fam == "hybrid":
            return hybrid.zamba_decode_step(bk, cfg, cache, token, pos)
        if fam == "vlm":
            return vlm.vlm_decode_step(bk, cfg, cache, token, pos)
        if fam == "audio":
            return encdec.encdec_decode_step(bk, cfg, cache, token, pos)
        raise ValueError(fam)

    return f
