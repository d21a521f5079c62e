"""Training launcher: any --arch, AR or diffusion objective, CPU-runnable at
reduced scale and mesh-ready at full scale.

    PYTHONPATH=src python -m repro.launch.train --arch olmo-1b --reduced \
        --objective diffusion --steps 200 --batch 8 --seq 128
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from ..checkpoint import ckpt
from ..configs.registry import get_config
from ..data.synthetic import TokenStream, class_ids, latent_images, stub_embeds
from ..models import api
from ..optim import AdamW, warmup_cosine
from .compile_cache import enable_compile_cache


def make_train_step(cfg, objective, opt):
    loss_fn = api.train_loss(cfg, objective)

    @jax.jit
    def step(params, opt_state, batch, rng):
        loss, grads = jax.value_and_grad(loss_fn)(params, batch, rng)
        params, opt_state = opt.update(grads, opt_state, params)
        return params, opt_state, loss

    return step


def build_batch_fn(cfg, batch_size, seq_len, seed=0):
    if cfg.family == "dit":
        def fn(i):
            return {"latents": jnp.asarray(latent_images(
                        batch_size, cfg.patch_tokens, cfg.latent_dim, seed + i)),
                    "class_ids": jnp.asarray(class_ids(batch_size, seed=seed + i))}
        return fn
    stream = TokenStream(cfg.vocab_size, seq_len, batch_size, seed)

    def fn(i):
        b = {k: jnp.asarray(v) for k, v in stream.block(i).items()}
        if cfg.family == "vlm":
            b["image_embeds"] = jnp.asarray(
                stub_embeds(batch_size, cfg.image_tokens, cfg.d_model, seed + i))
        if cfg.family == "audio":
            b["audio_embeds"] = jnp.asarray(
                stub_embeds(batch_size, cfg.audio_frames, cfg.d_model, seed + i))
        return b

    return fn


def train(arch: str, *, reduced=True, objective="ar", steps=100, batch=8,
          seq=128, lr=3e-4, ckpt_dir=None, ckpt_every=0, log_every=10,
          seed=0, log_file=None):
    cfg = get_config(arch)
    if reduced:
        cfg = cfg.reduced()
    rng = jax.random.PRNGKey(seed)
    params = api.init_params(cfg, rng)
    opt = AdamW(lr=warmup_cosine(lr, min(20, steps // 10 + 1), steps))
    opt_state = opt.init(params)
    step_fn = make_train_step(cfg, objective, opt)
    batch_fn = build_batch_fn(cfg, batch, seq, seed)
    history = []
    t0 = time.time()
    for i in range(steps):
        rng, sub = jax.random.split(rng)
        params, opt_state, loss = step_fn(params, opt_state, batch_fn(i), sub)
        if i % log_every == 0 or i == steps - 1:
            loss_v = float(loss)
            history.append({"step": i, "loss": loss_v,
                            "elapsed_s": round(time.time() - t0, 1)})
            print(f"step {i:5d} loss {loss_v:.4f}")
        if ckpt_dir and ckpt_every and i and i % ckpt_every == 0:
            ckpt.save(ckpt_dir, {"params": params}, step=i)
    if ckpt_dir:
        ckpt.save(ckpt_dir, {"params": params}, step=steps)
    if log_file:
        Path(log_file).parent.mkdir(parents=True, exist_ok=True)
        Path(log_file).write_text(json.dumps(history, indent=1))
    return params, history


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--objective", default="ar", choices=["ar", "diffusion"])
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    scale = ap.add_mutually_exclusive_group()
    scale.add_argument("--reduced", action="store_true",
                       help="reduced CPU-scale config (the default)")
    scale.add_argument("--full", action="store_true",
                       help="full config (default: reduced CPU-scale)")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=0)
    ap.add_argument("--log-file", default=None)
    args = ap.parse_args()
    enable_compile_cache()
    train(args.arch, reduced=not args.full, objective=args.objective,
          steps=args.steps, batch=args.batch, seq=args.seq, lr=args.lr,
          ckpt_dir=args.ckpt_dir, ckpt_every=args.ckpt_every,
          log_file=args.log_file)


if __name__ == "__main__":
    main()
