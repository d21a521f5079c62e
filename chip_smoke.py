"""Chip smoke test: the diffusion serving path, once, on one TPU chip at the
published DiT widths (random weights from a seed).

    python chip_smoke.py

Phases, each fatal on failure:

a. device: JAX's first device must be a TPU. There is no CPU fallback.
b. kernels: each Pallas kernel runs compiled (never interpreted) at dit-i256
   serving shapes (4 slots, 8 rows under guidance, T=256, D=1152, head_dim
   72) and is compared with its jnp oracle under highest matmul precision.
c. eval: one full-width dit-i256 eps eval on perturbed params (the
   adaLN-zero init makes an untrained DiT output exactly zero), kernels
   pinned to `pallas` and then to `jnp`.
d. guided serving: dit-i256 through `launch.serve.serve_diffusion` (4 slots,
   UniPC-3, NFE 10, guidance, 8 Poisson requests, pipeline depth 2). Every
   request must complete with a finite latent, and the compiled step must
   hold the Pallas kernels (`tpu_custom_call`).
e. unguided serving: the same for dit-cifar, unguided (the remainder tile of
   the solver update at N = 3072 and attention's padding of T = 64).

Compile seconds, tick time and peak device memory are printed as smoke
observations, not benchmark numbers. The last line of standard output is
one JSON object naming the device.
"""

from __future__ import annotations

import dataclasses
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

SLOTS, ROWS, REQUESTS = 4, 8, 8
# max |kernel - oracle| / max |oracle|
# (the kernels run at default precision: flash attention's fp32 dots may
# take bf16 MXU passes)
KERNEL_TOL = {"unipc_update": 1e-5, "modulate": 1e-5, "gate_residual": 1e-5,
              "flash_attention": 1e-2}
BF16_TOL = 1e-2          # any kernel on bf16 operands (bf16 output rounding)
EVAL_TOL = 1e-4          # pallas vs jnp kernels through all 28 blocks


def require(cond: bool, msg: str) -> None:
    if not cond:
        raise SystemExit(f"chip_smoke FAILED: {msg}")


def rel_err(got, want) -> float:
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    require(got.shape == want.shape, f"shape {got.shape} != {want.shape}")
    require(bool(np.isfinite(got).all()), "non-finite kernel output")
    return float(np.abs(got - want).max() / max(np.abs(want).max(), 1e-30))


def check_device():
    dev = jax.devices()[0]
    require(dev.platform == "tpu",
            f"needs a TPU, JAX found platform {dev.platform!r}")
    print(f"phase a: device {dev.device_kind} ({dev.platform}), "
          f"{len(jax.devices())} visible", flush=True)
    return dev


def check_kernels():
    from repro.kernels.adaln_modulate import ops as ad_ops, ref as ad_ref
    from repro.kernels.flash_attention import ops as fa_ops, ref as fa_ref
    from repro.kernels.quant_matmul import ops as qm_ops, ref as qm_ref
    from repro.kernels.unipc_update import ops as up_ops, ref as up_ref

    T, D, H, L, K = 256, 1152, 16, 32, 5
    ks = iter(jax.random.split(jax.random.PRNGKey(0), 16))
    normal = lambda shape: jax.random.normal(next(ks), shape, jnp.float32)
    terms = normal((K, SLOTS, T, L))
    x, y = normal((ROWS, T, D)), normal((ROWS, T, D))
    sh, sc, g = normal((ROWS, D)), normal((ROWS, D)), normal((ROWS, D))
    q, k, v = (normal((ROWS, H, T, D // H)) for _ in range(3))
    qw, ws = qm_ref.quantize(normal((D, 4 * D)) / np.sqrt(D))
    cases = [
        ("unipc_update", "bcast", lambda w: up_ops.weighted_combine(
            terms, w, backend="pallas"),
         lambda w: up_ref.weighted_combine(terms, w), (normal((K,)),)),
        ("unipc_update", "per-slot", lambda w: up_ops.weighted_combine(
            terms, w, backend="pallas"),
         lambda w: up_ref.weighted_combine(terms, w), (normal((K, SLOTS)),)),
        ("quant_matmul_int8", "bfloat16", lambda a: qm_ops.quant_matmul(
            a, qw, ws, backend="pallas"),
         lambda a: qm_ref.quant_matmul(a, qw, ws),
         (x.astype(jnp.bfloat16),)),
    ]
    for dt in ("float32", "bfloat16"):
        c = lambda a: a.astype(dt)
        cases += [
            ("modulate", dt, lambda *a: ad_ops.modulate(*a, backend="pallas"),
             ad_ref.modulate, (c(x), c(sh), c(sc))),
            ("gate_residual", dt,
             lambda *a: ad_ops.gate_residual(*a, backend="pallas"),
             ad_ref.gate_residual, (c(x), c(g), c(y))),
            ("flash_attention", dt, lambda *a: fa_ops.attention(
                *a, causal=False, backend="pallas"),
             lambda *a: fa_ref.attention(*a, causal=False),
             (c(q), c(k), c(v))),
        ]
    for name, variant, kernel, oracle, args in cases:
        got = jax.jit(kernel)(*args)
        with jax.default_matmul_precision("highest"):
            want = jax.jit(oracle)(*args)
        err = rel_err(got, want)
        tol = BF16_TOL if variant == "bfloat16" else KERNEL_TOL[name]
        print(f"phase b: {name}[{variant}] max_rel_err={err!r} tol={tol}",
              flush=True)
        require(err <= tol, f"{name}[{variant}] error {err} > {tol}")


def check_eval():
    from repro.configs.registry import get_config
    from repro.launch.sample import NULL_CLASS_ID
    from repro.models import api

    cfg = dataclasses.replace(get_config("dit-i256"), dtype="float32")
    params = api.init_params(cfg, jax.random.PRNGKey(1))
    leaves, treedef = jax.tree.flatten(params)
    keys = jax.random.split(jax.random.PRNGKey(2), len(leaves))
    params = jax.tree.unflatten(treedef, [
        a + 0.02 * jax.random.normal(k, a.shape, a.dtype)
        if jnp.issubdtype(a.dtype, jnp.floating) else a
        for a, k in zip(leaves, keys)])
    x = jax.random.normal(jax.random.PRNGKey(3),
                          (ROWS, cfg.patch_tokens, cfg.latent_dim))
    t = jnp.full((ROWS,), 0.4, jnp.float32)
    ids = jnp.concatenate([jnp.arange(SLOTS, dtype=jnp.int32) * 97,
                           jnp.full((SLOTS,), NULL_CLASS_ID, jnp.int32)])
    outs = {}
    for backend in ("pallas", "jnp"):
        pinned = dataclasses.replace(cfg, attention_backend=backend,
                                     adaln_backend=backend)
        net = api.eps_network(pinned)
        with jax.default_matmul_precision("highest"):
            outs[backend] = np.asarray(jax.jit(
                lambda p, x, t, ids: net(p, x, t, {"class_ids": ids})
            )(params, x, t, ids))
    ref = outs["jnp"]
    require(float(np.abs(ref).max()) > 0, "perturbed eval is degenerate")
    err = rel_err(outs["pallas"], ref)
    print(f"phase c: dit-i256 eps eval pallas vs jnp max_rel_err={err!r} "
          f"tol={EVAL_TOL}", flush=True)
    require(err <= EVAL_TOL, f"eval parity error {err} > {EVAL_TOL}")


def check_serving(phase: str, arch: str, cfg_scale: float, dev) -> None:
    from repro.configs.registry import get_config
    from repro.launch.serve import serve_diffusion

    cfg = get_config(arch)
    report = {}
    t0 = time.perf_counter()
    latents = serve_diffusion(
        arch, reduced=False, batch=SLOTS, nfe=10, order=3, solver="unipc",
        cfg_scale=cfg_scale, arrival_rate=1.0, requests=REQUESTS,
        pipeline_depth=2, seed=0, report=report)
    wall = time.perf_counter() - t0
    m = report["metrics"]
    require(m.completed == REQUESTS,
            f"{arch}: {m.completed}/{REQUESTS} requests completed")
    require(latents.shape == (REQUESTS, cfg.patch_tokens, cfg.latent_dim),
            f"{arch}: latents shaped {latents.shape}")
    require(bool(np.isfinite(latents).all()), f"{arch}: non-finite latents")
    require("tpu_custom_call" in report["step_text"],
            f"{arch}: no Pallas kernel in the compiled step")
    peak = dev.memory_stats().get("peak_bytes_in_use")
    print(f"phase {phase}: {arch} cfg={cfg_scale} {m.completed}/{REQUESTS} "
          f"requests finite, tpu_custom_call in step", flush=True)
    print(f"phase {phase}: smoke observation, not a benchmark: "
          f"compile_s={report['compile_s']!r} tick_ms={m.tick_s * 1e3!r} "
          f"wall_s={wall!r} peak_bytes_in_use={peak!r}", flush=True)


def main() -> None:
    dev = check_device()
    from repro.launch.compile_cache import enable_compile_cache

    print(f"compile cache: {enable_compile_cache()}", flush=True)
    check_kernels()
    check_eval()
    check_serving("d", "dit-i256", 4.0, dev)
    check_serving("e", "dit-cifar", 0.0, dev)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))


if __name__ == "__main__":
    main()
