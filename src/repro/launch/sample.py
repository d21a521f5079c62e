"""Diffusion sampling launcher — the paper's workload. Loads (or freshly
initializes) an eps-network for --arch, then samples with any solver in the
zoo at a given NFE budget. Every solver runs scan-compiled through the
engine (`SamplerEngine.build`: weight-table compiler -> one `lax.scan` ->
fused Pallas state update); `--loop` pins the python-loop GridSolver
reference instead. Conditional sampling (dit family): `--cfg-scale` fuses
classifier-free guidance into the scan — cond+uncond stacked into ONE
batched network call per step — and `--thresholding` adds Imagen-style
dynamic thresholding; both default off.

    PYTHONPATH=src python -m repro.launch.sample --arch dit-cifar --reduced \
        --solver dpmpp --order 2 --nfe 10 --cfg-scale 2.0
"""

from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.tree_util import Partial

from ..checkpoint import ckpt
from ..configs.registry import get_config
from ..data.synthetic import class_ids
from ..diffusion import VPLinear
from ..engine import EngineSpec, SamplerEngine
from ..models import api
from .compile_cache import enable_compile_cache

NULL_CLASS_ID = 1000  # init_dit allocates num_classes + 1 embeddings; the
                      # extra row is the CFG null class


def build_engine(cfg, params, schedule, batch: int, seed: int = 0,
                 want_cfg: bool = False, per_request_cond: bool = False,
                 eval_dtype: str = "float32",
                 cache_block: int = 0, quant: str = "none") -> SamplerEngine:
    """Wire the arch's eps-network into a SamplerEngine: the cond branch,
    and — for dit-family conditional sampling — the stacked 2B cond+uncond
    branch that fused CFG serves from, plus the uncond branch for the
    sequential loop reference.

    per_request_cond (dit only): instead of baking a per-batch-row class-id
    array at build time (slot-positional — fine for a uniform batch, wrong
    under continuous batching where a request's slot depends on arrival
    order), the eps branches take `class_ids` as a per-call (B,) keyword
    argument, which the serving scheduler scatters per request.

    A text-conditioned config (`cfg.caption_dim > 0`, PixArt-alpha) takes
    `caption` (B, caption_tokens, caption_dim) and `caption_len` (B,) in
    place of class ids, per request only. The guidance half is the learned
    null caption `y_embedding` under each row's own caption length, as
    PixArt's sampling scripts pair them. The cache and quantized tiers do
    not serve it.

    eval_dtype="bfloat16" is the fast serving eval (DESIGN.md §11): the
    network's params-at-use and activations run in bf16 (params are pre-cast
    once, so serving HBM reads are halved; the conditioning MLP keeps its
    fp32 compute). The engine side of the boundary — solver state, combine
    weights, eps↔x0 — stays fp32 via the matching `EngineSpec.eval_dtype`.

    cache_block > 0 additionally wires the feature-reuse eval (DESIGN.md
    §12, dit only): the engine gets `eps_cached` — the same network with a
    deep-feature cache split at block `cache_block` — plus the matching
    `CacheSpec`, and serves cached plans whose specs carry the same
    `cache_block`. Incompatible with guidance (see `EngineSpec.resolve`).

    quant != "none" (DESIGN.md §14, dit only) calibrates and installs the
    tier's quantized param tree (`api.calibrate_and_quantize`, deterministic
    given `seed`) before wiring, so every eps branch — stacked CFG, cached —
    routes its dense sites through kernels/quant_matmul. The engine records
    the tier and `model_fn` rejects specs that disagree, exactly like
    eval_dtype.

    Every eps branch is a `jax.tree_util.Partial` binding the (cast or
    quantized) param tree, so the engine's compiled programs take the
    weights as an argument instead of baking them in as constants."""
    import dataclasses

    if eval_dtype not in ("float32", "bfloat16"):
        raise ValueError(f"eval_dtype must be 'float32' or 'bfloat16', "
                         f"got {eval_dtype!r}")
    if cfg.caption_dim and (cache_block or quant != "none"):
        raise ValueError(f"{cfg.arch_id!r} is text-conditioned; the cached "
                         f"and quantized tiers serve class-conditioned "
                         f"DiTs only")
    if quant != "none" and cfg.family != "dit":
        raise ValueError(f"the quantized denoiser path needs the dit "
                         f"family; {cfg.arch_id!r} is family "
                         f"{cfg.family!r}")
    if cache_block:
        if cfg.family != "dit":
            raise ValueError(f"cache_block needs the dit family; "
                             f"{cfg.arch_id!r} is family {cfg.family!r}")
        if want_cfg:
            raise ValueError("feature reuse serves unconditional programs "
                             "only (EngineSpec.resolve rejects cache_block "
                             "with cfg_scale)")
        if not 1 <= cache_block < cfg.num_layers:
            raise ValueError(f"cache_block must be in "
                             f"1..{cfg.num_layers - 1}, got {cache_block}")
    if eval_dtype == "bfloat16":
        cfg = dataclasses.replace(cfg, dtype=eval_dtype)
        params = api.cast_params_for_eval(params, eval_dtype)
    if quant != "none":
        # quantize after the eval cast: records are derived from the exact
        # tree the net will otherwise read, scales stay fp32 either way
        cfg, params, _ = api.calibrate_and_quantize(
            cfg, params, quant, schedule=schedule, seed=seed)
    net = api.eps_network(cfg)

    def eps_with(extra):
        # jit so the python-loop reference path gets compiled evals too; the
        # scan path's outer jit simply inlines it
        return Partial(jax.jit(
            lambda p, x, t: net(p, x, jnp.asarray(t, jnp.float32), extra)),
            params)

    def cache_kw(baked=None):
        """(eps_cached, cache_spec) for this wiring — None, None uncached.
        `baked` fixes the batch dict at build time (the uniform-batch mode);
        otherwise the per-call extras are the batch (per-request mode)."""
        if not cache_block:
            return {}
        from ..engine import CacheSpec
        from ..models.dit import dit_cache_shape

        cnet = api.eps_network_cached(cfg, cache_block)

        def eps_cached(p, x, t, cache, reuse, **extra):
            return cnet(p, x, jnp.asarray(t, jnp.float32),
                        baked if baked is not None else extra, cache, reuse)

        return {"eps_cached": Partial(eps_cached, params),
                "cache_spec": CacheSpec(shape=dit_cache_shape(cfg),
                                        block=cache_block,
                                        n_blocks=cfg.num_layers,
                                        dtype=eval_dtype)}

    if cfg.family != "dit":
        if want_cfg:
            raise ValueError("classifier-free guidance needs the dit family "
                             "(class-conditional eps-net)")
        return SamplerEngine(schedule, eps=eps_with({}),
                             eval_dtype=eval_dtype, quant=quant)
    if cfg.caption_dim:
        return _text_engine(cfg, net, params, schedule, per_request_cond,
                            eval_dtype)
    null = jnp.full((batch,), NULL_CLASS_ID, jnp.int32)
    if per_request_cond:
        def eps_cond(p, x, t, class_ids):
            return net(p, x, jnp.asarray(t, jnp.float32),
                       {"class_ids": class_ids})

        def eps_stacked(p, xx, t, class_ids):
            ids2 = jnp.concatenate([jnp.asarray(class_ids, jnp.int32),
                                    jnp.full_like(class_ids, NULL_CLASS_ID,
                                                  jnp.int32)])
            return net(p, xx, jnp.asarray(t, jnp.float32),
                       {"class_ids": ids2})

        return SamplerEngine(schedule,
                             eps=Partial(jax.jit(eps_cond), params),
                             eps_stacked=Partial(jax.jit(eps_stacked), params),
                             eps_uncond=eps_with({"class_ids": null}),
                             eval_dtype=eval_dtype, quant=quant,
                             **cache_kw())
    ids = jnp.asarray(class_ids(batch, seed=seed))
    return SamplerEngine(
        schedule,
        eps=eps_with({"class_ids": ids}),
        eps_stacked=eps_with({"class_ids": jnp.concatenate([ids, null])}),
        eps_uncond=eps_with({"class_ids": null}),
        eval_dtype=eval_dtype, quant=quant,
        **cache_kw(baked={"class_ids": ids}),
    )


def _text_engine(cfg, net, params, schedule, per_request_cond,
                 eval_dtype) -> SamplerEngine:
    """The eps branches of a text-conditioned DiT, each taking a caption
    per request. The stacked (guided) branch puts the learned null caption
    `y_embedding` under the second half's rows, each with its own row's
    caption length."""
    if not per_request_cond:
        raise ValueError(f"{cfg.arch_id!r} is text-conditioned: its "
                         f"engine takes a caption per request "
                         f"(per_request_cond=True)")

    def eps_cond(p, x, t, caption, caption_len):
        return net(p, x, jnp.asarray(t, jnp.float32),
                   {"caption": caption, "caption_len": caption_len})

    def eps_stacked(p, xx, t, caption, caption_len):
        null = jnp.broadcast_to(p["backbone"]["y_embedding"].astype(
            caption.dtype), caption.shape)
        lens = jnp.asarray(caption_len, jnp.int32)
        return eps_cond(p, xx, t, jnp.concatenate([caption, null]),
                        jnp.concatenate([lens, lens]))

    return SamplerEngine(schedule, eps=Partial(jax.jit(eps_cond), params),
                         eps_stacked=Partial(jax.jit(eps_stacked), params),
                         eval_dtype=eval_dtype)


def require_dit_for_cfg(ap, arch: str, cfg_scale: float) -> str:
    """Argparse-friendly guard shared by the sample/serve CLIs: guidance
    needs the class-conditional dit family. Returns the arch's family."""
    from ..configs.registry import get_config

    family = get_config(arch).family
    if cfg_scale and family != "dit":
        ap.error(f"--cfg-scale needs a class-conditional eps-net; "
                 f"--arch {arch} is family '{family}', not 'dit' "
                 f"(try dit-cifar or dit-i256)")
    return family


def latent_shape(cfg, batch):
    if cfg.family == "dit":
        return (batch, cfg.patch_tokens, cfg.latent_dim)
    return (batch, 64, cfg.latent_dim)  # diffusion-LM over a 64-token window


def sample(arch: str, *, reduced=True, solver="unipc", order=3, nfe=10,
           variant="bh2", prediction=None, batch=4, seed=0, params=None,
           loop=False, fused_update=True, cfg_scale=0.0,
           cfg_schedule="constant", thresholding=False, plan=None,
           eval_dtype="float32", quant="none"):
    cfg = get_config(arch)
    if reduced:
        cfg = cfg.reduced()
    rng = jax.random.PRNGKey(seed)
    if params is None:
        params = api.init_params(cfg, rng)
    schedule = VPLinear()
    plan_tab = None
    cache_block = 0
    if plan is not None:
        # a tuned SolverPlan (path or object) replaces the registry table:
        # the spec keeps only the conditioning/runtime knobs
        from ..tuning import SolverPlan

        if loop:
            raise ValueError("a tuned plan runs the scan-compiled table; "
                             "there is no python-loop reference for "
                             "searched plans")
        if isinstance(plan, str):
            plan = SolverPlan.load(plan)
        solver, nfe, order = "unipc", plan.nfe, max(plan.orders)
        prediction = plan.prediction
        # a cached plan (nonzero cache_depth) needs the cache-wired engine
        # and a spec carrying the same static boundary
        cache_block = plan.cache_block
        plan_tab = plan.compile(schedule)
    if loop and eval_dtype != "float32":
        raise ValueError("the python-loop reference is fp32-only; "
                         "eval_dtype rides the engine paths")
    if loop and quant != "none":
        raise ValueError("the python-loop reference is fp32-only; "
                         "quantized tiers ride the engine paths")
    engine = build_engine(cfg, params, schedule, batch, seed,
                          want_cfg=cfg_scale != 0.0, eval_dtype=eval_dtype,
                          cache_block=cache_block, quant=quant)
    spec = EngineSpec(solver=solver, nfe=nfe, order=order, variant=variant,
                      prediction=prediction, cfg_scale=cfg_scale,
                      cfg_schedule=cfg_schedule, thresholding=thresholding,
                      fused_update=fused_update, eval_dtype=eval_dtype,
                      cache_block=cache_block, quant=quant)
    x_T = jax.random.normal(rng, latent_shape(cfg, batch), jnp.float32)

    t0 = time.time()
    if loop:
        run = engine.build_loop(spec)
        x0 = run(x_T)
        nfe_used = run.solver.model.nfe  # measured eval count
    else:
        tab = engine.compile(spec, table=plan_tab)
        x0 = engine.build(spec, table=tab)(x_T)
        # the scan evaluates the final step's eps too; fused CFG keeps one
        # (2B-batched) call per step
        nfe_used = len(tab.timesteps)
    dt = time.time() - t0
    x0 = np.asarray(x0)
    path = "loop" if loop else "scan"
    tag = (f"{solver}-{order}" + (" [plan]" if plan_tab is not None else "")
           + (f" [{quant}]" if quant != "none" else ""))
    cache_note = (f" evals/latent={plan.eval_cost(cfg.num_layers):.2f} "
                  f"(cache_block={cache_block})" if cache_block else "")
    print(f"{tag} [{path}] nfe={nfe_used}{cache_note} cfg={cfg_scale} "
          f"wall={dt:.2f}s out_shape={x0.shape} mean={x0.mean():+.4f} "
          f"std={x0.std():.4f} finite={np.isfinite(x0).all()}")
    return x0


def main():
    from ..engine import SOLVERS

    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="dit-cifar")
    ap.add_argument("--solver", default="unipc", choices=sorted(SOLVERS))
    ap.add_argument("--order", type=int, default=3)
    ap.add_argument("--nfe", type=int, default=10)
    ap.add_argument("--variant", default="bh2", choices=["bh1", "bh2", "vary"])
    ap.add_argument("--prediction", default=None, choices=["data", "noise"],
                    help="override the solver's native prediction type "
                         "(unipc/ddim/dpm support both)")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--loop", action="store_true",
                    help="python-loop GridSolver reference instead of the "
                         "scan-compiled engine path")
    ap.add_argument("--no-fused-update", action="store_true",
                    help="pin the inline jnp op-chain combine in the scan "
                         "sampler (default: fused kernel dispatch)")
    ap.add_argument("--cfg-scale", type=float, default=0.0,
                    help="classifier-free guidance scale (0 = off); fused "
                         "into the scan as one batched eval per step")
    ap.add_argument("--cfg-schedule", default="constant",
                    choices=["constant", "linear", "cosine"])
    ap.add_argument("--thresholding", action="store_true",
                    help="Imagen-style dynamic thresholding of the x0 "
                         "prediction (data-prediction solvers)")
    ap.add_argument("--eval-dtype", default="float32",
                    choices=["float32", "bfloat16"],
                    help="eps-network eval precision (default fp32); "
                         "bfloat16 is the fast serving eval — solver state "
                         "and combine weights stay fp32 (DESIGN.md §11)")
    ap.add_argument("--quant", default="none",
                    choices=["none", "w8a16", "w8a8", "fp8a16", "w4a16"],
                    help="quantized denoiser tier (DESIGN.md §14): int8/fp8 "
                         "weight matmuls with calibrated scales, fp32 "
                         "accumulation; dit family only")
    ap.add_argument("--plan", default=None,
                    help="path to a tuned SolverPlan JSON (repro.launch.tune)"
                         "; overrides --solver/--order/--nfe with the plan's "
                         "searched per-step schedule")
    scale = ap.add_mutually_exclusive_group()
    scale.add_argument("--reduced", action="store_true",
                       help="reduced CPU-scale config (the default)")
    scale.add_argument("--full", action="store_true")
    ap.add_argument("--ckpt", default=None)
    args = ap.parse_args()
    enable_compile_cache()
    require_dit_for_cfg(ap, args.arch, args.cfg_scale)
    if args.plan and args.loop:
        ap.error("--plan runs the scan-compiled table; --loop has no "
                 "python-loop reference for searched plans")
    if args.loop and args.eval_dtype != "float32":
        ap.error("--eval-dtype rides the engine paths; the python-loop "
                 "reference is fp32-only")
    if args.loop and args.quant != "none":
        ap.error("--quant rides the engine paths; the python-loop "
                 "reference is fp32-only")
    if args.quant != "none" and get_config(args.arch).family != "dit":
        ap.error(f"--quant needs the dit family; --arch {args.arch} is "
                 f"family {get_config(args.arch).family!r}")
    params = None
    if args.ckpt:
        tree, _ = ckpt.restore(args.ckpt)
        params = tree["params"]
    sample(args.arch, reduced=not args.full, solver=args.solver,
           order=args.order, nfe=args.nfe, variant=args.variant,
           prediction=args.prediction, batch=args.batch, params=params,
           loop=args.loop, fused_update=not args.no_fused_update,
           cfg_scale=args.cfg_scale, cfg_schedule=args.cfg_schedule,
           thresholding=args.thresholding, plan=args.plan,
           eval_dtype=args.eval_dtype, quant=args.quant)


if __name__ == "__main__":
    main()
