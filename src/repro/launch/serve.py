"""Batched serving loop. Token models: prefill a batch of prompts, then
greedy/temperature decode with the per-family cache. Diffusion models (dit
family): one request = one latent to generate, served with *continuous
batching* (DESIGN.md §9) — a request-level scheduler over `--batch` slots
drives the engine's per-slot step function, so requests admit the moment a
slot frees, carry their own seed and guidance scale, and emit without waiting
for a batch to drain. One batched (optionally 2B cond+uncond stacked) network
eval per tick; any registered solver; CPU-runnable at reduced scale.

    PYTHONPATH=src python -m repro.launch.serve --arch qwen2-0.5b --reduced \
        --batch 4 --prompt-len 32 --gen 32
    PYTHONPATH=src python -m repro.launch.serve --arch dit-cifar --reduced \
        --batch 8 --nfe 10 --solver dpmpp --order 2 --cfg-scale 2.0
    PYTHONPATH=src python -m repro.launch.serve --arch dit-cifar --reduced \
        --batch 4 --nfe 10 --arrival-rate 0.4 --requests 16   # Poisson traffic
    PYTHONPATH=src python -m repro.launch.serve --arch dit-cifar --reduced \
        --batch 8 --tiers fast,balanced,quality --arrival-rate 0.5
        # quality tiers: one compiled plan-bank program (DESIGN.md §10)
"""

from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from ..configs.registry import get_config
from ..data.synthetic import TokenStream, caption, class_ids, stub_embeds
from ..models import api
from .compile_cache import enable_compile_cache


def serve(arch: str, *, reduced=True, batch=4, prompt_len=32, gen=32,
          temperature=0.0, seed=0):
    cfg = get_config(arch)
    if reduced:
        cfg = cfg.reduced()
    rng = jax.random.PRNGKey(seed)
    params = api.init_params(cfg, rng)
    max_len = prompt_len + gen

    stream = TokenStream(cfg.vocab_size, prompt_len, batch, seed)
    batch_in = {"tokens": jnp.asarray(stream.block(0)["tokens"])}
    if cfg.family == "vlm":
        batch_in["image_embeds"] = jnp.asarray(
            stub_embeds(batch, cfg.image_tokens, cfg.d_model, seed))
    if cfg.family == "audio":
        batch_in["audio_embeds"] = jnp.asarray(
            stub_embeds(batch, cfg.audio_frames, cfg.d_model, seed))

    prefill = jax.jit(lambda p, b: api.prefill_fn(cfg)(p, b, max_len))
    decode = jax.jit(lambda p, c, t, pos: api.decode_fn(cfg)(p, c, t, pos))

    t0 = time.time()
    logits, cache = prefill(params, batch_in)
    prefill_s = time.time() - t0

    def sample_tok(lg, key):
        if temperature <= 0:
            return jnp.argmax(lg[:, -1], axis=-1).astype(jnp.int32)
        return jax.random.categorical(key, lg[:, -1] / temperature).astype(jnp.int32)

    toks = []
    tok = sample_tok(logits, rng)
    t0 = time.time()
    for i in range(gen):
        toks.append(np.asarray(tok))
        logits, cache = decode(params, cache, tok[:, None],
                               jnp.int32(prompt_len + i))
        rng, sub = jax.random.split(rng)
        tok = sample_tok(logits, sub)
    jax.block_until_ready(logits)
    decode_s = time.time() - t0
    out = np.stack(toks, axis=1)
    print(f"prefill {prefill_s*1e3:.1f} ms; decode {gen} steps "
          f"{decode_s*1e3:.1f} ms ({decode_s/gen*1e3:.2f} ms/tok, "
          f"batch={batch})")
    return out


def serve_diffusion(arch: str, *, reduced=True, batch=4, nfe=10, order=3,
                    solver="unipc", fused_update=True, cfg_scale=0.0,
                    cfg_schedule="constant", thresholding=False, seed=0,
                    arrival_rate=None, trace=None, requests=None,
                    plan_bank=None, tiers=None, eval_dtype="float32",
                    quant="none", pipeline_depth=2, trace_out=None,
                    metrics_out=None, metrics_every=None,
                    probe_fraction=0.0, probe_ref_nfe=64,
                    resilience=None, faults=None, report=None):
    """Continuous-batching diffusion serving through the engine's per-slot
    step program (`SamplerEngine.build_step` + `serving.SlotScheduler`):
    `batch` slots, requests admitted the tick a slot frees, per-request
    seed/cfg-scale, one batched eps-net eval per tick. `cfg_scale` turns on
    fused classifier-free guidance — ONE (2B-batched, cond+uncond stacked)
    network call per tick, the per-slot guidance scale riding the step state;
    `thresholding` adds dynamic thresholding of the x0 prediction. On TPU the
    fused-update dispatch selects the single-pass Pallas combine, and the
    slot batch shards over the data axis under SERVE_RULES.

    Traffic: `trace` (a JSON arrival trace) or `arrival_rate` (Poisson,
    requests per tick) serve asynchronous traffic; with neither, `batch`
    requests all arrive at tick 0 (classic batch serving, now through the
    same scheduler). The step program is compiled ahead of time
    (`jit(...).lower(...).compile()`), so compile and steady-state serving
    are reported separately. Returns the finished latents ordered by rid.

    `pipeline_depth` (DESIGN.md §13) is how many ticks the scheduler keeps
    in flight: the default 2 overlaps host bookkeeping and admission with
    device execution (JAX async dispatch, trailing-stream readback of
    finished latents); 1 is the synchronous legacy loop. Finished latents
    and tick-denominated metrics are bit-identical across depths.

    Quality tiers (DESIGN.md §10): `plan_bank` (a JSON bank of tuned
    `SolverPlan`s from `repro.launch.tune --bank`) or `tiers` (a list of
    hand-set tier names from `engine.default_tier_specs`) compiles ONE
    `StepProgram` serving every tier — requests tagged fast/balanced/quality
    coexist in the same batch with per-slot row offsets. Untagged generated
    traffic cycles through the tiers.

    Resilience (DESIGN.md §16): `resilience` (a `serving.ResilienceConfig`)
    bounds the admission queue with a shed policy, expires queued requests
    past their TTL, re-admits requests whose latent came back non-finite
    (walking a degraded-tier fallback chain), and recovers from host/device
    desync instead of raising. `faults` (a `serving.FaultPlan`, CLI
    `--inject-faults`) deterministically injects NaN latents, meta-counter
    corruption, and admission clock skew to exercise those paths — requests
    no fault touched still finish bit-identical to a clean run.

    Observability (DESIGN.md §15): `trace_out` records per-tick / per-request
    spans into a Chrome trace_event JSON (opens in chrome://tracing);
    `metrics_out` writes the metrics artifact (registry snapshot delta +
    derived ServeMetrics + Prometheus exposition, with periodic rows every
    `metrics_every` ticks); `probe_fraction` > 0 replays that fraction of
    completions against a `probe_ref_nfe`-step fp32 UniPC reference and
    records per-tier trajectory-discrepancy gauges. All three are off by
    default — the untraced path is byte-for-byte the old serving loop.
    Render the artifacts with `python -m repro.launch.obsreport`.

    `report` (a dict, optional) receives the run's `ServeMetrics`
    (`metrics`), the AOT compile seconds (`compile_s`) and the compiled
    step's HLO text (`step_text`), for callers that check the served
    program itself (chip_smoke.py).
    """
    from ..engine import EngineSpec, default_tier_specs
    from ..diffusion import VPLinear
    from ..obs import QualityProbe, Tracer, build_reference_fn
    from ..obs import metrics as obsm
    from ..obs.report import write_metrics_artifact
    from ..serving import Request, SlotScheduler, load_trace, poisson_requests, run_trace
    from .sample import NULL_CLASS_ID, build_engine

    cfg = get_config(arch)
    if reduced:
        cfg = cfg.reduced()
    rng = jax.random.PRNGKey(seed)
    params = api.init_params(cfg, rng)
    # a cached plan bank (DESIGN.md §12) decides the engine's cache wiring,
    # so load it before build_engine; every cached tier must agree on the one
    # static block boundary the compiled program bakes in
    plans = None
    cache_block = 0
    if plan_bank is not None:
        from ..tuning import load_bank

        plans = load_bank(plan_bank)
        blocks = sorted({p.cache_block for p in plans.values()
                         if p.cache_block})
        if len(blocks) > 1:
            raise ValueError(
                f"plan bank {plan_bank} mixes cache boundaries {blocks}; one "
                f"compiled program serves one static cache_block — retune "
                f"the bank with a single --cache-block")
        cache_block = blocks[0] if blocks else 0
        if cache_block and cfg_scale != 0.0:
            raise ValueError(
                f"plan bank {plan_bank} schedules feature reuse "
                f"(cache_block={cache_block}) but --cfg-scale={cfg_scale}; "
                f"cached programs serve unconditional sampling only")
        # a quant-tuned bank records its tier in plan meta (launch/tune.py);
        # one quantized param tree serves the whole program, so the bank
        # must be uniform and must agree with an explicit --quant
        bank_quants = sorted({p.meta.get("quant", "none")
                              for p in plans.values()})
        if len(bank_quants) > 1:
            raise ValueError(
                f"plan bank {plan_bank} mixes quant tiers {bank_quants}; "
                f"one quantized param tree serves one compiled program — "
                f"retune the bank with a single --quant")
        if bank_quants[0] != "none":
            if quant not in ("none", bank_quants[0]):
                raise ValueError(
                    f"plan bank {plan_bank} was tuned for "
                    f"quant={bank_quants[0]!r} but --quant={quant!r}; a "
                    f"plan's parity gate only holds for the tier it was "
                    f"scored against")
            quant = bank_quants[0]
    engine = build_engine(cfg, params, VPLinear(), batch, seed,
                          want_cfg=cfg_scale != 0.0, per_request_cond=True,
                          eval_dtype=eval_dtype, cache_block=cache_block,
                          quant=quant)
    spec = EngineSpec(solver=solver, nfe=nfe, order=order,
                      cfg_scale=cfg_scale, cfg_schedule=cfg_schedule,
                      thresholding=thresholding, fused_update=fused_update,
                      eval_dtype=eval_dtype, quant=quant)
    common = dict(cfg_scale=cfg_scale, cfg_schedule=cfg_schedule,
                  thresholding=thresholding, fused_update=fused_update,
                  eval_dtype=eval_dtype, cache_block=cache_block,
                  quant=quant)
    tier_names = None
    if plans is not None:
        schedule = engine.schedule
        tier_specs = {
            name: EngineSpec(solver="unipc", nfe=p.nfe,
                             order=max(p.orders), prediction=p.prediction,
                             **common)
            for name, p in plans.items()}
        tables = {name: p.compile(schedule) for name, p in plans.items()}
        program = engine.build_bank(tier_specs, tables)
        tier_names = list(plans)
    elif tiers:
        all_specs = default_tier_specs(**common)
        unknown = [t for t in tiers if t not in all_specs]
        if unknown:
            raise ValueError(f"unknown tiers {unknown}; hand-set tiers are "
                             f"{sorted(all_specs)}")
        program = engine.build_bank({t: all_specs[t] for t in tiers})
        tier_names = list(tiers)
    else:
        program = engine.build_step(spec)
    # idle slots are conditioned on the null class; every request carries its
    # own class id (drawn from its seed), so conditioning is reproducible
    # regardless of which slot the scheduler admits it into
    tracer = None
    if trace_out is not None:
        tracer = Tracer(meta={"arch": arch, "slots": batch,
                              "pipeline_depth": pipeline_depth,
                              "eval_dtype": eval_dtype, "quant": quant,
                              "cache_block": cache_block,
                              "cfg_scale": cfg_scale,
                              "tiers": tier_names})
    probe = None
    if probe_fraction > 0.0:
        # the reference engine is deliberately plain — fp32, unquantized,
        # uncached — so the probe measures what the SERVING tier's precision
        # tricks cost, against the converged solver trajectory
        ref_engine = build_engine(cfg, params, VPLinear(), batch, seed,
                                  want_cfg=cfg_scale != 0.0,
                                  per_request_cond=True)
        probe = QualityProbe(
            build_reference_fn(ref_engine, spec, ref_nfe=probe_ref_nfe),
            probe_fraction)
    if cfg.caption_dim:
        # an idle slot holds a one-token empty caption; its output is never
        # read
        extras_init = {"caption": np.zeros((cfg.caption_tokens,
                                            cfg.caption_dim), np.float32),
                       "caption_len": 1}
    else:
        extras_init = {"class_ids": NULL_CLASS_ID}
    sched = SlotScheduler(program, batch,
                          (cfg.patch_tokens, cfg.latent_dim),
                          extras_init=extras_init,
                          pipeline_depth=pipeline_depth,
                          tracer=tracer, probe=probe,
                          resilience=resilience, faults=faults)
    compile_s = sched.aot_compile()
    if trace is not None:
        reqs = load_trace(trace)
    elif arrival_rate is not None:
        n_req = requests if requests is not None else 4 * batch
        reqs = poisson_requests(n_req, arrival_rate, seed=seed,
                                base_seed=seed, tiers=tier_names)
    else:
        reqs = [Request(rid=i, seed=seed + i) for i in range(batch)]
    for r in reqs:
        # single assignment point for untagged requests on a tiered program
        # (trace requests may carry their own tags)
        if tier_names is not None and r.tier is None:
            r.tier = tier_names[r.rid % len(tier_names)]
        if cfg.caption_dim and "caption" not in (r.extras or {}):
            emb, n = caption(r.seed, cfg.caption_tokens, cfg.caption_dim)
            r.extras = {**(r.extras or {}), "caption": emb,
                        "caption_len": n}
        elif not cfg.caption_dim and "class_ids" not in (r.extras or {}):
            r.extras = {**(r.extras or {}),
                        "class_ids": int(class_ids(1, seed=r.seed)[0])}
    snap0 = sched.registry.snapshot()
    snapshot_log = [] if metrics_out is not None else None
    if metrics_out is not None and not metrics_every:
        metrics_every = 8
    m = run_trace(sched, reqs, snapshot_every=metrics_every,
                  snapshot_log=snapshot_log)
    if report is not None:
        report.update(metrics=m, compile_s=compile_s,
                      step_text=sched.compiled_text())
    if trace_out is not None:
        exported = tracer.export(trace_out)
        print(f"trace: {len(exported['traceEvents'])} events "
              f"({tracer.dropped} dropped) -> {trace_out}")
    if metrics_out is not None:
        write_metrics_artifact(
            metrics_out,
            metrics=obsm.delta(snap0, sched.registry.snapshot()),
            serve_metrics=m.row(),
            static={"mode": m.mode, "slots": m.slots, "n_rows": m.n_rows,
                    "pipeline_depth": m.pipeline_depth},
            exposition=sched.registry.exposition(),
            rows=snapshot_log,
            probe=probe.summary() if probe is not None else None)
        print(f"metrics: {len(snapshot_log)} periodic rows -> {metrics_out}")
    if probe is not None:
        for t, row in sorted(probe.summary().items()):
            print(f"  probe tier {t}: {row['count']} replayed, "
                  f"discrepancy mean {row['mean']:.3e} max {row['max']:.3e} "
                  f"(vs fp32 unipc-3 nfe={probe_ref_nfe})")
    mode = (f"bank[{','.join(tier_names)}]" if tier_names
            else f"{solver} nfe={nfe} order={order}")
    print(f"diffusion slots={batch} {mode} depth={m.pipeline_depth} "
          f"cfg={cfg_scale} fused_update={fused_update} eval={eval_dtype} "
          f"quant={quant}: "
          f"compile {compile_s:.2f}s (AOT), tick {m.tick_s*1e3:.1f} ms, "
          f"{m.completed}/{m.requests} requests, "
          f"throughput {m.throughput_rps:.2f} req/s, "
          f"latency p50/p95 {m.latency_s_p50*1e3:.0f}/"
          f"{m.latency_s_p95*1e3:.0f} ms, occupancy {m.occupancy:.2f}, "
          f"evals/latent {m.evals_per_latent:.1f}")
    if (m.rejected or m.retries or m.failed or m.recoveries
            or m.faults_injected):
        print(f"  resilience: {m.rejected} rejected "
              f"({m.expired} expired), {m.degraded} shed-degraded, "
              f"{m.retries} retries, {m.failed} failed, "
              f"{m.recoveries} desync recoveries, "
              f"{m.faults_injected} faults injected")
        for ev in sched.events:
            print(f"    event {ev}")
    if m.per_tier:
        for t, row in m.per_tier.items():
            cost = (f" ({row['eval_cost']:.2f} full-eval units)"
                    if row["eval_cost"] and row["eval_cost"] != row["evals"]
                    else "")
            print(f"  tier {t}: {row['completed']} done, "
                  f"{row['evals']} evals/request{cost}, "
                  f"p50 latency {row['latency_ticks_p50']:.0f} ticks")
    # failed completions (retry budget exhausted on a non-finite latent)
    # carry poisoned arrays; never ship those
    order_by_rid = sorted((c for c in sched.completions if c.ok),
                          key=lambda c: c.rid)
    if not order_by_rid:  # e.g. an empty trace
        return np.zeros((0, cfg.patch_tokens, cfg.latent_dim), np.float32)
    return np.stack([c.latent for c in order_by_rid], axis=0)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=32)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--nfe", type=int, default=None,
                    help="diffusion serving: sampler steps (default 10; "
                         "incompatible with --plan-bank/--tiers, which "
                         "carry per-tier schedules)")
    ap.add_argument("--order", type=int, default=None,
                    help="diffusion serving: solver order (default 3; "
                         "incompatible with --plan-bank/--tiers)")
    from ..engine import SOLVERS
    ap.add_argument("--solver", default=None, choices=sorted(SOLVERS),
                    help="diffusion serving: any engine-registered solver "
                         "(default unipc; incompatible with "
                         "--plan-bank/--tiers)")
    ap.add_argument("--no-fused-update", action="store_true",
                    help="diffusion serving: pin the jnp op-chain combine")
    ap.add_argument("--cfg-scale", type=float, default=0.0,
                    help="diffusion serving: fused classifier-free guidance "
                         "scale (0 = off; one batched eval per step)")
    ap.add_argument("--cfg-schedule", default="constant",
                    choices=["constant", "linear", "cosine"])
    ap.add_argument("--thresholding", action="store_true",
                    help="diffusion serving: dynamic thresholding (off by "
                         "default)")
    ap.add_argument("--eval-dtype", default="float32",
                    choices=["float32", "bfloat16"],
                    help="diffusion serving: eps-network eval precision "
                         "(default fp32); bfloat16 halves the network's "
                         "serving HBM traffic — solver state and combine "
                         "weights stay fp32 (DESIGN.md §11)")
    ap.add_argument("--quant", default="none",
                    choices=["none", "w8a16", "w8a8", "fp8a16", "w4a16"],
                    help="diffusion serving: quantized denoiser tier "
                         "(DESIGN.md §14); calibrates + installs int8/fp8 "
                         "weight records before compiling the step program. "
                         "A quant-tuned plan bank pins its own tier")
    ap.add_argument("--arrival-rate", type=float, default=None,
                    help="diffusion serving: Poisson request arrivals, in "
                         "requests per tick (one tick = one batched eval); "
                         "omit for all requests at tick 0")
    ap.add_argument("--trace", default=None,
                    help="diffusion serving: JSON arrival trace "
                         "(list of {rid, seed, arrival, cfg_scale})")
    ap.add_argument("--requests", type=int, default=None,
                    help="diffusion serving: request count for "
                         "--arrival-rate traffic (default 4x batch)")
    ap.add_argument("--pipeline-depth", type=int, default=2,
                    help="diffusion serving: ticks kept in flight "
                         "(DESIGN.md §13); 1 = synchronous loop, >= 2 "
                         "overlaps host bookkeeping with device execution; "
                         "finished latents are bit-identical at any depth")
    ap.add_argument("--trace-out", default=None,
                    help="diffusion serving: write a Chrome trace_event JSON "
                         "of per-tick and per-request spans (open in "
                         "chrome://tracing; DESIGN.md §15)")
    ap.add_argument("--metrics-out", default=None,
                    help="diffusion serving: write the metrics artifact "
                         "(registry snapshot + derived ServeMetrics + "
                         "Prometheus exposition); render with "
                         "python -m repro.launch.obsreport")
    ap.add_argument("--metrics-every", type=int, default=None,
                    help="periodic snapshot row cadence in executed ticks "
                         "for --metrics-out (default 8)")
    ap.add_argument("--probe-fraction", type=float, default=0.0,
                    help="diffusion serving: replay this fraction of "
                         "completed requests against a high-NFE fp32 "
                         "reference and record per-tier trajectory-"
                         "discrepancy gauges (0 = off)")
    ap.add_argument("--probe-ref-nfe", type=int, default=64,
                    help="NFE of the probe's UniPC-3 reference run")
    ap.add_argument("--max-queue", type=int, default=None,
                    help="diffusion serving resilience (DESIGN.md §16): "
                         "bound on queued requests; past it new submissions "
                         "are shed per --shed-policy (default unbounded)")
    ap.add_argument("--shed-policy", default="reject",
                    choices=["reject", "degrade"],
                    help="what happens to submissions past --max-queue: "
                         "'reject' returns a typed Rejection, 'degrade' "
                         "remaps them to --degrade-tier first")
    ap.add_argument("--degrade-tier", default=None,
                    help="tier shed requests are remapped to under "
                         "--shed-policy degrade (needs --plan-bank/--tiers)")
    ap.add_argument("--ttl", type=float, default=None,
                    help="diffusion serving resilience: admission deadline "
                         "in tick-clock units past arrival; queued requests "
                         "whose deadline passes before a slot frees are "
                         "expired, not served late")
    ap.add_argument("--max-retries", type=int, default=0,
                    help="diffusion serving resilience: re-admissions after "
                         "a non-finite latent (same seed) before emitting a "
                         "failed completion (default 0)")
    ap.add_argument("--retry-fallback", default=None,
                    help="comma-separated safer-tier chain walked on retry "
                         "(needs --plan-bank/--tiers); omit to retry on the "
                         "same tier")
    ap.add_argument("--recovery", default="recover",
                    choices=["recover", "raise"],
                    help="host/device desync handling: 'recover' drains the "
                         "pipeline, resyncs from device state and requeues "
                         "(the default); 'raise' keeps the legacy hard "
                         "RuntimeError")
    ap.add_argument("--inject-faults", default=None,
                    help="diffusion serving chaos (DESIGN.md §16): "
                         "semicolon-separated fault clauses, e.g. "
                         "'nan:rid=2,step=1;meta:tick=6;skew:tick=3,delta=9' "
                         "or 'seed:7,requests=8,nfe=4' for a seeded plan")
    bank = ap.add_mutually_exclusive_group()
    bank.add_argument("--plan-bank", default=None,
                      help="diffusion serving: JSON bank of tuned SolverPlans"
                           " (repro.launch.tune --bank); serves every tier "
                           "from one compiled step program")
    bank.add_argument("--tiers", default=None,
                      help="diffusion serving: comma-separated hand-set "
                           "quality tiers (fast,balanced,quality) served "
                           "from one compiled step program")
    scale = ap.add_mutually_exclusive_group()
    scale.add_argument("--reduced", action="store_true",
                       help="reduced CPU-scale config (the default)")
    scale.add_argument("--full", action="store_true")
    args = ap.parse_args()
    enable_compile_cache()
    from .sample import require_dit_for_cfg
    family = require_dit_for_cfg(ap, args.arch, args.cfg_scale)
    if family != "dit" and (args.arrival_rate is not None or args.trace):
        ap.error(f"--arrival-rate/--trace drive the diffusion request "
                 f"scheduler; --arch {args.arch} is family '{family}' "
                 f"(token serving decodes a fixed batch)")
    if family != "dit" and (args.plan_bank or args.tiers):
        ap.error(f"--plan-bank/--tiers serve diffusion quality tiers; "
                 f"--arch {args.arch} is family '{family}'")
    if family != "dit" and args.eval_dtype != "float32":
        ap.error(f"--eval-dtype configures the diffusion engine's network "
                 f"eval; --arch {args.arch} is family '{family}'")
    if family != "dit" and args.quant != "none":
        ap.error(f"--quant configures the diffusion engine's denoiser; "
                 f"--arch {args.arch} is family '{family}'")
    if ((args.plan_bank or args.tiers)
            and (args.solver is not None or args.nfe is not None
                 or args.order is not None)):
        ap.error("--solver/--nfe/--order configure a single-plan program; "
                 "a plan bank / tier program takes its per-tier schedules "
                 "from the bank (drop those flags)")
    solver = args.solver if args.solver is not None else "unipc"
    nfe = args.nfe if args.nfe is not None else 10
    order = args.order if args.order is not None else 3
    if args.arrival_rate is not None and args.arrival_rate <= 0:
        ap.error(f"--arrival-rate must be > 0 requests per tick, "
                 f"got {args.arrival_rate}")
    if family != "dit" and args.pipeline_depth != 2:
        ap.error(f"--pipeline-depth configures the diffusion serving loop; "
                 f"--arch {args.arch} is family '{family}'")
    if args.pipeline_depth < 1:
        ap.error(f"--pipeline-depth must be >= 1, got {args.pipeline_depth}")
    if family != "dit" and (args.trace_out or args.metrics_out
                            or args.probe_fraction):
        ap.error(f"--trace-out/--metrics-out/--probe-fraction instrument the "
                 f"diffusion serving loop; --arch {args.arch} is family "
                 f"'{family}'")
    if not 0.0 <= args.probe_fraction <= 1.0:
        ap.error(f"--probe-fraction must be in [0, 1], "
                 f"got {args.probe_fraction}")
    wants_resilience = (args.max_queue is not None or args.ttl is not None
                        or args.max_retries or args.retry_fallback
                        or args.degrade_tier or args.shed_policy != "reject"
                        or args.recovery != "recover")
    if family != "dit" and (wants_resilience or args.inject_faults):
        ap.error(f"--max-queue/--ttl/--max-retries/--inject-faults and "
                 f"friends configure the diffusion serving scheduler; "
                 f"--arch {args.arch} is family '{family}'")
    resilience = None
    if wants_resilience:
        from ..serving import ResilienceConfig
        resilience = ResilienceConfig(
            max_queue=args.max_queue, shed_policy=args.shed_policy,
            degrade_tier=args.degrade_tier, default_ttl=args.ttl,
            max_retries=args.max_retries,
            fallback=(tuple(args.retry_fallback.split(","))
                      if args.retry_fallback else ()),
            recovery=args.recovery)
    faults = None
    if args.inject_faults:
        from ..serving import parse_fault_spec
        faults = parse_fault_spec(args.inject_faults)
    if family == "dit":
        serve_diffusion(args.arch, reduced=not args.full, batch=args.batch,
                        nfe=nfe, order=order, solver=solver,
                        fused_update=not args.no_fused_update,
                        cfg_scale=args.cfg_scale,
                        cfg_schedule=args.cfg_schedule,
                        thresholding=args.thresholding,
                        arrival_rate=args.arrival_rate, trace=args.trace,
                        requests=args.requests, plan_bank=args.plan_bank,
                        tiers=(args.tiers.split(",") if args.tiers else None),
                        eval_dtype=args.eval_dtype, quant=args.quant,
                        pipeline_depth=args.pipeline_depth,
                        trace_out=args.trace_out,
                        metrics_out=args.metrics_out,
                        metrics_every=args.metrics_every,
                        probe_fraction=args.probe_fraction,
                        probe_ref_nfe=args.probe_ref_nfe,
                        resilience=resilience, faults=faults)
        return
    serve(args.arch, reduced=not args.full, batch=args.batch,
          prompt_len=args.prompt_len, gen=args.gen,
          temperature=args.temperature)


if __name__ == "__main__":
    main()
