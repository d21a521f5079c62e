"""The system under test, built as `launch.serve.serve_diffusion` builds it.

The only module of the benchmark that imports the program. It takes the
benchmark's weights, checks them against the program's own parameter
layout, wires the engine and the slot scheduler, and compiles the step
ahead of time. Everything it passes is read from the configuration file.
"""

from __future__ import annotations

import dataclasses
from dataclasses import dataclass

import jax

from bench.traffic.generate import conditioning


@dataclass
class Served:
    sched: object          # repro.serving.SlotScheduler
    rows_per_slot: int     # rows of each denoiser eval per busy slot
    compile_s: float


def model_config(config: dict):
    """The program's ModelConfig for `config`: its registered architecture
    with every size the file states."""
    from repro.configs.registry import get_config

    m = config["model"]
    base = get_config(config["arch"])
    names = {f.name for f in dataclasses.fields(base)}
    return dataclasses.replace(base, **{k: v for k, v in m.items()
                                        if k in names})


def check_layout(cfg, params) -> None:
    """The benchmark's weights must have exactly the program's layout."""
    from repro.models import api

    want = jax.eval_shape(lambda: api.init_params(cfg, jax.random.PRNGKey(0)))
    got = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
                       params)
    if jax.tree.structure(want) != jax.tree.structure(got):
        raise ValueError(f"weight layout differs from the program's: "
                         f"{jax.tree.structure(got)} vs "
                         f"{jax.tree.structure(want)}")
    for (path, w), g in zip(jax.tree_util.tree_leaves_with_path(want),
                            jax.tree.leaves(got)):
        if (w.shape, w.dtype) != (g.shape, g.dtype):
            raise ValueError(f"weight {jax.tree_util.keystr(path)}: "
                             f"{g.shape} {g.dtype}, the program has "
                             f"{w.shape} {w.dtype}")


def build(config: dict, params) -> Served:
    from repro.diffusion import VPLinear
    from repro.engine import EngineSpec
    from repro.launch.sample import build_engine
    from repro.serving import SlotScheduler

    cfg = model_config(config)
    check_layout(cfg, params)
    sch, sol, srv = config["schedule"], config["solver"], config["serving"]
    if sch["kind"] != "vp_linear":
        raise ValueError(f"schedule kind {sch['kind']!r}")
    schedule = VPLinear(beta_0=sch["beta_0"], beta_1=sch["beta_1"],
                        T=sch["T"], t_eps=sch["t_eps"])
    guided = bool(srv["guided"])
    slots = int(srv["slots"])
    engine = build_engine(cfg, params, schedule, slots, want_cfg=guided,
                          per_request_cond=True)
    spec = EngineSpec(
        solver=sol["name"], nfe=sol["nfe"], order=sol["order"],
        prediction=sol["prediction"], variant=sol["variant"],
        spacing=sol["spacing"], lower_order_final=sol["lower_order_final"],
        corrector_at_last=sol["corrector_at_last"],
        cfg_scale=float(srv["cfg_scales"][0]) if guided else 0.0)
    program = engine.build_step(spec)
    sched = SlotScheduler(program, slots, (cfg.patch_tokens, cfg.latent_dim),
                          extras_init=conditioning(config).extras_init(config),
                          pipeline_depth=int(srv["pipeline_depth"]))
    compile_s = sched.aot_compile()
    return Served(sched=sched, rows_per_slot=2 if guided else 1,
                  compile_s=compile_s)


def request(config: dict, spec):
    """The program's Request for one traffic `Spec`, its conditioning made
    by the configuration's kind."""
    from repro.serving import Request

    return Request(rid=spec.rid, seed=spec.seed, cfg_scale=spec.cfg_scale,
                   extras=conditioning(config).extras(config, spec))


def counters(sched) -> dict:
    """The scheduler's counters as {full name: value}."""
    snap = sched.registry.snapshot(include_samples=False)
    return {k: v["value"] for k, v in snap.items() if v["type"] == "counter"}
