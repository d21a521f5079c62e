"""Solver-quality telemetry: serving-time trajectory-discrepancy probe.

The tuning stack scores plans offline against a high-NFE reference run
(`tuning/objective.py`); this module moves the same measurement into
serving. A `QualityProbe` deterministically samples a fraction of COMPLETED
requests, replays each one's initial latent through a high-NFE UniPC
reference runner (fp32, unquantized, uncached — the converged trajectory),
and records the served latent's relative discrepancy

    d = || x0_served - x0_ref ||_2 / max(|| x0_ref ||_2, 1e-12)

as per-tier gauges/histograms in the metrics registry. An over-quantized or
over-cached tier that passed its tune-time parity gate but drifts in
production is then visible in the serving metrics, not only at tune time.

Cost model: each probed request pays one `ref_nfe`-eval batch-1 reference
run on the host thread, which is why the probe is opt-in
(`--probe-fraction 0`, the default, never builds it) and why selection is a
deterministic hash of the rid — the same trace probes the same requests at
every pipeline depth, keeping probe metrics inside the deterministic
snapshot slice.
"""

from __future__ import annotations

from dataclasses import replace as dc_replace
from typing import Callable, List, Optional

import numpy as np

PROBE_BUCKETS = (1e-4, 3e-4, 1e-3, 3e-3, 1e-2, 3e-2, 1e-1, 3e-1, 1.0)


def build_reference_fn(engine, spec, *, ref_nfe: int = 64,
                       ref_order: int = 3) -> Callable:
    """A jitted high-NFE reference runner with per-request conditioning.

    `tuning.objective.reference_trajectory` serves the unconditional tuning
    path (`engine.build` on the reference spec); serving requests also carry
    per-request guidance scales and conditioning extras (class ids), so this
    runner threads them through `step_fn_over_rows`'s `model_kwargs` — the
    same mechanism the serving step program uses — instead of the scan's
    baked table columns.

    `engine` must be wired fp32 / quant="none" / cache_block=0 (the
    reference measures the solver+schedule, not the serving engine's
    precision tricks); the spec handshake in `model_fn` enforces it.
    Returns `reference(x_T, g=None, extras=None) -> np.ndarray` over a
    (B, *sample) batch; `g` is one scalar guidance scale for the batch
    (None -> the spec's nominal), `extras` maps conditioning keys to scalars
    or (B,) arrays.
    """
    import jax
    import jax.numpy as jnp

    from ..core.coeffs import augment_step_rows
    from ..core.unipc import step_fn_over_rows
    from ..engine.compiler import step_guidance_profile

    ref_spec = dc_replace(spec.resolve(), solver="unipc", nfe=ref_nfe,
                          order=ref_order, prediction=None,
                          eval_dtype="float32", quant="none",
                          cache_block=0).resolve()
    tab = engine.compile(ref_spec)
    engine.check_wiring(ref_spec, tab)
    rows_np = augment_step_rows(tab)
    uses_cfg = bool(ref_spec.cfg_scale)
    if uses_cfg:
        # per-request scale x the schedule's shape, exactly like the serving
        # step program (engine._step_program): drop the absolute g column
        prof = jnp.asarray(step_guidance_profile(tab, ref_spec), jnp.float32)
        rows_np = {k: v for k, v in rows_np.items() if k != "mc_g"}
    rows = {k: jnp.asarray(v, jnp.float32) for k, v in rows_np.items()}
    n_rows = int(rows["t"].shape[0])
    K = int(rows["w_pred"].shape[-1])
    nominal = float(ref_spec.cfg_scale or 0.0)

    nets = engine.nets()

    @jax.jit
    def run(nets, x_T, g, extras):
        # the weights are an argument, as in the serving step program
        step = step_fn_over_rows(engine.model_fn(ref_spec, tab, nets), rows,
                                 sign=float(tab.sign),
                                 fused_update=ref_spec.fused_update)
        E0 = jnp.zeros((K + 1,) + x_T.shape, x_T.dtype)

        def body(carry, j):
            kw = dict(extras)
            if uses_cfg:
                kw["g"] = g * prof[j]
            return step(carry, j, model_kwargs=kw or None), None

        carry, _ = jax.lax.scan(body, (x_T, E0), jnp.arange(n_rows))
        return carry[0]

    def reference(x_T, g=None, extras=None):
        x_T = jnp.asarray(x_T, jnp.float32)
        B = x_T.shape[0]
        gv = jnp.full((B,), nominal if g is None else float(g), jnp.float32)
        ex = {}
        for k, v in (extras or {}).items():
            a = np.asarray(v)
            dt = jnp.int32 if np.issubdtype(a.dtype, np.integer) \
                else jnp.float32
            ex[k] = jnp.full((B,), v, dt) if a.ndim == 0 \
                else jnp.asarray(a, dt)
        return np.asarray(run(nets, x_T, gv, ex))

    return reference


def probe_selected(rid: int, fraction: float, salt: int = 0) -> bool:
    """Deterministic rid -> [0, 1) hash against the probe fraction: the same
    requests are probed on every run / pipeline depth of the same trace
    (Knuth multiplicative hash; no RNG state, no draw-order dependence)."""
    if fraction <= 0.0:
        return False
    if fraction >= 1.0:
        return True
    u = ((int(rid) * 2654435761 + int(salt) * 40503) % (1 << 32)) / (1 << 32)
    return u < fraction


class QualityProbe:
    """Replay sampled completions against the reference runner.

    reference_fn: `build_reference_fn`'s closure (or any
        (x_T, g, extras) -> x0_ref batch callable).
    fraction: probability a completed rid is probed (deterministic in rid).
    registry: optional `obs.metrics.MetricsRegistry` receiving, per tier
        label: `probe_requests` (counter), `probe_discrepancy` (last-value
        gauge), `probe_discrepancy_hist` (histogram over PROBE_BUCKETS).
    tracer: optional `obs.trace.Tracer`; each probe emits an instant event
        carrying rid / tier / discrepancy.
    max_probes: hard cap on replays per run (the probe is a sampled
        diagnostic, not a second serving workload).
    """

    def __init__(self, reference_fn: Callable, fraction: float,
                 registry=None, tracer=None, salt: int = 0,
                 max_probes: Optional[int] = None):
        if not (0.0 <= fraction <= 1.0):
            raise ValueError(f"probe fraction must be in [0, 1], "
                             f"got {fraction}")
        self.reference_fn = reference_fn
        self.fraction = float(fraction)
        self.registry = registry
        self.tracer = tracer
        self.salt = int(salt)
        self.max_probes = max_probes
        self.results: List[dict] = []

    def selected(self, rid: int) -> bool:
        if self.max_probes is not None and len(self.results) >= self.max_probes:
            return False
        return probe_selected(rid, self.fraction, self.salt)

    def observe(self, req, completion, x_T) -> Optional[float]:
        """Measure one completion's discrepancy (caller pre-filters with
        `selected`); returns d, or None if the rid was not sampled."""
        if not self.selected(completion.rid):
            return None
        x_T = np.asarray(x_T)[None]
        x_ref = np.asarray(self.reference_fn(
            x_T, g=req.cfg_scale, extras=req.extras))[0]
        served = np.asarray(completion.latent, np.float32)
        d = float(np.linalg.norm(served - x_ref)
                  / max(float(np.linalg.norm(x_ref)), 1e-12))
        tier = completion.tier or "default"
        self.results.append({"rid": completion.rid, "tier": tier,
                             "discrepancy": d,
                             "eval_cost": completion.eval_cost})
        if self.registry is not None:
            lbl = {"tier": tier}
            self.registry.counter(
                "probe_requests", lbl,
                help="completed requests replayed by the quality probe").inc()
            self.registry.gauge(
                "probe_discrepancy", lbl,
                help="latest trajectory discrepancy vs the high-NFE "
                     "reference").set(d)
            self.registry.histogram(
                "probe_discrepancy_hist", PROBE_BUCKETS, lbl,
                help="trajectory discrepancy distribution").observe(d)
        if self.tracer is not None:
            self.tracer.instant("probe", cat="quality",
                                args={"rid": completion.rid, "tier": tier,
                                      "discrepancy": d})
        return d

    def summary(self) -> dict:
        """{tier: {count, mean, max}} over everything probed so far."""
        by_tier: dict = {}
        for r in self.results:
            by_tier.setdefault(r["tier"], []).append(r["discrepancy"])
        return {t: {"count": len(ds),
                    "mean": float(np.mean(ds)),
                    "max": float(np.max(ds))}
                for t, ds in sorted(by_tier.items())}
