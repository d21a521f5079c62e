"""Continuous-batching serving: per-slot step function + request scheduler.

The acceptance property: a batch of requests started at *staggered* ticks
through the scheduler produces, per request, latents matching the uniform
`build()` scan for the same (solver, order, nfe, seed, cfg-scale) — across
solvers and with per-request guidance scales. Plus scheduler invariants
(eval count == ticks, occupancy, gang-mode degradation) and the 1-device
mesh/SERVE_RULES bit-identity of both engine paths.
"""

from dataclasses import replace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.diffusion import GaussianDPM
from repro.engine import EngineSpec, SamplerEngine
from repro.serving import Request, SlotScheduler, poisson_requests, run_trace


def _eps_jx(dpm):
    """Gaussian-DPM eps-net that accepts scalar or per-sample (B,) t."""
    sched = dpm.schedule

    def eps(x, t):
        t = jnp.asarray(t)
        a = jnp.exp(sched.log_alpha_jax(t))
        sig = jnp.sqrt(1 - a * a)
        if t.ndim == 1:
            bshape = (-1,) + (1,) * (x.ndim - 1)
            a, sig = a.reshape(bshape), sig.reshape(bshape)
        return sig * (x - a * dpm.mu) / (a * a * dpm.s ** 2 + sig * sig)

    return eps


def _cfg_engine(vp):
    cond = GaussianDPM(vp, mu=0.7, s=0.35)
    uncond = GaussianDPM(vp, mu=-0.4, s=0.5)
    eps_c, eps_u = _eps_jx(cond), _eps_jx(uncond)

    def eps_stacked(xx, t):
        x1, x2 = jnp.split(xx, 2, axis=0)
        tt = jnp.asarray(t)
        t1, t2 = (jnp.split(tt, 2, axis=0) if tt.ndim == 1 else (tt, tt))
        return jnp.concatenate([eps_c(x1, t1), eps_u(x2, t2)], axis=0)

    return SamplerEngine(vp, eps=eps_c, eps_stacked=eps_stacked,
                         eps_uncond=eps_u)


def _x_T(rid, d=8):
    return np.random.default_rng(100 + rid).normal(size=(d,)).astype(np.float32)


def _staggered_serve(engine, spec, rids, arrivals, slots, cfg_scales=None):
    """Run rids through the scheduler with the given arrival ticks; returns
    {rid: latent}."""
    program = engine.build_step(spec)
    sched = SlotScheduler(program, slots, (8,))
    reqs = [Request(rid=r, arrival=float(a), x_T=_x_T(r),
                    cfg_scale=None if cfg_scales is None else cfg_scales[i])
            for i, (r, a) in enumerate(zip(rids, arrivals))]
    run_trace(sched, reqs)
    return {c.rid: c.latent for c in sched.completions}, sched


# ---------------------------------------------------------------------------
# heterogeneous-batch parity (the acceptance criterion)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("solver,order", [
    ("unipc", 3), ("dpmpp", 2), ("deis", 3), ("pndm", 4), ("ddim", 1),
])
def test_staggered_requests_match_uniform_scan(gaussian_dpm, solver, order):
    """Six requests admitted at staggered ticks over three slots == the
    uniform build() scan per request, <=1e-5 fp32."""
    eng = SamplerEngine(gaussian_dpm.schedule, eps=_eps_jx(gaussian_dpm))
    spec = EngineSpec(solver=solver, order=order, nfe=8)
    rids = list(range(6))
    got, sched = _staggered_serve(eng, spec, rids,
                                  arrivals=[0, 0, 2, 5, 7, 11], slots=3)
    xs = jnp.asarray(np.stack([_x_T(r) for r in rids]))
    ref = np.asarray(eng.build(spec)(xs))
    assert len(got) == len(rids)
    for i, r in enumerate(rids):
        np.testing.assert_allclose(got[r], ref[i], atol=1e-5, rtol=0)
    # invariant: one batched eval per tick, every request on its full budget
    assert sched.evals == sched.ticks
    assert all(c.evals == sched.program.n_rows for c in sched.completions)


def test_per_request_guidance_scales_match_uniform_scan(vp):
    """Per-slot cfg: one compiled program serves requests at different
    guidance scales; each matches a uniform scan built at that scale."""
    eng = _cfg_engine(vp)
    spec = EngineSpec(solver="unipc", order=3, nfe=8, cfg_scale=2.0)
    scales = [1.0, 2.0, 3.5, 0.0, 2.0]
    rids = list(range(5))
    got, _ = _staggered_serve(eng, spec, rids, arrivals=[0, 0, 1, 4, 6],
                              slots=2, cfg_scales=scales)
    for r, s in zip(rids, scales):
        ref_spec = replace(spec, cfg_scale=s)
        ref = np.asarray(eng.build(ref_spec)(
            jnp.asarray(_x_T(r))[None, :]))[0]
        np.testing.assert_allclose(got[r], ref, atol=1e-5, rtol=0,
                                   err_msg=f"rid={r} cfg_scale={s}")


def test_per_request_cfg_with_schedule_and_thresholding(vp):
    """Scheduled guidance + dynamic thresholding survive the per-slot path:
    the table contributes the schedule *profile*, the slot its scale."""
    eng = _cfg_engine(vp)
    spec = EngineSpec(solver="unipc", order=2, nfe=8, cfg_scale=2.0,
                      cfg_schedule="linear", cfg_scale_end=1.0,
                      thresholding=True)
    got, _ = _staggered_serve(eng, spec, [0, 1], arrivals=[0, 3], slots=2,
                              cfg_scales=[2.0, 2.0])
    ref = np.asarray(eng.build(spec)(
        jnp.asarray(np.stack([_x_T(0), _x_T(1)]))))
    np.testing.assert_allclose(got[0], ref[0], atol=1e-5, rtol=0)
    np.testing.assert_allclose(got[1], ref[1], atol=1e-5, rtol=0)


# ---------------------------------------------------------------------------
# scheduler mechanics
# ---------------------------------------------------------------------------


def test_scheduler_invariants_and_occupancy(gaussian_dpm):
    eng = SamplerEngine(gaussian_dpm.schedule, eps=_eps_jx(gaussian_dpm))
    program = eng.build_step(EngineSpec(solver="dpmpp", order=2, nfe=6))
    sched = SlotScheduler(program, slots=3, sample_shape=(8,))
    reqs = poisson_requests(7, rate=0.6, seed=3)
    m = run_trace(sched, reqs)
    assert m.completed == 7 and m.evals == m.ticks
    assert 0.0 < m.occupancy <= 1.0
    assert m.evals_per_latent >= program.n_rows / sched.slots
    # per-request NFE accounting: every completion consumed the full grid
    assert all(c.evals == program.n_rows for c in sched.completions)
    # latency can never undercut the service time
    assert m.latency_ticks_p50 >= program.n_rows


def test_gang_mode_admits_only_into_empty_batch(gaussian_dpm):
    eng = SamplerEngine(gaussian_dpm.schedule, eps=_eps_jx(gaussian_dpm))
    program = eng.build_step(EngineSpec(solver="ddim", order=1, nfe=4))
    sched = SlotScheduler(program, slots=2, sample_shape=(8,), gang=True)
    for r in range(3):
        sched.submit(Request(rid=r, x_T=_x_T(r)))
    sched.tick()
    assert sched.active == 2 and len(sched.queue) == 1
    # mid-flight ticks must NOT admit the queued request
    sched.tick()
    assert sched.active == 2 and len(sched.queue) == 1
    sched.drain()
    assert len(sched.completions) == 3


def test_continuous_beats_gang_at_2x_arrival_rate(gaussian_dpm):
    """The serving win: at 2x the slot-capacity arrival rate, continuous
    batching finishes the trace sooner (higher throughput) and wastes fewer
    slot-evals per latent than sequential full-batch serving."""
    eng = SamplerEngine(gaussian_dpm.schedule, eps=_eps_jx(gaussian_dpm))
    spec = EngineSpec(solver="unipc", order=3, nfe=8)
    slots = 4

    def run(gang):
        program = eng.build_step(spec)
        sched = SlotScheduler(program, slots, (8,), gang=gang)
        rate = 2.0 * slots / program.n_rows
        return run_trace(sched, poisson_requests(16, rate, seed=7))

    cont, gang = run(False), run(True)
    assert cont.completed == gang.completed == 16
    assert cont.throughput_per_tick > gang.throughput_per_tick
    assert cont.evals_per_latent <= gang.evals_per_latent


def test_cfg_request_on_uncond_program_is_rejected(gaussian_dpm):
    """A request carrying a guidance scale must not be silently served
    unguided by a program compiled without cfg."""
    eng = SamplerEngine(gaussian_dpm.schedule, eps=_eps_jx(gaussian_dpm))
    program = eng.build_step(EngineSpec(solver="unipc", order=2, nfe=4))
    sched = SlotScheduler(program, slots=2, sample_shape=(8,))
    with pytest.raises(ValueError, match="without guidance"):
        sched.submit(Request(rid=0, cfg_scale=3.0))
    # an explicit 0.0 is the unguided path and stays accepted
    sched.submit(Request(rid=1, cfg_scale=0.0, x_T=_x_T(1)))
    sched.drain()
    assert len(sched.completions) == 1


def test_latency_uses_trace_clock_across_idle_gaps(gaussian_dpm):
    """Completion latency is measured on the arrival clock: a request after
    a long idle gap (which the trace driver fast-forwards over) still gets
    latency >= its own service time, never a negative value."""
    eng = SamplerEngine(gaussian_dpm.schedule, eps=_eps_jx(gaussian_dpm))
    program = eng.build_step(EngineSpec(solver="unipc", order=2, nfe=4))
    sched = SlotScheduler(program, slots=2, sample_shape=(8,))
    reqs = [Request(rid=0, x_T=_x_T(0), arrival=0.0),
            Request(rid=1, x_T=_x_T(1), arrival=50.0)]
    run_trace(sched, reqs)
    lats = {c.rid: c.latency_ticks for c in sched.completions}
    assert lats[0] == program.n_rows
    assert lats[1] == program.n_rows  # admitted immediately after the gap


def test_idle_slots_are_identity_and_poison_free(gaussian_dpm):
    """Ticks with idle slots must not corrupt the active ones, and an idle
    slot's state must stay fixed (the init row is an identity update)."""
    eng = SamplerEngine(gaussian_dpm.schedule, eps=_eps_jx(gaussian_dpm))
    program = eng.build_step(EngineSpec(solver="unipc", order=3, nfe=6))
    sched = SlotScheduler(program, slots=3, sample_shape=(8,))
    sched.submit(Request(rid=0, x_T=_x_T(0)))
    before = np.asarray(sched.state[0][1:])
    for _ in range(program.n_rows):
        sched.tick()
    np.testing.assert_array_equal(np.asarray(sched.state[0][1:]), before)
    ref = np.asarray(eng.build(EngineSpec(solver="unipc", order=3, nfe=6))(
        jnp.asarray(_x_T(0))[None, :]))[0]
    np.testing.assert_allclose(sched.completions[0].latent, ref,
                               atol=1e-5, rtol=0)


def test_per_request_class_conditioning_is_slot_independent():
    """A dit request's class conditioning rides the request (scheduler
    extras), not the slot: the same (seed, class_id, cfg_scale) request
    produces the same latent no matter which slot admission lands it in."""
    from repro.configs.registry import get_config
    from repro.diffusion import VPLinear
    from repro.launch.sample import NULL_CLASS_ID, build_engine
    from repro.models import api

    cfg = get_config("dit-cifar").reduced()
    params = api.init_params(cfg, jax.random.PRNGKey(0))
    engine = build_engine(cfg, params, VPLinear(), 2, 0, want_cfg=True,
                          per_request_cond=True)
    program = engine.build_step(
        EngineSpec(solver="unipc", order=2, nfe=3, cfg_scale=2.0))

    def serve(reqs):
        sched = SlotScheduler(program, 2,
                              (cfg.patch_tokens, cfg.latent_dim),
                              extras_init={"class_ids": NULL_CLASS_ID})
        run_trace(sched, reqs)
        return {c.rid: c.latent for c in sched.completions}

    probe = dict(seed=42, cfg_scale=3.0, extras={"class_ids": 7})
    # alone -> slot 0
    solo = serve([Request(rid=9, **probe)])
    # behind an earlier request -> slot 1
    staggered = serve([Request(rid=0, seed=1, arrival=0.0,
                               extras={"class_ids": 3}),
                       Request(rid=9, arrival=1.0, **probe)])
    np.testing.assert_array_equal(solo[9], staggered[9])


# ---------------------------------------------------------------------------
# plan banks: mixed-tier batches (DESIGN.md §10)
# ---------------------------------------------------------------------------


def _tier_specs():
    return {"fast": EngineSpec(solver="unipc", nfe=5, order=2),
            "balanced": EngineSpec(solver="unipc", nfe=8, order=3),
            "quality": EngineSpec(solver="unipc", nfe=12, order=3)}


def test_mixed_tier_batch_matches_per_tier_uniform_scans(gaussian_dpm):
    """The bank acceptance property: fast/balanced/quality requests served
    out of ONE compiled StepProgram match each tier's own uniform build()
    scan <= 1e-5 fp32."""
    eng = SamplerEngine(gaussian_dpm.schedule, eps=_eps_jx(gaussian_dpm))
    tiers = _tier_specs()
    program = eng.build_bank(tiers)
    assert set(program.tiers) == set(tiers)
    sched = SlotScheduler(program, 3, (8,))
    names = ["fast", "balanced", "quality", "quality", "fast", "balanced"]
    reqs = [Request(rid=r, arrival=float(a), x_T=_x_T(r), tier=names[r])
            for r, a in zip(range(6), [0, 0, 1, 3, 6, 9])]
    run_trace(sched, reqs)
    got = {c.rid: c for c in sched.completions}
    assert len(got) == 6
    for r, name in enumerate(names):
        ref = np.asarray(eng.build(tiers[name])(
            jnp.asarray(_x_T(r))[None, :]))[0]
        np.testing.assert_allclose(got[r].latent, ref, atol=1e-5, rtol=0,
                                   err_msg=f"rid={r} tier={name}")
        # per-tier NFE accounting: evals == that tier's own row count
        assert got[r].evals == tiers[name].nfe + 1
        assert got[r].tier == name
    assert sched.evals == sched.ticks


def test_bank_with_per_request_guidance_scales(vp):
    """Tiers and per-request cfg compose: a bank program serves requests at
    different tiers AND different guidance scales, each matching the uniform
    scan built at that (tier, scale)."""
    eng = _cfg_engine(vp)
    tiers = {"fast": EngineSpec(solver="unipc", nfe=4, order=2,
                                cfg_scale=2.0),
             "quality": EngineSpec(solver="unipc", nfe=9, order=3,
                                   cfg_scale=2.0)}
    program = eng.build_bank(tiers)
    sched = SlotScheduler(program, 2, (8,))
    cases = [(0, "fast", 1.0), (1, "quality", 3.0), (2, "fast", 2.0)]
    reqs = [Request(rid=r, arrival=float(i), x_T=_x_T(r), tier=t,
                    cfg_scale=s) for i, (r, t, s) in enumerate(cases)]
    run_trace(sched, reqs)
    got = {c.rid: c.latent for c in sched.completions}
    for r, t, s in cases:
        ref_spec = replace(tiers[t], cfg_scale=s)
        ref = np.asarray(eng.build(ref_spec)(
            jnp.asarray(_x_T(r))[None, :]))[0]
        np.testing.assert_allclose(got[r], ref, atol=1e-5, rtol=0,
                                   err_msg=f"rid={r} tier={t} scale={s}")


def test_bank_from_tuned_plans_round_trips_through_serving(vp, tmp_path):
    """save_bank -> load_bank -> build_bank(tables=plan tables) serves each
    tier exactly as the plan's own uniform scan."""
    from repro.tuning import SolverPlan, load_bank, save_bank

    dpm = GaussianDPM(vp)
    eng = SamplerEngine(vp, eps=_eps_jx(dpm))
    plans = {"fast": SolverPlan.default(4, order=2),
             "quality": SolverPlan.default(8, order=3)}
    path = str(tmp_path / "bank.json")
    save_bank(path, plans)
    loaded = load_bank(path)
    tier_specs = {k: EngineSpec(solver="unipc", nfe=p.nfe,
                                order=max(p.orders))
                  for k, p in loaded.items()}
    tables = {k: p.compile(vp) for k, p in loaded.items()}
    program = eng.build_bank(tier_specs, tables)
    sched = SlotScheduler(program, 2, (8,))
    reqs = [Request(rid=0, x_T=_x_T(0), tier="fast"),
            Request(rid=1, x_T=_x_T(1), tier="quality", arrival=1.0)]
    run_trace(sched, reqs)
    got = {c.rid: c.latent for c in sched.completions}
    for r, k in ((0, "fast"), (1, "quality")):
        ref = np.asarray(eng.build(tier_specs[k],
                                   table=tables[k])(
            jnp.asarray(_x_T(r))[None, :]))[0]
        np.testing.assert_allclose(got[r], ref, atol=1e-5, rtol=0)


def test_tier_tags_are_validated(gaussian_dpm):
    eng = SamplerEngine(gaussian_dpm.schedule, eps=_eps_jx(gaussian_dpm))
    bank = eng.build_bank({"fast": EngineSpec(solver="unipc", nfe=4,
                                              order=2)})
    sched = SlotScheduler(bank, 2, (8,))
    with pytest.raises(ValueError, match="unknown tier"):
        sched.submit(Request(rid=0, tier="turbo"))
    with pytest.raises(ValueError, match="tag requests"):
        sched.submit(Request(rid=1))          # untagged on a bank
    single = eng.build_step(EngineSpec(solver="unipc", nfe=4, order=2))
    sched2 = SlotScheduler(single, 2, (8,))
    with pytest.raises(ValueError, match="single plan"):
        sched2.submit(Request(rid=2, tier="fast"))


def test_bank_rejects_mixed_prediction_and_guidance(gaussian_dpm, vp):
    eng = SamplerEngine(gaussian_dpm.schedule, eps=_eps_jx(gaussian_dpm))
    with pytest.raises(ValueError, match="prediction"):
        eng.build_bank({"a": EngineSpec(solver="unipc", nfe=4),
                        "b": EngineSpec(solver="ddim", nfe=4,
                                        prediction="noise")})
    eng2 = _cfg_engine(vp)
    with pytest.raises(ValueError, match="guidance scale"):
        eng2.build_bank({"a": EngineSpec(solver="unipc", nfe=4,
                                         cfg_scale=2.0),
                         "b": EngineSpec(solver="unipc", nfe=6,
                                         cfg_scale=3.0)})


def test_per_tier_metrics_reported(gaussian_dpm):
    from repro.serving import poisson_requests

    eng = SamplerEngine(gaussian_dpm.schedule, eps=_eps_jx(gaussian_dpm))
    program = eng.build_bank(_tier_specs())
    sched = SlotScheduler(program, 3, (8,))
    reqs = poisson_requests(9, rate=0.5, seed=5,
                            tiers=["fast", "balanced", "quality"])
    m = run_trace(sched, reqs)
    assert m.completed == 9
    assert set(m.per_tier) == {"fast", "balanced", "quality"}
    for name, spec in _tier_specs().items():
        assert m.per_tier[name]["completed"] == 3
        assert m.per_tier[name]["evals"] == spec.nfe + 1
        assert m.per_tier[name]["latency_ticks_p50"] >= spec.nfe + 1


# ---------------------------------------------------------------------------
# scheduler edge cases (ISSUE 6 satellite)
# ---------------------------------------------------------------------------


def test_burst_arrivals_beyond_slots_serve_fifo(gaussian_dpm):
    """3x-slots requests all arriving at tick 0: admission drains the queue
    strictly FIFO (rid order), nothing is dropped, and the queue backlog
    shrinks only as slots free."""
    eng = SamplerEngine(gaussian_dpm.schedule, eps=_eps_jx(gaussian_dpm))
    program = eng.build_step(EngineSpec(solver="unipc", order=2, nfe=4))
    sched = SlotScheduler(program, slots=2, sample_shape=(8,))
    for r in range(6):
        sched.submit(Request(rid=r, x_T=_x_T(r)))
    sched.tick()
    assert sched.active == 2 and len(sched.queue) == 4
    # mid-flight ticks keep the backlog: no slot frees before n_rows ticks
    for _ in range(program.n_rows - 1):
        sched.tick()
    assert len(sched.completions) == 2 and len(sched.queue) == 4
    sched.tick()                      # freed slots refill on the NEXT tick
    assert sched.active == 2 and len(sched.queue) == 2
    sched.drain()
    assert [c.rid for c in sched.completions] == list(range(6))
    finishes = [c.finish_tick for c in sched.completions]
    assert finishes == sorted(finishes)


def test_nfe_budget_one_request_completes(gaussian_dpm):
    """The minimum budget: nfe=1 compiles to 2 rows (init + one step) and a
    request consumes exactly those two evals."""
    eng = SamplerEngine(gaussian_dpm.schedule, eps=_eps_jx(gaussian_dpm))
    spec = EngineSpec(solver="unipc", order=1, nfe=1)
    program = eng.build_step(spec)
    assert program.n_rows == 2
    sched = SlotScheduler(program, slots=2, sample_shape=(8,))
    m = run_trace(sched, [Request(rid=0, x_T=_x_T(0))])
    assert m.completed == 1 and m.ticks == 2
    c = sched.completions[0]
    assert c.evals == 2 and c.latency_ticks == 2
    ref = np.asarray(eng.build(spec)(jnp.asarray(_x_T(0))[None, :]))[0]
    np.testing.assert_allclose(c.latent, ref, atol=1e-5, rtol=0)


def test_empty_trace_and_single_tier_metrics(gaussian_dpm):
    """Zero-completion metrics must not divide by zero, and a bank trace
    that exercises only one tier reports per_tier for that tier alone."""
    eng = SamplerEngine(gaussian_dpm.schedule, eps=_eps_jx(gaussian_dpm))
    program = eng.build_bank(_tier_specs())
    sched = SlotScheduler(program, slots=2, sample_shape=(8,))
    m0 = run_trace(sched, [])
    assert m0.completed == 0 and m0.ticks == 0 and m0.evals == 0
    assert m0.occupancy == 0.0 and m0.throughput_rps == 0.0
    assert m0.per_tier is None
    m1 = run_trace(sched, [Request(rid=0, x_T=_x_T(0), tier="fast"),
                           Request(rid=1, x_T=_x_T(1), tier="fast")])
    assert m1.completed == 2
    assert set(m1.per_tier) == {"fast"}
    assert m1.per_tier["fast"]["completed"] == 2


def test_trace_clock_resets_on_scheduler_reuse(gaussian_dpm):
    """A second trace on the same scheduler restarts the arrival clock at 0:
    its metrics cover only the new run (counter snapshots) and its
    completions' latencies are not inflated by the first run's clock."""
    eng = SamplerEngine(gaussian_dpm.schedule, eps=_eps_jx(gaussian_dpm))
    program = eng.build_step(EngineSpec(solver="unipc", order=2, nfe=4))
    sched = SlotScheduler(program, slots=2, sample_shape=(8,))
    m1 = run_trace(sched, [Request(rid=0, x_T=_x_T(0), arrival=3.0)])
    assert sched.clock is None        # the driver always restores tick time
    m2 = run_trace(sched, [Request(rid=1, x_T=_x_T(1), arrival=0.0)])
    assert m1.completed == m2.completed == 1
    assert m2.ticks == program.n_rows == m2.evals
    lat = {c.rid: c.latency_ticks for c in sched.completions}
    assert lat[0] == program.n_rows   # measured from ITS arrival, not tick 0
    assert lat[1] == program.n_rows   # run-2 clock restarted at 0
    assert len(sched.completions) == 2


# ---------------------------------------------------------------------------
# 1-device mesh under SERVE_RULES: bit-identical to no mesh context
# ---------------------------------------------------------------------------


def _dit_setup(batch=2, nfe=4):
    from repro.configs.registry import get_config
    from repro.diffusion import VPLinear
    from repro.launch.sample import build_engine
    from repro.models import api

    cfg = get_config("dit-cifar").reduced()
    params = api.init_params(cfg, jax.random.PRNGKey(0))
    engine = build_engine(cfg, params, VPLinear(), batch, 0)
    spec = EngineSpec(solver="unipc", order=3, nfe=nfe)
    x_T = jax.random.normal(jax.random.PRNGKey(1),
                            (batch, cfg.patch_tokens, cfg.latent_dim),
                            jnp.float32)
    return engine, spec, x_T


def test_scan_path_bit_identical_under_serve_rules_mesh():
    from repro.launch.mesh import make_host_mesh
    from repro.parallel.sharding import SERVE_RULES, sharding_rules

    engine, spec, x_T = _dit_setup()
    plain = np.asarray(engine.build(spec)(x_T))
    with sharding_rules(make_host_mesh(), SERVE_RULES):
        meshed = np.asarray(engine.build(spec)(x_T))
    np.testing.assert_array_equal(plain, meshed)


def test_step_path_bit_identical_under_serve_rules_mesh():
    from repro.launch.mesh import make_host_mesh
    from repro.parallel.sharding import SERVE_RULES, sharding_rules

    engine, spec, x_T = _dit_setup()

    def serve(mesh_ctx):
        program = engine.build_step(spec)
        sched = SlotScheduler(program, 2,
                              sample_shape=x_T.shape[1:])
        reqs = [Request(rid=i, arrival=float(2 * i),
                        x_T=np.asarray(x_T[i])) for i in range(2)]
        if mesh_ctx:
            with sharding_rules(make_host_mesh(), SERVE_RULES):
                run_trace(sched, reqs)
        else:
            run_trace(sched, reqs)
        return {c.rid: c.latent for c in sched.completions}

    plain, meshed = serve(False), serve(True)
    for r in plain:
        np.testing.assert_array_equal(plain[r], meshed[r])
    # and the staggered step path agrees with the uniform scan on the dit net
    ref = np.asarray(engine.build(spec)(x_T))
    for i in range(2):
        np.testing.assert_allclose(plain[i], ref[i], atol=1e-5, rtol=0)


# ---------------------------------------------------------------------------
# array extras: a caption per request (text-conditioned DiT)
# ---------------------------------------------------------------------------

def _caption(seed, n, shape=(6, 4)):
    out = np.zeros(shape, np.float32)
    out[:n] = np.random.default_rng(seed).normal(size=(n, shape[1]))
    return out


def test_array_extras_land_in_their_own_slots(gaussian_dpm):
    """Per-request array extras keep their trailing shape and dtype through
    extras_init, admission and the admission apply: each request's caption
    and length land in its own slot, and empty slots keep extras_init, also
    after a slot is reused."""
    eng = SamplerEngine(gaussian_dpm.schedule, eps=_eps_jx(gaussian_dpm))
    program = eng.build_step(EngineSpec(solver="unipc", order=2, nfe=3))
    init = {"caption": np.full((6, 4), -1.0, np.float32), "caption_len": 1}

    def ignore_extras(state, meta, g, extras):
        return program.flight(program.nets, state, meta, g, None)

    sched = SlotScheduler(program, 4, (8,), extras_init=init,
                          step_override=ignore_extras)
    assert sched.extras["caption"].shape == (4, 6, 4)
    assert sched.extras["caption"].dtype == jnp.float32
    assert sched.extras["caption_len"].dtype == jnp.int32
    lens = {0: 1, 1: 6, 2: 3}
    for rid, n in lens.items():
        sched.submit(Request(rid=rid, seed=rid, extras={
            "caption": _caption(rid, n), "caption_len": n}))
    sched.tick()
    cap = np.asarray(sched.extras["caption"])
    got_lens = np.asarray(sched.extras["caption_len"])
    for slot, req in enumerate(sched.slot_req[:3]):
        np.testing.assert_array_equal(cap[slot], _caption(req.rid,
                                                          lens[req.rid]))
        assert got_lens[slot] == lens[req.rid]
    np.testing.assert_array_equal(cap[3], init["caption"])
    assert got_lens[3] == 1
    # a request without the caption key takes extras_init's, in a reused slot
    while sched.active:
        sched.tick()
    sched.submit(Request(rid=7, seed=7, extras={"caption_len": 4}))
    sched.tick()
    slot = sched.slot_req.index(next(r for r in sched.slot_req
                                     if r is not None and r.rid == 7))
    np.testing.assert_array_equal(np.asarray(sched.extras["caption"])[slot],
                                  init["caption"])
    assert int(np.asarray(sched.extras["caption_len"])[slot]) == 4


def _text_program(slots):
    from repro.configs.registry import get_config
    from repro.diffusion import VPLinear
    from repro.launch.sample import build_engine
    from repro.models import api

    cfg = get_config("pixart-xl2-512").reduced(patch_tokens=16)
    params = api.init_params(cfg, jax.random.PRNGKey(0))
    # a fresh init zeroes the output projection; perturb every weight so
    # the output depends on the caption
    leaves, tree = jax.tree.flatten(params)
    keys = jax.random.split(jax.random.PRNGKey(1), len(leaves))
    params = jax.tree.unflatten(tree, [
        a + 0.05 * jax.random.normal(k, a.shape, a.dtype)
        for a, k in zip(leaves, keys)])
    engine = build_engine(cfg, params, VPLinear(), slots, 0, want_cfg=True,
                          per_request_cond=True)
    program = engine.build_step(
        EngineSpec(solver="unipc", order=2, nfe=3, cfg_scale=2.0))
    init = {"caption": np.zeros((cfg.caption_tokens, cfg.caption_dim),
                                np.float32), "caption_len": 1}
    return cfg, program, init


def test_per_request_caption_conditioning_is_slot_independent():
    """A text request's caption rides the request, not the slot: the same
    (seed, caption, length, cfg_scale) request gives the same latent bit for
    bit alone in slot 0 and behind another request in slot 1, and a
    different caption gives a different latent."""
    cfg, program, init = _text_program(2)
    shape = (cfg.caption_tokens, cfg.caption_dim)

    def serve(reqs):
        sched = SlotScheduler(program, 2, (cfg.patch_tokens, cfg.latent_dim),
                              extras_init=init)
        run_trace(sched, reqs)
        return {c.rid: c.latent for c in sched.completions}

    probe = dict(seed=42, cfg_scale=3.0,
                 extras={"caption": _caption(5, 9, shape), "caption_len": 9})
    solo = serve([Request(rid=9, **probe)])
    staggered = serve([Request(rid=0, seed=1, arrival=0.0, extras={
        "caption": _caption(6, 16, shape), "caption_len": 16}),
        Request(rid=9, arrival=1.0, **probe)])
    np.testing.assert_array_equal(solo[9], staggered[9])
    other = serve([Request(rid=9, seed=42, cfg_scale=3.0, extras={
        "caption": _caption(5, 3, shape), "caption_len": 3})])
    assert np.abs(other[9] - solo[9]).max() > 1e-3
