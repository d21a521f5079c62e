"""Admission draws a seed request's initial latent on the device, inside the
one jitted admission apply (`serving/scheduler._apply_admission`), from the
raw threefry key words of `PRNGKey(seed)` built in numpy (`key_words`).

The load-bearing properties:

* the key words are exactly `PRNGKey(seed)`'s, and the apply's draw is
  bit-equal to the eager `jax.random.normal(PRNGKey(seed), ...)`, over the
  seed range the benchmark's generator uses and past 32 bits;
* a request's own `x_T` lands in its slot exactly, beside seed requests
  admitted on the same tick; `x_T` wins over a seed;
* admission reads nothing back (`host_syncs{site="draw"}` stays 0) and
  counts each device draw in `serve_device_draws`;
* serving from seeds is bit-identical to serving the eager draws as `x_T`,
  at pipeline depths 1/2/3 and through a desync recovery's redraw;
* one admission executable per (B, sample shape), whatever the mix.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.engine import EngineSpec, SamplerEngine
from repro.serving import (FaultPlan, MetaFault, Request, SlotScheduler,
                           poisson_requests, run_trace)
from repro.serving.scheduler import _apply_admission, key_words

from test_serving import _eps_jx

SEEDS = (0, 1, 2 ** 31 - 2, 2 ** 32 - 1, 2 ** 32 + 7, -1)
SHAPE = (16, 4)


def _eager(seed, shape=SHAPE):
    return np.asarray(jax.random.normal(jax.random.PRNGKey(seed), shape,
                                        jnp.float32))


def _program(gaussian_dpm):
    eng = SamplerEngine(gaussian_dpm.schedule, eps=_eps_jx(gaussian_dpm))
    return eng.build_step(EngineSpec(solver="unipc", order=3, nfe=7))


def _val(sched, full):
    return sched.registry.snapshot()[full]["value"]


@pytest.mark.parametrize("seed", SEEDS)
def test_key_words_are_prngkeys(seed):
    np.testing.assert_array_equal(
        key_words(seed),
        np.asarray(jax.random.key_data(jax.random.PRNGKey(seed))))


def test_key_words_follow_x64():
    """With x64 on, PRNGKey keeps the seed's high 32 bits: so do the key
    words (with it off, the seed is truncated to 32 bits)."""
    jax.config.update("jax_enable_x64", True)
    try:
        for seed in (2 ** 32 + 7, -1, 2 ** 40 + 3):
            np.testing.assert_array_equal(
                key_words(seed),
                np.asarray(jax.random.key_data(jax.random.PRNGKey(seed))))
        assert key_words(2 ** 32 + 7).tolist() == [1, 7]
    finally:
        jax.config.update("jax_enable_x64", False)


@pytest.mark.parametrize("seed", SEEDS)
def test_admission_draw_is_the_eager_draw(gaussian_dpm, seed):
    """The apply's draw equals the eager one bit for bit; idle slots keep
    their state; the probe's host draw gives the same latent."""
    sched = SlotScheduler(_program(gaussian_dpm), 3, SHAPE)
    req = Request(rid=0, seed=seed)
    sched.submit(req)
    sched._admit()
    x = np.asarray(sched.state[0])
    np.testing.assert_array_equal(x[0], _eager(seed))
    assert not x[1:].any()
    assert _val(sched, "serve_device_draws") == 1
    assert _val(sched, 'host_syncs{site="draw"}') == 0
    np.testing.assert_array_equal(sched._draw(req), _eager(seed))
    assert _val(sched, 'host_syncs{site="draw"}') == 1


def test_mixed_admission_keeps_given_latents(gaussian_dpm):
    """Seed and x_T requests admitted on one tick: seed slots get their
    device draw, x_T slots exactly their given latent (x_T wins over a
    seed), the free slot stays as it was."""
    sched = SlotScheduler(_program(gaussian_dpm), 5, SHAPE)
    rng = np.random.default_rng(3)
    given = {1: rng.normal(size=SHAPE).astype(np.float32),
             3: rng.normal(size=SHAPE).astype(np.float32)}
    for r in (Request(rid=0, seed=2 ** 32 + 7),
              Request(rid=1, x_T=given[1]),
              Request(rid=2, seed=-1),
              Request(rid=3, seed=11, x_T=given[3])):
        sched.submit(r)
    sched._admit()
    x = np.asarray(sched.state[0])
    np.testing.assert_array_equal(x[0], _eager(2 ** 32 + 7))
    np.testing.assert_array_equal(x[1], given[1])
    np.testing.assert_array_equal(x[2], _eager(-1))
    np.testing.assert_array_equal(x[3], given[3])
    assert not x[4].any()
    assert _val(sched, "serve_admitted") == 4
    assert _val(sched, "serve_device_draws") == 2
    assert _val(sched, 'host_syncs{site="draw"}') == 0


def test_one_admission_executable_per_shape(gaussian_dpm):
    """Seed-only, mixed and x_T-only ticks of every admission count run one
    compiled apply. The sample shape is this test's own, so the module-level
    cache grows by exactly that executable."""
    shape = (5, 3)
    sched = SlotScheduler(_program(gaussian_dpm), 4, shape)
    before = _apply_admission._cache_size()
    rid = 0
    for batch in (["seed"], ["seed", "x_T"], ["x_T"] * 3, ["seed"] * 4):
        for kind in batch:
            sched.submit(Request(rid=rid, seed=rid) if kind == "seed" else
                         Request(rid=rid, x_T=np.full(shape, rid, np.float32)))
            rid += 1
        sched.drain()
    assert len(sched.completions) == rid
    assert _apply_admission._cache_size() - before == 1


@pytest.mark.parametrize("depth", (1, 2, 3))
def test_seed_requests_match_eager_x_T(gaussian_dpm, depth):
    """Serving from seeds equals serving the eager draws as x_T: the same
    completions in the same order, bit for bit — and again when a desync
    recovery requeues in-flight requests and the apply redraws them."""
    program = _program(gaussian_dpm)
    trace = [(r.rid, r.arrival, 2 ** 31 - 2 - 7919 * r.rid)
             for r in poisson_requests(9, rate=0.5, seed=5)]

    def run(by_seed, faults=None):
        sched = SlotScheduler(program, 3, (8,), pipeline_depth=depth,
                              faults=faults)
        run_trace(sched, [
            Request(rid=rid, arrival=a, seed=s) if by_seed else
            Request(rid=rid, arrival=a, x_T=_eager(s, (8,)))
            for rid, a, s in trace])
        return sched

    ref, seeded = run(False), run(True)
    assert _val(ref, "serve_device_draws") == 0
    assert _val(seeded, "serve_device_draws") \
        == _val(seeded, "serve_admitted") == 9
    assert [c.rid for c in seeded.completions] \
        == [c.rid for c in ref.completions]
    for a, b in zip(ref.completions, seeded.completions):
        np.testing.assert_array_equal(a.latent, b.latent)

    faulted = run(True, FaultPlan(metas=(MetaFault(tick=5),)))
    assert faulted._recoveries >= 1
    got = {c.rid: c for c in faulted.completions}
    assert any(c.requeues for c in got.values())
    assert _val(faulted, "serve_device_draws") \
        == _val(faulted, "serve_admitted") > 9
    assert _val(faulted, 'host_syncs{site="draw"}') == 0
    for c in ref.completions:
        np.testing.assert_array_equal(got[c.rid].latent, c.latent)
