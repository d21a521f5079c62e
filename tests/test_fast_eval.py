"""The fast-eval denoiser path (DESIGN.md §11): flash attention in the model
stack, the fused adaLN kernel, the bf16 serving eval, and donated step
buffers. Acceptance: the new default eval path matches the eager fp32 path
<= 1e-5; bf16 is opt-in with its tolerance asserted here; the donated AOT
step is bit-identical to the undonated one."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.registry import get_config
from repro.kernels.adaln_modulate import ops as ad_ops, ref as ad_ref
from repro.kernels.flash_attention import ops as fa_ops, ref as fa_ref
from repro.models import api


# ---------------------------------------------------------------------------
# flash attention: non-causal DiT parity + dispatch policy
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("B,Hq,Hkv,S,D", [
    (2, 4, 4, 64, 32),     # dit-cifar tokens (sub-block S)
    (1, 4, 4, 256, 32),    # dit-i256 tokens (two S tiles)
    (2, 4, 2, 200, 32),    # non-block-multiple S, GQA
    (1, 2, 1, 130, 64),    # remainder of 2 over one tile
])
def test_flash_noncausal_matches_sdpa_at_dit_shapes(B, Hq, Hkv, S, D):
    """The kernel (interpret mode) == the model-side seq-major sdpa for the
    non-causal full-token path the DiT blocks run, including token counts
    that are not block multiples."""
    from repro.models.layers import sdpa

    ks = jax.random.split(jax.random.PRNGKey(S), 3)
    q = jax.random.normal(ks[0], (B, S, Hq, D))
    k = jax.random.normal(ks[1], (B, S, Hkv, D))
    v = jax.random.normal(ks[2], (B, S, Hkv, D))
    want = sdpa(q, k, v, causal=False)
    for backend in ("interpret", "jnp"):
        got = fa_ops.attention(
            q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
            v.transpose(0, 2, 1, 3), causal=False,
            backend=backend).transpose(0, 2, 1, 3)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-5, atol=2e-5, err_msg=backend)


def test_flash_attention_dispatch_policy():
    """The explicit pallas|interpret|jnp policy of unipc_update/ops.py:
    platform selection, explicit pinning, unknown backends rejected."""
    assert fa_ops.select_backend("tpu") == "pallas"
    assert fa_ops.select_backend("cpu") == "jnp"
    assert fa_ops.select_backend("gpu") == "jnp"
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (1, 2, 64, 16))
    want = fa_ref.attention(q, q, q, causal=True)
    # jnp backend IS the oracle; interpret runs the real kernel
    got_jnp = fa_ops.attention(q, q, q, causal=True, backend="jnp")
    np.testing.assert_array_equal(np.asarray(got_jnp), np.asarray(want))
    got_int = fa_ops.attention(q, q, q, causal=True, backend="interpret")
    np.testing.assert_allclose(np.asarray(got_int), np.asarray(want),
                               rtol=2e-5, atol=2e-5)
    with pytest.raises(ValueError, match="backend"):
        fa_ops.attention(q, q, q, backend="cuda")


def test_attention_chunk_remainder(rng):
    """The chunked path is no longer dead for S % chunk != 0: remainder
    query chunks are padded and sliced, same softmax."""
    from repro.models.layers import chunked_sdpa, sdpa

    ks = jax.random.split(rng, 3)
    B, S, H, D = 2, 100, 4, 16  # 100 = 3*32 + 4 remainder
    q = jax.random.normal(ks[0], (B, S, H, D))
    k = jax.random.normal(ks[1], (B, S, H, D))
    v = jax.random.normal(ks[2], (B, S, H, D))
    for causal, window in ((True, None), (False, None), (True, 24)):
        want = sdpa(q, k, v, causal=causal, sliding_window=window)
        got = chunked_sdpa(q, k, v, causal=causal, sliding_window=window,
                           chunk=32)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# fused adaLN kernel
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("B,T,D", [
    (2, 64, 128),    # dit-cifar reduced block shape
    (4, 256, 128),   # dit-i256 reduced block shape
    (3, 100, 130),   # remainder T tile + non-128-multiple D (masked LN)
    (1, 7, 48),      # sub-tile everything
])
def test_adaln_modulate_kernel_vs_ref(B, T, D):
    ks = jax.random.split(jax.random.PRNGKey(B * T + D), 4)
    x = jax.random.normal(ks[0], (B, T, D))
    sh = jax.random.normal(ks[1], (B, D))
    sc = jax.random.normal(ks[2], (B, D))
    g = jax.random.normal(ks[3], (B, D))
    want = ad_ref.modulate(x, sh, sc)
    got = ad_ops.modulate(x, sh, sc, backend="interpret")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
    want_g = ad_ref.gate_residual(x, g, x)
    got_g = ad_ops.gate_residual(x, g, x, backend="interpret")
    np.testing.assert_allclose(np.asarray(got_g), np.asarray(want_g),
                               rtol=1e-5, atol=1e-5)


def test_adaln_matches_inline_dit_math(rng):
    """The op == the pre-PR inline chain `layernorm({}, h)*(1+sc)+sh`
    bit-for-bit at fp32 (jnp backend) and <=1e-5 through the kernel."""
    from repro.models.layers import layernorm

    ks = jax.random.split(rng, 3)
    x = jax.random.normal(ks[0], (2, 64, 128))
    sh = jax.random.normal(ks[1], (2, 128))
    sc = jax.random.normal(ks[2], (2, 128))
    inline = layernorm({}, x) * (1 + sc[:, None]) + sh[:, None]
    np.testing.assert_array_equal(
        np.asarray(ad_ops.modulate(x, sh, sc, backend="jnp")),
        np.asarray(inline))
    np.testing.assert_allclose(
        np.asarray(ad_ops.modulate(x, sh, sc, backend="interpret")),
        np.asarray(inline), rtol=1e-5, atol=1e-5)


def test_adaln_dispatch_policy():
    assert ad_ops.select_backend("tpu") == "pallas"
    assert ad_ops.select_backend("cpu") == "jnp"
    x = jnp.ones((1, 8, 16))
    with pytest.raises(ValueError, match="backend"):
        ad_ops.modulate(x, jnp.ones((1, 16)), jnp.ones((1, 16)),
                        backend="cuda")
    with pytest.raises(ValueError, match="backend"):
        ad_ops.gate_residual(x, jnp.ones((1, 16)), x, backend="cuda")


# ---------------------------------------------------------------------------
# the DiT fast-eval path end to end
# ---------------------------------------------------------------------------


def _noisy(params, rng, scale=0.05):
    """Perturb every float leaf: the adaLN-zero init makes an untrained DiT
    output exactly zero (zero out_proj, zero gates), which would make any
    output-parity assertion vacuous."""
    leaves, treedef = jax.tree.flatten(params)
    ks = jax.random.split(rng, len(leaves))
    return jax.tree.unflatten(treedef, [
        a + scale * jax.random.normal(k, a.shape, a.dtype)
        if jnp.issubdtype(a.dtype, jnp.floating) else a
        for a, k in zip(leaves, ks)])


def _dit_eval(cfg, params, x, t, ids):
    net = api.eps_network(cfg)
    return np.asarray(jax.jit(
        lambda x, t: net(params, x, t, {"class_ids": ids}))(x, t))


def test_dit_interpret_kernels_match_default(rng):
    """dit_apply with the real kernels (interpret mode) == the default
    (jnp-dispatch) eval <= 1e-5 — the served-path parity acceptance."""
    cfg = get_config("dit-cifar").reduced()
    params = _noisy(api.init_params(cfg, rng), jax.random.PRNGKey(9))
    B = 2
    x = jax.random.normal(jax.random.PRNGKey(1),
                          (B, cfg.patch_tokens, cfg.latent_dim))
    t = jnp.full((B,), 0.4)
    ids = jnp.asarray([3, 7], jnp.int32)
    default = _dit_eval(cfg, params, x, t, ids)
    assert np.abs(default).max() > 0  # the noisy net is non-degenerate
    pinned = dataclasses.replace(cfg, attention_backend="interpret",
                                 adaln_backend="interpret")
    kern = _dit_eval(pinned, params, x, t, ids)
    np.testing.assert_allclose(kern, default, rtol=1e-5, atol=1e-5)


def test_bf16_eval_sample_close_to_fp32(vp):
    """End-to-end engine sample with eval_dtype=bfloat16 vs fp32: the solver
    state stays fp32, so the drift is the network's bf16 rounding carried
    through NFE evals. Documented bound (DESIGN.md §11): <= 1e-2 relative
    L-inf on the sampled latents (measured ~2.5e-3 on this net) — far above
    fp32 path noise, far below sample-visible error."""
    from repro.engine import EngineSpec
    from repro.launch.sample import build_engine

    cfg = get_config("dit-cifar").reduced()
    params = _noisy(api.init_params(cfg, jax.random.PRNGKey(0)),
                    jax.random.PRNGKey(9))
    x_T = jax.random.normal(jax.random.PRNGKey(1),
                            (2, cfg.patch_tokens, cfg.latent_dim))
    outs = {}
    for ed in ("float32", "bfloat16"):
        eng = build_engine(cfg, params, vp, 2, eval_dtype=ed)
        spec = EngineSpec(solver="unipc", order=2, nfe=6, eval_dtype=ed)
        outs[ed] = np.asarray(eng.build(spec)(x_T))
    assert outs["bfloat16"].dtype == np.float32  # state stays fp32
    err = np.abs(outs["bfloat16"] - outs["float32"]).max()
    rel = err / np.abs(outs["float32"]).max()
    assert rel < 1e-2, f"bf16 eval drifted {rel} relative from fp32"
    assert err > 0  # bf16 must actually have run in reduced precision


def test_eval_dtype_validation():
    from repro.engine import EngineSpec
    from repro.launch.sample import build_engine

    with pytest.raises(ValueError, match="eval_dtype"):
        EngineSpec(solver="unipc", eval_dtype="float16").resolve()
    with pytest.raises(ValueError, match="eval_dtype"):
        build_engine(get_config("dit-cifar").reduced(), {}, None, 2,
                     eval_dtype="float16")


def test_engine_and_spec_eval_dtype_must_match(vp):
    """A bf16-wired engine rejects fp32 specs (and vice versa): the net-side
    cast and the engine-side fp32 boundary cannot silently desynchronize."""
    from repro.engine import EngineSpec
    from repro.launch.sample import build_engine

    cfg = get_config("dit-cifar").reduced()
    params = api.init_params(cfg, jax.random.PRNGKey(0))
    eng16 = build_engine(cfg, params, vp, 2, eval_dtype="bfloat16")
    with pytest.raises(ValueError, match="wired for 'bfloat16'"):
        eng16.build(EngineSpec(solver="unipc", nfe=4))
    eng32 = build_engine(cfg, params, vp, 2)
    with pytest.raises(ValueError, match="wired for 'float32'"):
        eng32.build(EngineSpec(solver="unipc", nfe=4,
                               eval_dtype="bfloat16"))


def test_bank_tiers_must_share_eval_dtype(gaussian_dpm):
    from repro.engine import EngineSpec, SamplerEngine

    def eps(x, t):
        return jnp.zeros_like(x)

    eng = SamplerEngine(gaussian_dpm.schedule, eps=eps)
    with pytest.raises(ValueError, match="eval_dtype"):
        eng.build_bank({
            "a": EngineSpec(solver="unipc", nfe=4, order=2),
            "b": EngineSpec(solver="unipc", nfe=6, order=2,
                            eval_dtype="bfloat16"),
        })


# ---------------------------------------------------------------------------
# donated step buffers
# ---------------------------------------------------------------------------


def _gauss_engine(gaussian_dpm):
    from repro.engine import SamplerEngine

    sched = gaussian_dpm.schedule

    def eps(x, t):
        t = jnp.asarray(t)
        a = jnp.exp(sched.log_alpha_jax(t))
        sig = jnp.sqrt(1 - a * a)
        if t.ndim == 1:
            bshape = (-1,) + (1,) * (x.ndim - 1)
            a, sig = a.reshape(bshape), sig.reshape(bshape)
        return sig * (x - a * gaussian_dpm.mu) / (
            a * a * gaussian_dpm.s ** 2 + sig * sig)

    return SamplerEngine(sched, eps=eps)


def test_donated_step_bit_identical_to_undonated(gaussian_dpm):
    """The AOT-compiled step with donated (x, E) buffers produces bit-identical
    trajectories to the undonated program — donation only recycles memory."""
    from repro.engine import EngineSpec

    eng = _gauss_engine(gaussian_dpm)
    spec = EngineSpec(solver="unipc", order=2, nfe=5)
    slots, shape = 3, (6,)
    prog_d = eng.build_step(spec, donate=True)
    prog_u = eng.build_step(spec, donate=False)
    x0 = jax.random.normal(jax.random.PRNGKey(0), (slots,) + shape)

    def run(prog):
        state = prog.init_state(slots, shape)
        state = (state[0] + x0, state[1])
        # every slot busy from row 0 with the full budget: tick i runs row i
        meta = np.array(prog.init_meta(slots))
        meta[3] = 1
        meta = jnp.asarray(meta)
        # AOT-compile exactly as the scheduler does
        compiled = prog.flight.lower(prog.nets, state, meta, None,
                                     None).compile()
        outs = []
        for _ in range(prog.n_rows):
            state, meta, _ = compiled(prog.nets, state, meta, None, None)
            outs.append(np.asarray(state[0]))
        return outs

    for a, b in zip(run(prog_d), run(prog_u)):
        np.testing.assert_array_equal(a, b)


def test_donated_step_consumes_input_state(gaussian_dpm):
    """Donation is real: after a donated step call, the input buffers are
    gone (deleted on CPU/TPU) — the scheduler's reassign-always contract."""
    from repro.engine import EngineSpec

    eng = _gauss_engine(gaussian_dpm)
    prog = eng.build_step(EngineSpec(solver="unipc", order=2, nfe=4))
    state = prog.init_state(2, (4,))
    new_state, _, _ = prog.step_flight(state, prog.init_meta(2))
    assert new_state[0].shape == state[0].shape
    with pytest.raises(RuntimeError, match="deleted"):
        _ = np.asarray(state[0]) + 1


def test_scheduler_serves_with_donated_program(gaussian_dpm):
    """The scheduler end-to-end on the (default) donated program matches the
    uniform scan — the existing parity property survives donation."""
    from repro.engine import EngineSpec
    from repro.serving import Request, SlotScheduler, run_trace

    eng = _gauss_engine(gaussian_dpm)
    spec = EngineSpec(solver="unipc", order=2, nfe=5)
    prog = eng.build_step(spec)
    sched = SlotScheduler(prog, 2, (6,))
    sched.aot_compile()
    xs = [np.random.default_rng(40 + i).normal(size=(6,)).astype(np.float32)
          for i in range(4)]
    reqs = [Request(rid=i, arrival=float(a), x_T=xs[i])
            for i, a in enumerate([0, 0, 2, 3])]
    run_trace(sched, reqs)
    ref = np.asarray(eng.build(spec)(jnp.asarray(np.stack(xs))))
    got = {c.rid: c.latent for c in sched.completions}
    for i in range(4):
        np.testing.assert_allclose(got[i], ref[i], atol=1e-5, rtol=0)
