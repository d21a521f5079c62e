"""Per-kernel shape/dtype sweeps: Pallas (interpret mode) vs pure-jnp oracle."""

import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.flash_attention import ops as fa_ops, ref as fa_ref
from repro.kernels.unipc_update import ops as up_ops, ref as up_ref


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("K,shape", [
    (2, (128,)), (3, (4, 100)), (5, (2, 7, 33)), (6, (1, 2048)),
    (4, (3, 128, 130)),
])
def test_unipc_update_sweep(K, shape, dtype):
    rng = jax.random.PRNGKey(K)
    t = jax.random.normal(rng, (K,) + shape, jnp.float32).astype(dtype)
    w = jax.random.normal(jax.random.PRNGKey(K + 7), (K,), jnp.float32)
    got = up_ops.weighted_combine(t, w, force_pallas=True)
    want = up_ref.weighted_combine(t, w)
    assert got.dtype == want.dtype == dtype
    tol = 1e-5 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("B,Hq,Hkv,Sq,Skv,D,causal,window", [
    (1, 4, 2, 128, 128, 64, True, None),      # GQA causal
    (2, 4, 4, 256, 256, 32, True, None),      # MHA causal
    (1, 2, 1, 200, 200, 64, True, None),      # padded (non-multiple) seq
    (1, 4, 2, 128, 384, 64, False, None),     # cross-attention shape
    (1, 4, 4, 256, 256, 64, True, 96),        # sliding window
    (1, 8, 1, 128, 128, 128, True, None),     # MQA, wide head
])
def test_flash_attention_sweep(B, Hq, Hkv, Sq, Skv, D, causal, window, dtype):
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (B, Hq, Sq, D), jnp.float32).astype(dtype)
    k = jax.random.normal(ks[1], (B, Hkv, Skv, D), jnp.float32).astype(dtype)
    v = jax.random.normal(ks[2], (B, Hkv, Skv, D), jnp.float32).astype(dtype)
    got = fa_ops.attention(q, k, v, causal=causal, window=window,
                           force_pallas=True)
    want = fa_ref.attention(q, k, v, causal=causal, window=window)
    tol = 2e-5 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol)


def test_flash_attention_matches_model_sdpa():
    """The kernel agrees with the model-side sdpa (different layout)."""
    from repro.models.layers import sdpa
    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    B, Hq, Hkv, S, D = 2, 4, 2, 128, 32
    q = jax.random.normal(ks[0], (B, S, Hq, D))
    k = jax.random.normal(ks[1], (B, S, Hkv, D))
    v = jax.random.normal(ks[2], (B, S, Hkv, D))
    want = sdpa(q, k, v, causal=True)
    got = fa_ops.attention(q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
                           v.transpose(0, 2, 1, 3), causal=True,
                           force_pallas=True).transpose(0, 2, 1, 3)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_fused_update_in_scan_sampler(vp):
    """unipc_sample_scan with the fused Pallas update == jnp path."""
    import functools
    from repro.core import make_unipc_schedule, unipc_sample_scan
    from repro.kernels.unipc_update import ops as uops

    def eps(x, t):
        a = jnp.exp(vp.log_alpha_jax(jnp.asarray(t)))
        sig = jnp.sqrt(1 - a * a)
        return sig * (x - a * 0.7) / (a * a * 0.35 ** 2 + sig * sig)

    x_T = jax.random.normal(jax.random.PRNGKey(0), (4, 16))
    us = make_unipc_schedule(vp, 6, order=2, prediction="noise")
    ref_out = unipc_sample_scan(eps, x_T, us, fused_update=False)
    # monkeypatch dispatch: force the Pallas interpret path inside the scan
    orig = uops.weighted_combine
    uops.weighted_combine = functools.partial(orig, force_pallas=True)
    try:
        fused_out = unipc_sample_scan(eps, x_T, us, fused_update=True)
    finally:
        uops.weighted_combine = orig
    np.testing.assert_allclose(np.asarray(fused_out), np.asarray(ref_out),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("K,shape", [
    (5, (2049,)),                 # 1D, one full tile + 1-lane remainder
    (3, (3, 2178,)),              # batched, remainder tile of 130
    (4, (2, 5, 1000)),            # batched, sub-tile rows (remainder only)
    (5, (3, 64, 48)),             # dit-cifar latent batch (N = 3072)
    (5, (2, 256, 32)),            # dit-i256 latent batch (N = 8192)
])
def test_unipc_update_remainder_tiles(K, shape):
    """Arbitrary (non multiple of 16*128) per-sample sizes: the boundary tile
    is padded on load and masked on store, never shifted onto valid lanes."""
    rng = jax.random.PRNGKey(K)
    t = jax.random.normal(rng, (K,) + shape, jnp.float32)
    w = jax.random.normal(jax.random.PRNGKey(K + 7), (K,), jnp.float32)
    got = up_ops.weighted_combine(t, w, force_pallas=True)
    want = up_ref.weighted_combine(t, w)
    assert got.shape == shape
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_unipc_update_bf16_accumulates_fp32():
    """bf16 terms: the kernel must accumulate in fp32 — its output matches the
    fp32-accumulated oracle on the same bf16 inputs to cast precision, far
    tighter than a bf16-accumulated chain would land."""
    K, shape = 6, (2, 4, 1000)
    t = jax.random.normal(jax.random.PRNGKey(0), (K,) + shape,
                          jnp.float32).astype(jnp.bfloat16)
    w = jax.random.normal(jax.random.PRNGKey(1), (K,), jnp.float32)
    got = up_ops.weighted_combine(t, w, force_pallas=True)
    assert got.dtype == jnp.bfloat16
    want_f32 = jnp.tensordot(w, t.astype(jnp.float32), axes=1)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want_f32), rtol=1e-2, atol=1e-2)
    # and bit-parity with the oracle, which uses the same fp32 accumulation
    want = up_ref.weighted_combine(t, w)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=1e-6, atol=1e-6)


def test_unipc_update_dispatch():
    """select_backend policy + explicit backend pinning."""
    from repro.kernels.unipc_update.kernel import TILE
    assert up_ops.select_backend(1 << 20, "cpu") == "jnp"
    assert up_ops.select_backend(1 << 20, "gpu") == "jnp"
    assert up_ops.select_backend(1 << 20, "tpu") == "pallas"
    assert up_ops.select_backend(TILE - 1, "tpu") == "jnp"  # sub-tile state
    t = jax.random.normal(jax.random.PRNGKey(0), (3, 2, 300))
    w = jax.random.normal(jax.random.PRNGKey(1), (3,))
    want = up_ref.weighted_combine(t, w)
    for backend in ("jnp", "interpret"):
        got = up_ops.weighted_combine(t, w, backend=backend)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-5)
    with pytest.raises(ValueError):
        up_ops.weighted_combine(t, w, backend="cuda")


@pytest.mark.parametrize("order", [1, 2, 3])
def test_scan_fused_default_matches_jnp_path(vp, order, monkeypatch):
    """Acceptance: unipc_sample_scan(fused_update=True) == the inline jnp
    op-chain to <= 1e-5 at fp32 on a non-tile-aligned latent shape, with the
    kernel (interpret mode) actually on the dispatched path, orders 1-3."""
    import functools
    from repro.core import make_unipc_schedule, unipc_sample_scan
    from repro.kernels.unipc_update import ops as uops

    def data(x, t):
        a = jnp.exp(vp.log_alpha_jax(jnp.asarray(t)))
        sig = jnp.sqrt(1 - a * a)
        eps = sig * (x - a * 0.4) / (a * a * 0.5 ** 2 + sig * sig)
        return (x - sig * eps) / a

    x_T = jax.random.normal(jax.random.PRNGKey(order), (2, 7, 9))
    us = make_unipc_schedule(vp, 7, order=order, prediction="data")
    ref_out = unipc_sample_scan(data, x_T, us, fused_update=False)
    monkeypatch.setattr(uops, "weighted_combine",
                        functools.partial(uops.weighted_combine,
                                          force_pallas=True))
    fused_out = unipc_sample_scan(data, x_T, us, fused_update=True)
    np.testing.assert_allclose(np.asarray(fused_out), np.asarray(ref_out),
                               rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# per-row key lengths (cross-attention over a padded caption)
# ---------------------------------------------------------------------------

def _masked_softmax_attention(q, k, v, lens):
    """The explicit oracle: scores of each row's keys past its length set to
    -inf before a float32 softmax, at HIGHEST precision."""
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                   precision=jax.lax.Precision.HIGHEST) / np.sqrt(q.shape[-1])
    ok = jnp.arange(k.shape[2])[None] < jnp.asarray(lens)[:, None]
    s = jnp.where(ok[:, None, None, :], s, -jnp.inf)
    return jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, axis=-1), v,
                      precision=jax.lax.Precision.HIGHEST)


@pytest.mark.parametrize("backend", ["interpret", "jnp"])
@pytest.mark.parametrize("skv,lens", [
    (120, [1, 57, 120]),          # 120 keys padded to one block of 128
    (300, [128, 256, 300]),       # lengths ending on block edges
    (300, [1, 129, 255]),         # live blocks 1, 2 and 2 of 3
], ids=["caption-120", "block-edges", "skipped-blocks"])
def test_flash_attention_per_row_key_length(backend, skv, lens):
    """Each row attends to its own first lens[b] keys: Sq 256 against Skv
    keys, rows of different lengths in one call. Tolerance: float32 sums in
    another order (online softmax against one softmax), ~1e-6 here."""
    ks = jax.random.split(jax.random.PRNGKey(11), 3)
    B, H, Sq, D = len(lens), 2, 256, 72
    q = jax.random.normal(ks[0], (B, H, Sq, D))
    k = jax.random.normal(ks[1], (B, H, skv, D))
    v = jax.random.normal(ks[2], (B, H, skv, D))
    got = fa_ops.attention(q, k, v, causal=False, backend=backend,
                           kv_lens=jnp.asarray(lens, jnp.int32))
    want = _masked_softmax_attention(q, k, v, lens)
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=0)


def test_flash_attention_keys_past_a_row_length_are_never_read():
    """Keys past a row's length count for nothing: large garbage in the
    rest of its last live block is masked, and NaN in the blocks wholly
    past it never enters the sums (those tiles are skipped). The blocks are
    pinned to 128: the plan would take all 256 keys in one block, and the
    skipping holds wherever a call has several kv blocks."""
    ks = jax.random.split(jax.random.PRNGKey(12), 3)
    lens = jnp.array([5, 130], jnp.int32)
    q = jax.random.normal(ks[0], (2, 2, 128, 64))
    k = jax.random.normal(ks[1], (2, 2, 256, 64))
    v = jax.random.normal(ks[2], (2, 2, 256, 64))
    pos = jnp.arange(256)[None, None, :, None]
    past = pos >= lens[:, None, None, None]
    dead = pos >= 128 * (-(-lens // 128))[:, None, None, None]
    bad = jnp.where(dead, jnp.nan, 1e3)
    kn, vn = jnp.where(past, bad, k), jnp.where(past, bad, v)
    got = fa_ops.attention(q, kn, vn, causal=False, backend="interpret",
                           kv_lens=lens, blk_q=128, blk_k=128)
    want = _masked_softmax_attention(q, k, v, lens)
    assert np.isfinite(np.asarray(got)).all()
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=0)


# sha-256 (first 32 hex digits) of the unmasked self call's output on the
# CPU backend, at the blocks `tile_plan` picks: one kv block holding every
# key, q, k and v into the dots in their own dtype, float32 accumulation
SELF_CALL_PINS = {
    ("float32", 64): "f89b7b441a1a6d090013a51e77024ee3",
    ("float32", 256): "6d132f72fb2aa50e99aaa6c5985a36a9",
    ("bfloat16", 64): "bcadf3ac1b822abaf274782b26fce4a4",
    ("bfloat16", 256): "576d40ca11bdd21d2343308a60d514a5",
}


def _self_call(dtype, S, kv_lens=None):
    """(q, k, v, the kernel's output as float32) of one self call."""
    ks = jax.random.split(jax.random.PRNGKey(6), 3)
    q, k, v = (jax.random.normal(ki, (2, 3, S, 72)).astype(dtype)
               for ki in ks)
    out = fa_ops.attention(q, k, v, causal=False, kv_lens=kv_lens,
                           backend="interpret")
    return q, k, v, np.asarray(out.astype("float32"))


def _digest(out):
    return hashlib.sha256(np.ascontiguousarray(out).tobytes()).hexdigest()


@pytest.mark.parametrize("dtype,S", sorted(SELF_CALL_PINS))
def test_flash_attention_self_call_is_bit_identical(dtype, S):
    """The self call (no key lengths) computes the pinned bits at the
    plan's blocks, and they agree with the oracle."""
    q, k, v, out = _self_call(dtype, S)
    assert _digest(out)[:32] == SELF_CALL_PINS[(dtype, S)]
    want = np.asarray(fa_ref.attention(q, k, v, causal=False), np.float32)
    tol = (0, 2e-5) if dtype == "float32" else (3e-2, 3e-2)
    np.testing.assert_allclose(out, want, rtol=tol[0], atol=tol[1])


@pytest.mark.parametrize("dtype,S", sorted(SELF_CALL_PINS))
def test_flash_attention_full_key_lengths_match_self_call(dtype, S):
    """Key lengths that all equal Skv give the self call's bits."""
    *_, out = _self_call(dtype, S, kv_lens=jnp.full((2,), S, jnp.int32))
    assert _digest(out)[:32] == SELF_CALL_PINS[(dtype, S)]


# ---------------------------------------------------------------------------
# the tile plan at the cells' shapes
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("B,H,Sq,Skv,D,causal,plan,steps", [
    (16, 16, 256, 256, 72, False, (256, 256, 16), 16),
    (32, 6, 64, 64, 64, False, (64, 64, 6), 32),
    (16, 16, 1024, 1024, 72, False, (1024, 1024, 2), 128),
    (16, 16, 1024, 120, 72, False, (1024, 128, 4), 64),
    (1, 8, 8192, 8192, 128, True, (512, 512, 1), 8 * 16 * 16),
], ids=["i256", "s4", "pixart-self", "pixart-cross", "long-causal"])
def test_tile_plan_at_cell_shapes(B, H, Sq, Skv, D, causal, plan, steps):
    """Keys that fit take one kv block rounded up to the bf16 sublane
    multiple and no further; a long causal call keeps several kv blocks
    within the step's VMEM budget. The grid steps are those PERF.md
    records for each cell."""
    from repro.kernels.flash_attention import kernel as fa_kernel

    blk_q, blk_k, heads = fa_ops.tile_plan(Sq, Skv, D, H, H, jnp.bfloat16,
                                           causal, None)
    assert (blk_q, blk_k, heads) == plan
    nk = -(-Skv // blk_k)
    if causal:
        assert nk > 1
        assert blk_q * blk_k <= fa_kernel.SCORES_PER_STEP
        # q, k, v and out blocks of bf16, double-buffered
        assert 2 * 2 * (blk_q + blk_k) * D * 2 <= fa_kernel.IO_BYTES
    else:
        assert nk == 1 and blk_k == -(-Skv // 16) * 16
    assert B * H // heads * -(-Sq // blk_q) * nk == steps


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("case", ["s4-self", "cross-one-block"])
def test_flash_attention_at_planned_blocks(case, dtype):
    """Interpret mode against the oracle at the plan's own blocks: s4's 64
    keys unpadded with its six heads in one step, and one 128-key block
    of a 120-token caption with rows of lengths 1, 8, 57 and 120."""
    if case == "s4-self":
        shape_q, skv, lens = (2, 6, 64, 64), 64, None
    else:
        shape_q, skv, lens = (4, 2, 256, 72), 120, [1, 8, 57, 120]
    B, H, Sq, D = shape_q
    blk_q, blk_k, heads = fa_ops.tile_plan(Sq, skv, D, H, H, dtype, False,
                                           None)
    # the sublane multiple: 16 rows of bf16, 8 of float32
    keys = {"s4-self": 64, "cross-one-block": 128 if dtype == "bfloat16"
            else 120}[case]
    assert (blk_q, blk_k) == (Sq, keys)
    ks = jax.random.split(jax.random.PRNGKey(13), 3)
    q = jax.random.normal(ks[0], shape_q).astype(dtype)
    k = jax.random.normal(ks[1], (B, H, skv, D)).astype(dtype)
    v = jax.random.normal(ks[2], (B, H, skv, D)).astype(dtype)
    n = None if lens is None else jnp.asarray(lens, jnp.int32)
    got = np.asarray(fa_ops.attention(q, k, v, causal=False, kv_lens=n,
                                      backend="interpret"), np.float32)
    want = np.asarray(fa_ref.attention(q, k, v, causal=False, kv_lens=n),
                      np.float32)
    tol = (0, 2e-5) if dtype == "float32" else (3e-2, 3e-2)
    np.testing.assert_allclose(got, want, rtol=tol[0], atol=tol[1])
