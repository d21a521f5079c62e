"""Per-kernel shape/dtype sweeps: Pallas (interpret mode) vs pure-jnp oracle."""

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
    past it never enters the sums (those tiles are skipped)."""
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
                           kv_lens=lens)
    want = _masked_softmax_attention(q, k, v, lens)
    assert np.isfinite(np.asarray(got)).all()
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=0)


# sha-256 (first 32 hex digits) of the unmasked self call's output as the
# kernel gave it before per-row key lengths existed, on the CPU backend
SELF_CALL_PINS = {
    ("float32", 64): "b902a1f1f3e392d186b50e35ef359a81",
    ("float32", 256): "984b0358856658cfed0a31358c9d2589",
    ("bfloat16", 64): "9530b3ff11c9dfbe8f6408f062540ddd",
    ("bfloat16", 256): "f41c4f2be567405f560d79ecf8203b1e",
}


@pytest.mark.parametrize("dtype,S", sorted(SELF_CALL_PINS))
def test_flash_attention_self_call_is_bit_identical(dtype, S):
    """The self call (no key lengths) computes exactly what it did before
    the per-row path was added: same grid, same tiles, same bits."""
    import hashlib

    ks = jax.random.split(jax.random.PRNGKey(6), 3)
    q, k, v = (jax.random.normal(ki, (2, 3, S, 72)).astype(dtype)
               for ki in ks)
    out = np.asarray(fa_ops.attention(q, k, v, causal=False,
                                      backend="interpret").astype("float32"))
    digest = hashlib.sha256(np.ascontiguousarray(out).tobytes()).hexdigest()
    assert digest[:32] == SELF_CALL_PINS[(dtype, S)]
