"""Ahead-of-time v5e compiles of the four Pallas kernels at published DiT
widths, and a guard that the serving step takes its weights as arguments.

The compile tests run the TPU compiler that ships with JAX against a
described (not attached) v5e topology: they catch what interpret mode cannot,
such as block shapes that break the TPU tiling rule. Each asserts the kernel
is in the compiled program (`tpu_custom_call`). The topology is described
only inside the `one_chip` fixture, never at import: only one process at a
time may load the TPU library, and collection must look the same in every
test worker.
"""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.adaln_modulate import ops as ad_ops
from repro.kernels.flash_attention import ops as fa_ops
from repro.kernels.quant_matmul import ops as qm_ops
from repro.kernels.unipc_update import ops as up_ops

# arch -> (tokens T, d_model D, heads, latent_dim), the published widths
WIDTHS = {"dit-i256": (256, 1152, 16, 32), "dit-cifar": (64, 384, 6, 48),
          "pixart-xl2-512": (1024, 1152, 16, 16)}
CAPTION_TOKENS = 120   # PixArt-alpha's T5-XXL caption: the cross call's keys
SLOTS = 4          # serving slots; guided evals stack 2 * SLOTS rows
K = 5              # UniPC-3 combine terms


@pytest.fixture(scope="module")
def one_chip():
    """One device of a described v5e:2x2, with the persistent compile cache
    off (entries written here could not be read back without a chip)."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    from jax.sharding import SingleDeviceSharding

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        was = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        cc.reset_cache()
        yield SingleDeviceSharding(topo.devices[0])
        jax.config.update("jax_enable_compilation_cache", was)


def _kernel_case(kernel, arch, dtype):
    """(fn, arg shapes) for one kernel at one arch's serving shapes."""
    T, D, H, L = WIDTHS[arch]
    rows = 2 * SLOTS
    if kernel == "unipc_update_bcast":
        return (lambda t, w: up_ops.weighted_combine(t, w, backend="pallas"),
                [((K, SLOTS, T, L), dtype), ((K,), jnp.float32)])
    if kernel == "unipc_update_per_slot":
        return (lambda t, w: up_ops.weighted_combine(t, w, backend="pallas"),
                [((K, SLOTS, T, L), dtype), ((K, SLOTS), jnp.float32)])
    if kernel == "modulate":
        return (lambda x, a, b: ad_ops.modulate(x, a, b, backend="pallas"),
                [((rows, T, D), dtype), ((rows, D), dtype),
                 ((rows, D), dtype)])
    if kernel == "gate_residual":
        return (lambda r, g, y: ad_ops.gate_residual(r, g, y,
                                                     backend="pallas"),
                [((rows, T, D), dtype), ((rows, D), dtype),
                 ((rows, T, D), dtype)])
    if kernel == "flash_attention":
        qkv = ((rows, H, T, D // H), dtype)
        return (lambda q, k, v: fa_ops.attention(q, k, v, causal=False,
                                                 backend="pallas"),
                [qkv, qkv, qkv])
    if kernel == "flash_attention_cross":
        q = ((rows, H, T, D // H), dtype)
        kv = ((rows, H, CAPTION_TOKENS, D // H), dtype)
        return (lambda q, k, v, n: fa_ops.attention(
            q, k, v, causal=False, kv_lens=n, backend="pallas"),
            [q, kv, kv, ((rows,), jnp.int32)])
    if kernel == "quant_matmul_int8":
        return (lambda x, w, s: qm_ops.quant_matmul(x, w, s,
                                                    backend="pallas"),
                [((rows, T, D), dtype), ((D, 4 * D), jnp.int8),
                 ((4 * D,), jnp.float32)])
    raise AssertionError(kernel)


CASES = [(k, a, d) for a in WIDTHS for k, d in [
    ("unipc_update_bcast", jnp.float32),
    ("unipc_update_per_slot", jnp.float32),
    ("modulate", jnp.float32), ("modulate", jnp.bfloat16),
    ("gate_residual", jnp.float32), ("gate_residual", jnp.bfloat16),
    ("flash_attention", jnp.float32), ("flash_attention", jnp.bfloat16),
    ("quant_matmul_int8", jnp.bfloat16),
]] + [("flash_attention_cross", "pixart-xl2-512", jnp.bfloat16)]


@pytest.mark.parametrize("kernel,arch,dtype", CASES,
                         ids=[f"{k}-{a}-{jnp.dtype(d).name}"
                              for k, a, d in CASES])
def test_kernel_compiles_for_v5e(one_chip, kernel, arch, dtype):
    fn, shapes = _kernel_case(kernel, arch, dtype)
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("kernel,arch,lengths", [
    ("flash_attention", "pixart-xl2-512", (1024, 1024)),
    ("flash_attention_cross", "pixart-xl2-512", (1024, 128)),
    ("flash_attention", "dit-i256", (256, 256)),
], ids=["pixart-self", "pixart-cross", "i256-self"])
def test_flash_attention_call_reads_as_the_harness_parses_it(
        one_chip, kernel, arch, lengths):
    """The benchmark tells PixArt's self call from its cross call by the
    compiled kernel's name and its query and key operands (padded lengths
    at dim -2): a layout change that hid them would take the cross call's
    roofline away."""
    import sys
    from pathlib import Path

    sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
    from bench.costs import flash_attention as fa_costs

    fn, shapes = _kernel_case(kernel, arch, jnp.bfloat16)
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    calls = [line.strip() for line in text.splitlines()
             if "custom-call(" in line and fa_costs.kind(line.split("=")[0])]
    assert len(calls) == 1
    assert fa_costs.operand_lengths(calls[0]) == lengths


# ---------------------------------------------------------------------------
# weights enter the step program as arguments (runs on the CPU)
# ---------------------------------------------------------------------------

_CONST = re.compile(r"stablehlo\.constant dense<(.*?)> : tensor<([^>]*)>")
_BYTES = {"f32": 4, "i32": 4, "ui32": 4, "bf16": 2, "f16": 2, "i8": 1,
          "ui8": 1, "i1": 1, "f64": 8, "i64": 8}


def _dense_constant_bytes(hlo_text: str) -> int:
    """Bytes of the non-splat constants in StableHLO text. A splat (one
    repeated value, e.g. a zeros init) stores no weights."""
    total = 0
    for payload, ty in _CONST.findall(hlo_text):
        if not (payload.startswith("[") or payload.startswith('"0x')):
            continue
        *dims, dt = ty.split("x")
        total += int(np.prod([int(d) for d in dims])) * _BYTES[dt]
    return total


@pytest.mark.parametrize("wiring", ["guided", "w8a16", "cached"])
def test_full_width_step_holds_no_weight_constants(wiring):
    """Lower the full-width dit-cifar serving step: its dense constants must
    total well under 1% of the param bytes, so the weights are arguments —
    for the stacked-CFG eps, quantized records and the cached eps alike."""
    from repro.configs.registry import get_config
    from repro.diffusion import VPLinear
    from repro.engine import EngineSpec
    from repro.launch.sample import NULL_CLASS_ID, build_engine
    from repro.models import api
    from repro.serving import SlotScheduler

    cfg = get_config("dit-cifar")
    params = api.init_params(cfg, jax.random.PRNGKey(0))
    kw = {"guided": dict(want_cfg=True), "w8a16": dict(quant="w8a16"),
          "cached": dict(cache_block=4)}[wiring]
    engine = build_engine(cfg, params, VPLinear(), SLOTS, 0,
                          per_request_cond=True, **kw)
    spec = EngineSpec(solver="unipc", nfe=10, order=3,
                      cfg_scale=2.0 if wiring == "guided" else 0.0,
                      quant=kw.get("quant", "none"),
                      cache_block=kw.get("cache_block", 0))
    program = engine.build_step(spec)
    sched = SlotScheduler(program, SLOTS, (cfg.patch_tokens, cfg.latent_dim),
                          extras_init={"class_ids": NULL_CLASS_ID})
    text = program.flight.lower(program.nets, sched.state, sched.meta,
                                *sched._step_tail()).as_text()
    param_bytes = sum(a.nbytes for a in jax.tree.leaves(program.nets))
    assert param_bytes > 20e6  # the published width, not a reduced net
    assert _dense_constant_bytes(text) < 0.01 * param_bytes
