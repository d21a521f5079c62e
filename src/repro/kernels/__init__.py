"""Pallas TPU kernels for the perf-critical compute layers, each a package
of kernel.py (pl.pallas_call + BlockSpec VMEM tiling), ops.py (jit'd public
wrapper with the explicit pallas|interpret|jnp backend dispatch + padding)
and ref.py (pure-jnp oracle):

* unipc_update    — fused multi-term solver state update (one HBM pass);
                    the scan sampler's default combine (DESIGN.md §4-§5)
* flash_attention — blockwise GQA attention (causal, non-causal,
                    sliding-window, per-row key lengths), tiles planned
                    from the call's shapes; the model-side attention in
                    `models.layers.attention_apply` routes through its ops
                    wrapper (the fast-eval path, DESIGN.md §11)
* adaln_modulate  — fused layernorm + adaLN-zero scale/shift and the gated
                    residual re-entry; `models.dit` runs every block's
                    modulation through it (DESIGN.md §11)
* quant_matmul    — blocked matmul over quantized weights (int8, int4-in-
                    int8, fp8 e4m3; per-output-channel or per-tensor absmax
                    scales; optional static-scale int8 activations) with
                    fp32 MXU accumulation; `models.layers.dense_apply`
                    routes structural quant records through its ops wrapper
                    (the quantized serving path, DESIGN.md §14)

Validated against the oracles in interpret mode (tests/test_kernels.py,
tests/test_fast_eval.py, tests/test_quant.py); selected on TPU backends by
the ops wrappers, with the jnp oracles as the compiled-XLA path everywhere
else.
"""
