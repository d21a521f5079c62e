"""Model FLOPs of one DiT row-eval (one latent through the denoiser once),
counted from the configuration's shapes: 2 FLOPs per multiply-add of every
matmul, attention's QK^T and PV included, elementwise work left out."""


def flops_per_row_eval(m: dict) -> int:
    d, f, n = m["d_model"], m["d_ff"], m["num_layers"]
    T, L = m["patch_tokens"], m["latent_dim"]
    a = m["num_heads"] * m["head_dim"]
    block = (T * (2 * d * 3 * a + 2 * a * d + 2 * 2 * d * f)   # qkv, wo, mlp
             + 2 * 2 * T * T * a                             # QK^T, PV
             + 2 * d * 6 * d)                                # adaLN
    embed = 2 * T * L * d + 2 * m["time_features"] * d + 2 * d * d
    head = 2 * d * 2 * d + 2 * T * d * L
    return n * block + embed + head
