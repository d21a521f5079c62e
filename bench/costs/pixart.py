"""Model FLOPs of one PixArt-alpha row-eval (one latent through the
denoiser once), counted from the configuration's shapes: 2 FLOPs per
multiply-add of every matmul, both attentions' QK^T and PV included
(the cross call over the whole caption buffer, as the kernel is charged),
elementwise work left out."""


def flops_per_row_eval(m: dict) -> int:
    d, f, n = m["d_model"], m["d_ff"], m["num_layers"]
    T, L = m["patch_tokens"], m["latent_dim"]
    Tc, C = m["caption_tokens"], m["caption_dim"]
    a = m["num_heads"] * m["head_dim"]
    self_attn = T * (2 * d * 3 * a + 2 * a * d) + 2 * 2 * T * T * a
    cross = (T * (2 * d * a + 2 * a * d)        # q, out
             + Tc * 2 * d * 2 * a               # k, v over the caption
             + 2 * 2 * T * Tc * a)              # QK^T, PV
    mlp = T * 2 * 2 * d * f
    block = self_attn + cross + mlp
    caption = Tc * (2 * C * d + 2 * d * d)      # the caption projection
    time = 2 * m["time_features"] * d + 2 * d * d + 2 * d * 6 * d
    embed = 2 * T * L * d
    head = 2 * T * d * L
    return n * block + caption + time + embed + head
