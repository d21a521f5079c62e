"""kernels/flash_attention: operations and bytes of one call, from the
algorithm's own shapes (T unpadded, whatever block the kernel pads to)."""

import re

from bench.costs._shapes import step_shapes

NAME = re.compile(r"attn_kernel|flash_attention")


def kind(op_name: str):
    return "attention" if NAME.search(op_name) else None


def per_call(kind: str, config: dict, rows_per_slot: int):
    s = step_shapes(config, rows_per_slot)
    B, H, T, hd, eb = s["B"], s["H"], s["T"], s["hd"], s["eb"]
    flops = 2 * 2 * B * H * T * T * hd           # QK^T and PV
    nbytes = 4 * B * H * T * hd * eb             # q, k, v in; out
    return flops, nbytes
