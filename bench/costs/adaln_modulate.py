"""kernels/adaln_modulate: `modulate` (LN(x) (1 + scale) + shift) and
`gate_residual` (resid + gate y), operations and bytes of one call from the
algorithm's own shapes (D and T unpadded)."""

import re

from bench.costs._shapes import step_shapes

NAMES = {"modulate": re.compile(r"modulate_kernel|adaln_modulate"),
         "gate_residual": re.compile(r"gate_res")}


def kind(op_name: str):
    for k, pat in NAMES.items():
        if pat.search(op_name):
            return k
    return None


def per_call(kind: str, config: dict, rows_per_slot: int) -> list:
    """[(flops, bytes)] of one call: each of a block's calls of a kind
    sees the same shapes."""
    s = step_shapes(config, rows_per_slot)
    B, T, D, eb = s["B"], s["T"], s["D"], s["eb"]
    if kind == "modulate":
        # mean, variance, normalise, scale, shift: ~8 per element
        return [(8 * B * T * D, (2 * B * T * D + 2 * B * D) * eb)]
    return [(2 * B * T * D, (3 * B * T * D + B * D) * eb)]
