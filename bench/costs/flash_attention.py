"""kernels/flash_attention: operations and bytes of each call one block
makes, from the algorithm's own shapes (lengths unpadded, whatever block the
kernel pads to).

The configuration lists a block's attention calls in the order it makes
them, each by the `model` keys of its query and key lengths:

    "attention": [{"q": "patch_tokens", "kv": "patch_tokens"}]

DiT makes one self call; a text-conditioned block adds a cross call over its
caption, {"q": "patch_tokens", "kv": "caption_tokens"}.

A traced call is told from the others by its operands: on the TPU an op's
name is its HLO text, `%flash_attention.6 = bf16[192,128,64]{...}
custom-call(bf16[192,128,64]{...} %q, bf16[192,128,64]{...} %k, ...)`, whose
first two operands of rank 3 or more are the query and the key, padded to
the kernel's blocks: (..., Sq, hd) and (..., Skv, hd). A listed call fits a
traced shape when its lengths are no longer than the shape's; padding only
rounds up, so a call's own shape is the least traced shape it fits."""

import re

from bench.costs._shapes import step_shapes

NAME = re.compile(r"attn_kernel|flash_attention")
SHAPE = re.compile(r"\w+\[([\d,]*)\]")


def kind(op_name: str):
    return "attention" if NAME.search(op_name) else None


def per_call(kind: str, config: dict, rows_per_slot: int) -> list:
    """[(flops, bytes)] of each attention call of one block, in order."""
    s = step_shapes(config, rows_per_slot)
    B, H, hd, eb = s["B"], s["H"], s["hd"], s["eb"]
    out = []
    for sq, skv in lengths(config):
        flops = 2 * 2 * B * H * sq * skv * hd        # QK^T and PV
        nbytes = 2 * B * H * (sq + skv) * hd * eb    # q, out; k, v
        out.append((flops, nbytes))
    return out


def lengths(config: dict) -> list:
    """[(Sq, Skv)] of each listed call, unpadded."""
    m = config["model"]
    return [(m[c["q"]], m[c["kv"]]) for c in config["attention"]]


def operand_lengths(op_name: str):
    """(Sq, Skv) of a traced call as padded, from its query and key operands
    in the op's HLO text; None where the name carries no operands."""
    _, sep, args = op_name.partition("custom-call(")
    dims = [[int(d) for d in s.split(",") if d] for s in SHAPE.findall(args)]
    qk = [d for d in dims if len(d) >= 3][:2]
    return (qk[0][-2], qk[1][-2]) if sep and len(qk) == 2 else None


def call_of(op_name: str, names: list, config: dict):
    """Index in `per_call`'s list of the call a traced op makes, given the
    names of all traced ops of its kind; None where that is not known: the
    op matches no listed call, or listed calls of different lengths share
    its shape, or the trace's names carry no operands."""
    calls = lengths(config)
    shapes = {operand_lengths(n) for n in names}
    if None in shapes:
        return None

    def home(call):
        fits = [s for s in shapes if call[0] <= s[0] and call[1] <= s[1]]
        least = [s for s in fits
                 if all(s[0] <= t[0] and s[1] <= t[1] for t in fits)]
        return least[0] if least else None

    mine = [i for i, c in enumerate(calls)
            if home(c) == operand_lengths(op_name)]
    return mine[0] if len({calls[i] for i in mine}) == 1 else None
