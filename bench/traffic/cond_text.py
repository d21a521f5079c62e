"""Text conditioning: a request carries its caption's length in tokens; the
caption itself, an embedding of `model.caption_tokens` rows of
`model.caption_dim` (a text encoder's output, T5-XXL's 120 x 4096 for
PixArt-alpha), is made from the request's own seed where it is needed, with
zeros past its length. The configuration's block states the rest:

    "conditioning": {"kind": "text", "lengths": [lo, hi],
                     "null": {"seed": s, "length": n}}

`lengths`: a caption's length is uniform on lo..hi, both included. `null`:
the caption that fills an empty slot and the guidance half of a guided
eval, made as a request's caption from its own seed and length.

The program takes `caption` (float32 [tokens, width]) and `caption_len` as
a request's extras; the reference takes them stacked over the requests.
"""

import numpy as np


def caption(config: dict, seed: int, length: int) -> np.ndarray:
    """The embedding of a caption of `length` tokens, drawn from `seed`."""
    m = config["model"]
    out = np.zeros((m["caption_tokens"], m["caption_dim"]), np.float32)
    rng = np.random.default_rng(
        np.random.SeedSequence([int(seed), *map(ord, "caption")]))
    out[:length] = rng.standard_normal((length, m["caption_dim"]),
                                       dtype=np.float32)
    return out


def draw(config: dict, rng):
    lo, hi = config["conditioning"]["lengths"]
    return int(rng.integers(lo, hi + 1))


def extras(config: dict, spec):
    return {"caption": caption(config, spec.seed, spec.cond),
            "caption_len": spec.cond}


def _null(config: dict):
    n = config["conditioning"]["null"]
    return caption(config, n["seed"], n["length"]), int(n["length"])


def extras_init(config: dict) -> dict:
    emb, length = _null(config)
    return {"caption": emb, "caption_len": length}


def reference_inputs(config: dict, specs: list) -> dict:
    emb, length = _null(config)
    return {"captions": np.stack([caption(config, s.seed, s.cond)
                                  for s in specs]),
            "caption_lens": np.array([s.cond for s in specs], np.int32),
            "null_caption": emb, "null_len": length}
