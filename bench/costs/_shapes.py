"""Shapes every kernel of a served DiT step sees."""

ITEMSIZE = {"bfloat16": 2, "float16": 2, "float32": 4}


def step_shapes(config: dict, rows_per_slot: int) -> dict:
    m = config["model"]
    return {"B": config["serving"]["slots"] * rows_per_slot,
            "T": m["patch_tokens"], "D": m["d_model"], "H": m["num_heads"],
            "hd": m["head_dim"], "eb": ITEMSIZE[m["dtype"]]}
