"""Class conditioning: a request carries one class id (None where the
configuration serves unconditionally), the program takes it as the per-slot
extra `class_ids`, and the null class fills an empty slot and the guidance
half of a guided eval. The sizes are the configuration's:
`model.num_classes`, `serving.class_conditional`, `serving.null_class`."""


def draw(config: dict, rng):
    classes = (config["model"]["num_classes"]
               if config["serving"]["class_conditional"] else 0)
    return int(rng.integers(0, classes)) if classes else None


def extras(config: dict, spec):
    return None if spec.cond is None else {"class_ids": spec.cond}


def extras_init(config: dict) -> dict:
    return {"class_ids": config["serving"]["null_class"]}


def reference_inputs(config: dict, specs: list) -> dict:
    null = config["serving"]["null_class"]
    return {"class_ids": [null if s.cond is None else s.cond for s in specs],
            "null_class": null}
