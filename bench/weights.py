"""Weights from the seed, made on the device in one jitted call.

The names, shapes and scales come from the configuration's reference
(`param_specs`); the program is handed the same arrays. The values are the
benchmark's, so the reference can make them again after the program is gone
and take nothing the program has made.
"""

from __future__ import annotations

import importlib
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np


def seed_key(seed: int, stream: str):
    """A PRNG key from any non-negative seed (JAX keys hold 32 bits; a
    larger seed is hashed, not truncated) and a stream name."""
    words = np.random.SeedSequence(
        [int(seed), *map(ord, stream)]).generate_state(2)
    return jax.random.fold_in(jax.random.PRNGKey(int(words[0])),
                              int(words[1]))


@partial(jax.jit, static_argnames=("meta", "dtype"))
def _generate(key, *, meta, dtype):
    keys = jax.random.split(key, len(meta))
    return {name: (std * jax.random.normal(k, shape, jnp.float32)).astype(dtype)
            for k, (name, shape, std) in zip(keys, meta)}


def reference(config: dict):
    """The configuration's plain reference module (bench/models/<name>.py)."""
    return importlib.import_module(f"bench.models.{config['reference']}")


def make_params(config: dict, seed: int) -> dict:
    """The nested weight tree for `config`, in its stated parameter dtype."""
    specs = reference(config).param_specs(config["model"])
    meta = tuple((name, tuple(shape), float(std))
                 for name, (shape, std) in sorted(specs.items()))
    flat = _generate(seed_key(seed, "weights"), meta=meta,
                     dtype=config["model"]["param_dtype"])
    tree: dict = {}
    for name, arr in flat.items():
        *path, leaf = name.split("/")
        node = tree
        for p in path:
            node = node.setdefault(p, {})
        node[leaf] = arr
    return tree
