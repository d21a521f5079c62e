"""How `correct` is decided: the latents the window delivered against the
configuration's plain float32 reference, run over the same requests.

For each sampled request the reference draws the starting latent from the
request's seed, takes its conditioning as the configuration's kind makes it
(bench/traffic/cond_<kind>.py) and its guidance scale, and samples the whole
trajectory. The number compared is the worst request's relative error,
||x_served - x_ref|| / ||x_ref||. The reference makes its weights again from
the run's seed; it takes nothing the program has made.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from bench import weights
from bench.traffic import generate

NAME = "latent_rel_err_max"
# the control's precision: float8 e4m3's 3 explicit mantissa bits, the step
# below the bfloat16 the configurations state
CONTROL_BITS = 3


def start_latents(seeds, shape) -> jnp.ndarray:
    """x_T of each request: a standard normal drawn from its seed."""
    return jnp.stack([jax.random.normal(jax.random.PRNGKey(int(s)), shape,
                                        jnp.float32) for s in seeds])


def reference_latents(config: dict, seed: int, specs: list,
                      bits=None) -> np.ndarray:
    """The reference's x_0 for each request `Spec`, in blocks of a fixed
    size (the last block padded with its first request). `bits` gives the
    control: the reference computed at that many mantissa bits."""
    ref = weights.reference(config)
    cond = generate.conditioning(config)
    params = weights.make_params(config, seed)
    m, srv = config["model"], config["serving"]
    shape = (m["patch_tokens"], m["latent_dim"])
    block = int(config["check"]["block"])
    out = []
    for i in range(0, len(specs), block):
        part = list(specs[i:i + block])
        n = len(part)
        part += [part[0]] * (block - n)
        x_T = start_latents([s.seed for s in part], shape)
        g = [s.cfg_scale for s in part] if srv["guided"] else None
        x0 = ref.sample(params, m, config["schedule"], config["solver"], x_T,
                        g=g, bits=bits, **cond.reference_inputs(config, part))
        out.append(np.asarray(x0)[:n])
    del params
    return np.concatenate(out) if out else np.zeros((0,) + shape, np.float32)


def worst_error(served: np.ndarray, ref: np.ndarray) -> float:
    """max over requests of ||served - ref|| / ||ref|| (inf where the served
    latent is not finite)."""
    if not len(served):
        return float("inf")
    s = served.reshape(len(served), -1).astype(np.float64)
    r = ref.reshape(len(ref), -1).astype(np.float64)
    if not np.isfinite(s).all():
        return float("inf")
    return float(np.max(np.linalg.norm(s - r, axis=1)
                        / np.linalg.norm(r, axis=1)))


def compare(config: dict, seed: int, sample: list) -> float:
    """The compared number for the window's sample [(Spec, latent)]."""
    if not sample:
        return float("inf")
    specs = [s for s, _ in sample]
    served = np.stack([lat for _, lat in sample])
    return worst_error(served, reference_latents(config, seed, specs))


def control_error(config: dict, seed: int, bits: int = CONTROL_BITS) -> float:
    """The control's compared number: the reference computed at `bits`
    mantissa bits in the program's place, over as many requests as a
    window's check takes, against the float32 reference."""
    n = int(config["check"]["per_slot"]) * int(config["serving"]["slots"])
    specs = generate.Requests(config, seed).take(n)
    return worst_error(reference_latents(config, seed, specs, bits=bits),
                       reference_latents(config, seed, specs))
