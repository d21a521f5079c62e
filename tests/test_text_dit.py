"""The text-conditioned DiT (PixArt-alpha) beside the class-conditioned
one: the class path computes exactly what it did before the text path was
added, and pixart-xl2-512 serves through `serve_diffusion`."""

import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.registry import get_config
from repro.models import api

# sha-256 (first 32 hex digits) of the class-conditioned DiT's eps on
# perturbed weights as the program gave it before the text path existed,
# on the CPU backend: (activation dtype, kernel backend) -> digest
CLASS_PATH_PINS = {
    ("float32", "jnp"): "76cf3c13d1b018a4091ef7beb1055400",
    ("float32", "interpret"): "eb95150270a5217be2ab702cb176886d",
    ("bfloat16", "jnp"): "c7a5c9e33d9067e9722f997857767e26",
    ("bfloat16", "interpret"): "c7a5c9e33d9067e9722f997857767e26",
}


@pytest.mark.parametrize("dtype,backend", sorted(CLASS_PATH_PINS))
def test_class_conditioned_dit_is_bit_identical(dtype, backend):
    cfg = get_config("dit-i256").reduced(
        patch_tokens=16, latent_dim=8, dtype=dtype,
        attention_backend=backend, adaln_backend=backend)
    params = api.init_params(cfg, jax.random.PRNGKey(3))
    leaves, tree = jax.tree.flatten(params)
    keys = jax.random.split(jax.random.PRNGKey(4), len(leaves))
    params = jax.tree.unflatten(tree, [
        a + 0.05 * jax.random.normal(k, a.shape, a.dtype)
        for a, k in zip(leaves, keys)])
    x = jax.random.normal(jax.random.PRNGKey(5), (4, 16, 8))
    t = jnp.array([0.9, 0.5, 0.2, 0.01])
    ids = jnp.array([3, 1000, 17, 999])
    out = api.eps_network(cfg)(params, x, t, {"class_ids": ids})
    out = np.ascontiguousarray(np.asarray(out.astype(jnp.float32)))
    assert hashlib.sha256(out.tobytes()).hexdigest()[:32] \
        == CLASS_PATH_PINS[(dtype, backend)]


def test_text_config_takes_the_text_tree():
    """The path is chosen from the config's sizes: a caption width gives
    the adaLN-single tree, none the class-conditioned one."""
    text = jax.eval_shape(lambda: api.init_params(
        get_config("pixart-xl2-512").reduced(), jax.random.PRNGKey(0)))
    cls = jax.eval_shape(lambda: api.init_params(
        get_config("dit-i256").reduced(), jax.random.PRNGKey(0)))
    assert {"y_embedding", "t_block", "final_table"} <= set(text["backbone"])
    assert "class_embed" not in text["backbone"]
    assert {"cross", "scale_shift_table", "b1"} <= set(
        text["backbone"]["blocks"])
    assert "ada" not in text["backbone"]["blocks"]
    assert "class_embed" in cls["backbone"]
    assert "cross" not in cls["backbone"]["blocks"]


def test_serve_diffusion_serves_pixart_with_a_caption_per_request():
    """`serve_diffusion --arch pixart-xl2-512` through the normal path:
    every request carries a caption drawn from its seed, guided."""
    from repro.launch.serve import serve_diffusion

    report = {}
    lat = serve_diffusion("pixart-xl2-512", reduced=True, batch=2, nfe=3,
                          order=2, cfg_scale=4.5, arrival_rate=1.0,
                          requests=3, seed=1, report=report)
    assert report["metrics"].completed == 3
    assert len(lat) == 3 and all(np.isfinite(x).all() for x in lat)
