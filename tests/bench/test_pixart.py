"""PixArt-alpha through the normal serving path against its plain float32
reference, `bench/models/pixart_ref.py`, at a CPU size on seeded weights
(2 blocks of width 128, 64 tokens, captions of 16 x 64): one eval guided
and unguided, and whole UniPC trajectories through the benchmark's
`SlotScheduler`; its FLOP count at the published widths; and the readers of
its two per-layer metrics."""

import copy
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import cell as cells  # noqa: E402
from bench import system, weights  # noqa: E402
from bench.costs import pixart as pixart_flops  # noqa: E402
from bench.harness import Record  # noqa: E402
from bench.loadgen import Window  # noqa: E402
from bench.models import dit_ref, pixart_ref  # noqa: E402
from bench.traffic import cond_text  # noqa: E402

from test_harness_run import BACKLOG  # noqa: E402
from test_harness_text import LAYOUT, hlo  # noqa: E402

CONFIG = "pixart-512-cfg"
SEED = 2 ** 31 + 5
SMALL = dict(num_layers=2, d_model=128, num_heads=4, num_kv_heads=4,
             head_dim=32, d_ff=256, patch_tokens=64, caption_tokens=16,
             caption_dim=64, dtype="float32")
# float32 on both sides, summed in different orders: a few 1e-6 apart. The
# same eval at the configuration's bfloat16 reads about 5e-3 here, so the
# bound fails a lower precision by three orders of magnitude.
EVAL_RTOL = 1e-5


def small_config(**model) -> dict:
    c = copy.deepcopy(cells.load_config(CONFIG))
    c["model"].update(SMALL, **model)
    c["conditioning"]["lengths"] = [1, SMALL["caption_tokens"]]
    c["serving"]["slots"] = 4
    c["check"].update(per_slot=1000, block=8, limit=1e-4)
    return c


def rel(a, b) -> float:
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / np.linalg.norm(b))


def _eval_inputs(config, lens):
    m = config["model"]
    caps = np.stack([cond_text.caption(config, 300 + i, n)
                     for i, n in enumerate(lens)])
    x = jax.random.normal(jax.random.PRNGKey(7), (len(lens),
                                                  m["patch_tokens"],
                                                  m["latent_dim"]))
    return x, jnp.asarray(caps), jnp.asarray(lens, jnp.int32)


def _engine(config, params):
    from repro.diffusion import VPLinear
    from repro.launch.sample import build_engine

    cfg = system.model_config(config)
    return build_engine(cfg, params, VPLinear(), 4, want_cfg=True,
                        per_request_cond=True)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("guided", [False, True], ids=["unguided", "guided"])
def test_one_eval_matches_the_reference(guided, dtype):
    """The engine's eps branches against `pixart_eps`, captions of mixed
    lengths (1 and the maximum among them). Guided, the stacked branch's
    second half is the learned null caption under each row's own length.
    At float32 they agree to rounding; at bfloat16 they must not."""
    config = small_config(dtype=dtype)
    m = config["model"]
    params = weights.make_params(config, SEED)
    lens = [1, m["caption_tokens"], 5, 11]
    x, caps, n = _eval_inputs(config, lens)
    t = 0.37
    nets = _engine(config, params).nets()
    kw = dict(heads=m["num_heads"], head_dim=m["head_dim"],
              eps=m["norm_eps"])
    tf = jnp.asarray(dit_ref.time_features(t))
    if guided:
        got = nets["eps_stacked"](jnp.concatenate([x, x]), t, caps, n)
        null = jnp.broadcast_to(params["backbone"]["y_embedding"],
                                caps.shape)
        want = pixart_ref.pixart_eps(
            params, jnp.concatenate([x, x]), tf,
            jnp.concatenate([caps, null]), jnp.concatenate([n, n]), **kw)
    else:
        got = nets["eps"](x, t, caps, n)
        want = pixart_ref.pixart_eps(params, x, tf, caps, n, **kw)
    assert float(jnp.std(want)) > 0.5          # the output is not trivial
    err = rel(got, want)
    if dtype == "float32":
        assert err < EVAL_RTOL
    else:
        assert err > 100 * EVAL_RTOL


def test_each_caption_row_counts_up_to_its_length_only():
    """The program's key mask: rows of a caption past its length change
    nothing, a row within it changes the output."""
    config = small_config()
    params = weights.make_params(config, SEED)
    x, caps, n = _eval_inputs(config, [3, 9])
    eps = _engine(config, params).nets()["eps"]
    base = eps(x, 0.5, caps, n)
    past = caps.at[:, 9:].set(5.0).at[0, 3:].set(5.0)
    np.testing.assert_array_equal(eps(x, 0.5, past, n), base)
    within = caps.at[1, 8].add(1.0)
    assert float(jnp.abs(eps(x, 0.5, within, n) - base)[1].max()) > 1e-4


@pytest.mark.parametrize("seed", [SEED, 2 ** 33 + 1])
def test_trajectories_through_the_scheduler_match_the_reference(seed):
    """A whole benchmark run at the small size: requests with captions of
    1 to 16 tokens served guided through build_engine -> EngineSpec ->
    SlotScheduler, each delivered latent checked against the reference's
    guided UniPC trajectory. Limit 1e-4: float32 rounding over 10 evals
    (about 6e-6 read); bfloat16 reads above 1e-2."""
    from bench import harness

    c = cells.Cell(name="tiny", config=small_config(), traffic=BACKLOG,
                   chips=1, end_to_end=[{"name": "images_per_s",
                                         "unit": "images/s"}],
                   per_layer=[])
    out = harness.run(c, seed, 0.6, False, since_start=lambda: 1.0)
    assert out["correct"], out["checks"]
    assert out["attempted"] > 4 and out["failed"] == 0
    assert out["checks"]["latent_rel_err_max"]["value"] < 1e-4


def test_program_tree_is_the_references_at_full_width():
    """The program's PixArt parameter tree has exactly the reference's
    names, shapes and dtypes at the published widths (611 M parameters)."""
    config = cells.load_config(CONFIG)
    cfg = system.model_config(config)
    specs = pixart_ref.param_specs(config["model"])
    params = {}
    for name, (shape, _) in specs.items():
        *path, leaf = name.split("/")
        node = params
        for p in path:
            node = node.setdefault(p, {})
        node[leaf] = jax.ShapeDtypeStruct(shape, jnp.float32)
    system.check_layout(cfg, params)
    n = sum(int(np.prod(s)) for s, _ in specs.values())
    assert 605e6 < n < 615e6


def test_position_embedding_is_the_published_one():
    """The program's 2-D sin-cos embedding (jnp, on the device) equals the
    reference's, which follows MAE's numpy code line for line."""
    from repro.models.dit import pos_embed_2d

    for tokens, d in [(1024, 1152), (64, 128)]:
        np.testing.assert_allclose(pos_embed_2d(tokens, d),
                                   pixart_ref.pos_embed(tokens, d),
                                   atol=2e-4, rtol=0)


def test_norm_eps_reaches_the_program():
    config = cells.load_config(CONFIG)
    assert system.model_config(config).norm_eps == 1e-6
    assert system.model_config(cells.load_config("dit-i256-cfg")).norm_eps \
        == 1e-5


def test_flops_per_row_eval_at_the_published_widths():
    """1.236e12 FLOPs a row-eval: self-attention scores 11%, the cross
    path 15%, the MLPs 49%."""
    m = cells.load_config(CONFIG)["model"]
    f = pixart_flops.flops_per_row_eval(m)
    assert f == 1_235_948_175_360
    T, a, Tc, d, n = 1024, 1152, 120, 1152, 28
    assert 0.109 < n * 4 * T * T * a / f < 0.110
    cross = n * (4 * T * d * a + 4 * Tc * d * a + 4 * T * Tc * a)
    assert 0.150 < cross / f < 0.151
    assert 0.492 < n * 4 * T * d * 4608 / f < 0.493


def _cross_hlo(i: int) -> str:
    """The cross call's HLO text: the per-row key lengths come first, as
    the scalar-prefetch operand, then q and the padded caption's k, v."""
    q = f"bf16[256,1024,72]{LAYOUT}"
    kv = f"bf16[256,128,72]{LAYOUT}"
    return (f"%flash_attention.{i} = {q} custom-call(s32[16]{{0}} "
            f"%copy-done.61, {q} %bitcast.1, {kv} %bitcast.2, {kv} "
            '%bitcast.3), custom_call_target="tpu_custom_call"')


def _record(ops, config=None, counters=None, ticks=10):
    w = Window(closed_loop=True)
    w.ticks = ticks
    w.counters0 = {k: 0 for k in (counters or {})}
    w.counters1 = dict(counters or {})
    return Record(config=config or cells.load_config(CONFIG), window=w,
                  setup_s=1.0, rows_per_slot=2,
                  peaks=cells.peaks("TPU v5 lite"), trace={"ops": ops})


def test_cross_attention_roofline_reads_the_cross_calls_alone():
    config = cells.load_config(CONFIG)
    flash = cells.cost_module("flash_attention")
    peaks = cells.peaks("TPU v5 lite")
    (f_self, b_self), (f_cross, b_cross) = flash.per_call(
        "attention", config, 2)
    least_cross = max(f_cross / peaks["bf16_flops"],
                      b_cross / peaks["hbm_bytes_per_s"])
    ops = {hlo(3, 1024, 1024): {"calls": 280, "seconds": 9.0},
           _cross_hlo(4): {"calls": 280, "seconds": 4 * 280 * least_cross}}
    read = cells.metric_reader("cross_attention_roofline")
    assert read(_record(ops, config)) == pytest.approx(25.0, rel=1e-12)
    # the self call's time does not enter it
    ops[hlo(3, 1024, 1024)]["seconds"] = 1.0
    assert read(_record(ops, config)) == pytest.approx(25.0, rel=1e-12)
    # no cross call listed, or calls that cannot be told apart: no value
    alone = {**config, "attention": config["attention"][:1]}
    assert read(_record(ops, alone)) is None
    bare = {"_flash_attention.3": {"calls": 1, "seconds": 1.0},
            "_flash_attention.4": {"calls": 1, "seconds": 1.0}}
    assert read(_record(bare, config)) is None


def test_extras_mb_per_tick_reads_the_counter_where_there_is_one():
    read = cells.metric_reader("extras_mb_per_tick")
    one = 8 * 120 * 4096 * 4 + 8 * 4
    rec = _record({}, counters={"serve_extras_bytes": 5 * one}, ticks=8)
    assert read(rec) == pytest.approx(5 * one / 8 / 1e6)
    # a program without the counter (the parent) gives no value
    assert read(_record({}, counters={"serve_ticks": 8}, ticks=8)) is None


def test_text_configs_refuse_the_cached_and_quantized_tiers():
    from repro.diffusion import VPLinear
    from repro.launch.sample import build_engine

    config = small_config()
    cfg = system.model_config(config)
    params = weights.make_params(config, SEED)
    for kw in (dict(cache_block=1), dict(quant="w8a16")):
        with pytest.raises(ValueError, match="text-conditioned"):
            build_engine(cfg, params, VPLinear(), 2, **kw)
