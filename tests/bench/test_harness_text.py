"""The text path of the harness, against a concrete consumer at
PixArt-alpha's caption shape (T5-XXL's 120 tokens of 4096): the `text`
conditioning kind hands the program and the reference the same captions,
made from each request's seed, and flash attention is charged per call over
a block's self and cross calls."""

import sys
from pathlib import Path
from types import SimpleNamespace

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

import text_ref  # noqa: E402
from bench import cell as cells  # noqa: E402
from bench import check, loadgen, system, weights  # noqa: E402
from bench.harness import Record  # noqa: E402
from bench.traffic import generate  # noqa: E402

from test_harness_run import tiny  # noqa: E402

CAPTION = (120, 4096)
SEED = 2 ** 31 + 77
SELF = {"q": "patch_tokens", "kv": "patch_tokens"}
CROSS = {"q": "patch_tokens", "kv": "caption_tokens"}


def text_config() -> dict:
    c = tiny("dit-i256-cfg")
    c["model"].update(caption_tokens=CAPTION[0], caption_dim=CAPTION[1])
    c["reference"] = "text_ref"
    c["conditioning"] = {"kind": "text", "lengths": [1, CAPTION[0]],
                         "null": {"seed": 0, "length": 1}}
    c["attention"] = [SELF, CROSS]
    return c


def test_program_and_reference_get_the_same_captions(monkeypatch):
    config = text_config()
    seen = []

    def sample(*args, **kw):
        seen.append(kw)
        return text_ref.sample(*args, **kw)

    ref = SimpleNamespace(param_specs=text_ref.param_specs, sample=sample)
    monkeypatch.setattr(weights, "reference", lambda config: ref)
    n = 11                                  # a block and a padded part
    specs = generate.Requests(config, SEED).take(n)
    lens = [s.cond for s in specs]
    assert all(1 <= k <= CAPTION[0] for k in lens) and len(set(lens)) > 5
    # the Spec holds scalars alone, and the stream is the seed's
    assert specs == generate.Requests(config, SEED).take(n)
    assert len(set(specs)) == n
    extras = [system.request(config, s).extras for s in specs]
    x0 = check.reference_latents(config, SEED, specs)
    assert x0.shape == (n, 16, 8) and np.isfinite(x0).all()

    assert len(seen) == 2                   # blocks of 8
    got = np.concatenate([b["captions"] for b in seen])[:n]
    got_lens = np.concatenate([b["caption_lens"] for b in seen])[:n]
    for ex, cap, k in zip(extras, got, got_lens):
        assert ex["caption"].shape == CAPTION
        assert ex["caption"].dtype == np.float32
        np.testing.assert_array_equal(ex["caption"], cap)
        assert ex["caption_len"] == k
        # rows past the caption's length are zero, and only those
        assert not ex["caption"][k:].any()
        assert np.abs(ex["caption"][:k]).max(axis=1).min() > 0
    init = generate.conditioning(config).extras_init(config)
    for b in seen:
        np.testing.assert_array_equal(b["null_caption"], init["caption"])
        assert b["null_len"] == init["caption_len"] == 1


def test_reference_masks_the_caption_past_its_length(monkeypatch):
    config = text_config()
    monkeypatch.setattr(weights, "reference", lambda config: text_ref)
    params = weights.make_params(config, SEED)
    spec = generate.Requests(config, SEED).take(1)[0]
    cap = generate.conditioning(config).extras(config, spec)["caption"][None]
    k = np.array([spec.cond])
    x = np.ones((1, 16, 8), np.float32)
    e = text_ref.eps(params, config["model"], x, cap, k)
    past, within = cap.copy(), cap.copy()
    past[:, k[0]:] = 1.0
    within[:, 0] += 1.0
    np.testing.assert_array_equal(
        e, text_ref.eps(params, config["model"], x, past, k))
    assert not np.allclose(
        e, text_ref.eps(params, config["model"], x, within, k))


def _least(config, rows, call, peaks):
    """A call's least time, from its shapes by hand."""
    m = config["model"]
    B = config["serving"]["slots"] * rows
    H, hd, eb = m["num_heads"], m["head_dim"], 2
    sq, skv = m[call["q"]], m[call["kv"]]
    return max(4 * B * H * sq * skv * hd / peaks["bf16_flops"],
               2 * B * H * (sq + skv) * hd * eb / peaks["hbm_bytes_per_s"])


def _record(config, ops):
    return Record(config=config, window=loadgen.Window(), setup_s=1.0,
                  rows_per_slot=2, peaks=cells.peaks("TPU v5 lite"),
                  trace={"ops": ops})


def pixart_shapes() -> dict:
    """PixArt-alpha 512's widths: 1 024 tokens of 1152, 16 heads of 72, a
    caption of 120 tokens; 8 guided slots."""
    c = text_config()
    c["model"].update(d_model=1152, num_heads=16, num_kv_heads=16,
                      head_dim=72, patch_tokens=1024, dtype="bfloat16")
    c["serving"]["slots"] = 8
    return c


# a flash op's name as a TPU trace gives it: its HLO text (dit-s4, one v5e)
S4_FLASH = ('%flash_attention.6 = bf16[192,128,64]{2,1,0:T(8,128)(2,1)S(1)} '
            'custom-call(bf16[192,128,64]{2,1,0:T(8,128)(2,1)S(1)} '
            '%bitcast.185, bf16[192,128,64]{2,1,0:T(8,128)(2,1)S(1)} '
            '%bitcast.186, bf16[192,128,64]{2,1,0:T(8,128)(2,1)S(1)} '
            '%bitcast.187), custom_call_target="tpu_custom_call", '
            'operand_layout_constraints={bf16[192,128,64]{2,1,0}, '
            'bf16[192,128,64]{2,1,0}, bf16[192,128,64]{2,1,0}}, '
            'frontend_attributes={kernel_metadata={}}')
LAYOUT = "{2,1,0:T(8,128)(2,1)S(1)}"


def hlo(i: int, sq: int, skv: int, bh: int = 256, hd: int = 72) -> str:
    """A flash call's HLO text, query and key lengths padded as given."""
    q, kv = f"bf16[{bh},{sq},{hd}]{LAYOUT}", f"bf16[{bh},{skv},{hd}]{LAYOUT}"
    return (f"%flash_attention.{i} = {q} custom-call({q} %bitcast.1, "
            f"{kv} %bitcast.2, {kv} %bitcast.3), "
            'custom_call_target="tpu_custom_call"')


def test_flash_operand_lengths_are_read_from_the_hlo_text():
    flash = cells.cost_module("flash_attention")
    assert flash.operand_lengths(S4_FLASH) == (128, 128)
    assert flash.operand_lengths(hlo(4, 1024, 128)) == (1024, 128)
    assert flash.operand_lengths("_flash_attention.3___bf16_256_256_72") \
        is None


def _traced(config, ops):
    """{name: calls} timed at exactly each call's least time."""
    peaks = cells.peaks("TPU v5 lite")
    t = {SELF["kv"]: _least(config, 2, SELF, peaks),
         CROSS["kv"]: _least(config, 2, CROSS, peaks)}
    return {name: {"calls": n, "seconds": n * t[kv]}
            for name, (kv, n) in ops.items()}


def test_flash_roofline_charges_self_and_cross_calls_their_own_time():
    config = pixart_shapes()
    blocks = 28 * 10 * 37
    # one HLO instruction for each call of the block, as a compiled scan
    # names them; the key operand tells them apart (120 pads to 128)
    ops = _traced(config, {hlo(3, 1024, 1024): ("patch_tokens", blocks),
                           hlo(4, 1024, 128): ("caption_tokens", blocks)})
    read = cells.metric_reader("flash_attention_roofline")
    assert read(_record(config, ops)) == pytest.approx(100.0, rel=1e-12)
    # the order of the list does not matter
    swapped = {**config, "attention": [CROSS, SELF]}
    assert read(_record(swapped, ops)) == pytest.approx(100.0, rel=1e-12)
    # charged as self calls alone, the same trace reads impossibly high
    alone = {**config, "attention": [SELF]}
    assert read(_record(alone, ops)) > 105.0


def test_flash_roofline_reads_a_window_that_cuts_a_block():
    """The window opens after a block's self call and closes before its
    cross call: one self call more than cross calls, each op still charged
    its own."""
    config = pixart_shapes()
    blocks = 28 * 10 * 37
    ops = _traced(config, {hlo(3, 1024, 1024): ("patch_tokens", blocks + 1),
                           hlo(4, 1024, 128): ("caption_tokens", blocks)})
    read = cells.metric_reader("flash_attention_roofline")
    assert read(_record(config, ops)) == pytest.approx(100.0, rel=1e-12)


def test_flash_roofline_gives_no_value_where_a_call_is_not_known():
    config = pixart_shapes()
    read = cells.metric_reader("flash_attention_roofline")
    known = {hlo(3, 1024, 1024): ("patch_tokens", 10),
             hlo(4, 1024, 128): ("caption_tokens", 10)}
    # an op whose key is shorter than any listed call's
    unlisted = {**known, hlo(5, 1024, 64): ("caption_tokens", 10)}
    assert read(_record(config, _traced(config, unlisted))) is None
    # names that carry no operands: the two calls cannot be told apart
    bare = {"_flash_attention.3___bf16_256_1024_72": ("patch_tokens", 10),
            "_flash_attention.4___bf16_256_1024_72": ("caption_tokens", 10)}
    assert read(_record(config, _traced(config, bare))) is None
    # two listed calls of different lengths padded to one shape
    long = {**config, "model": {**config["model"], "caption_tokens": 1000}}
    padded = {hlo(3, 1024, 1024): ("patch_tokens", 10),
              hlo(4, 1024, 1024): ("caption_tokens", 10)}
    assert read(_record(long, _traced(long, padded))) is None
