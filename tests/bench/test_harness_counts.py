"""The benchmark's operation and byte counts against hand counts, and the
peaks table keyed by device kind."""

import json
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import cell  # noqa: E402
from bench.costs import adaln_modulate, dit, flash_attention  # noqa: E402


def config(name):
    return json.loads((ROOT / "bench" / "configs" / f"{name}.json").read_text())


def test_dit_i256_row_eval_flops_match_hand_count():
    # per token and block: qkv 6d^2 + wo 2d^2 + mlp 4 d f + attention 4 T d;
    # 28 blocks x 256 tokens, plus adaLN and the embeddings
    d, f, T = 1152, 4608, 256
    per_token_block = 8 * d * d + 4 * d * f + 4 * T * d
    hand = 28 * 256 * per_token_block
    got = dit.flops_per_row_eval(config("dit-i256-cfg")["model"])
    # DiT-XL/2: 118.6 GFLOPs (multiply-adds) in the DiT paper's Table 1
    assert got == pytest.approx(2 * 118.6e9, rel=0.01)
    assert hand < got < hand * 1.01


def test_dit_cifar_row_eval_flops_match_hand_count():
    # the small configuration, DiT-S/4: 1.41 GFLOPs a forward pass in the
    # DiT paper's Table 1, which counts multiply-adds
    got = dit.flops_per_row_eval(config("dit-s4")["model"])
    assert got == pytest.approx(2 * 1.41e9, rel=0.02)


@pytest.mark.parametrize("name,rows", [("dit-i256-cfg", 2), ("dit-s4", 1)])
def test_kernel_counts_use_unpadded_shapes(name, rows):
    c = config(name)
    m, slots = c["model"], c["serving"]["slots"]
    B, T, D = slots * rows, m["patch_tokens"], m["d_model"]
    H, hd = m["num_heads"], m["head_dim"]
    # one self call a block, over the patch tokens
    assert c["attention"] == [{"q": "patch_tokens", "kv": "patch_tokens"}]
    [(flops, nbytes)] = flash_attention.per_call("attention", c, rows)
    assert flops == 4 * B * H * T * T * hd
    assert nbytes == 4 * B * H * T * hd * 2          # bf16 q, k, v, out
    [(_, mod_bytes)] = adaln_modulate.per_call("modulate", c, rows)
    [(_, gate_bytes)] = adaln_modulate.per_call("gate_residual", c, rows)
    assert mod_bytes == (2 * B * T * D + 2 * B * D) * 2
    assert gate_bytes == (3 * B * T * D + B * D) * 2


def test_kernel_names_are_told_apart():
    # device ops carry the compiled HLO instruction's name, which for a
    # Pallas kernel is its jitted wrapper's (as a v5e compile names them)
    assert adaln_modulate.kind("gate_residual.12") == "gate_residual"
    assert adaln_modulate.kind("adaln_modulate.14") == "modulate"
    assert flash_attention.kind("flash_attention.6") == "attention"
    assert flash_attention.kind("adaln_modulate.14") is None
    assert flash_attention.kind("fusion.12") is None
    assert adaln_modulate.kind("convolution.3") is None


def test_peaks_are_keyed_by_device_kind():
    v5e = cell.peaks("TPU v5 lite")
    assert v5e["bf16_flops"] == 197e12 and v5e["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        cell.peaks("cpu")
