"""Both configurations read exactly as they did when their class
conditioning, FLOP module and attention shape were written into the
harness's code: the request stream, the program's `Request`s, the reference
latents, the FLOP count of a row-eval and each kernel's cost per call,
against values recorded from that harness on seeds 0 and 1."""

import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import cell as cells  # noqa: E402
from bench import check, loadgen, system  # noqa: E402
from bench.harness import Record  # noqa: E402
from bench.traffic import generate  # noqa: E402
from repro.serving import Request  # noqa: E402

from test_harness_run import tiny  # noqa: E402

LATENTS = Path(__file__).resolve().parent / "data" / "dit_reference_tiny.npz"
CELLS = [("dit-i256-cfg", 0), ("dit-i256-cfg", 1), ("dit-s4", 0),
         ("dit-s4", 1)]
ROWS = {"dit-i256-cfg": 2, "dit-s4": 1}
# guidance scales cycle over the requests; dit-s4 is served unguided
SCALES = {"dit-i256-cfg": (1.5, 4.0, 8.0), "dit-s4": None}
FLOPS = {"dit-i256-cfg": 237233405952, "dit-s4": 2822012928}
# (flops, bytes) of one call
COSTS = {"dit-i256-cfg": {"attention": (4831838208, 37748736),
                          "modulate": (37748736, 18948096),
                          "gate_residual": (9437184, 28348416)},
         "dit-s4": {"attention": (201326592, 6291456),
                    "modulate": (6291456, 3194880),
                    "gate_residual": (1572864, 4743168)}}
KERNEL = {"attention": "flash_attention", "modulate": "adaln_modulate",
          "gate_residual": "adaln_modulate"}
# the first 64 requests of each seed: latent seeds, then class ids. The
# stream depends on the seed and the number of classes alone, which both
# configurations share
CLASSES = 1000
SPECS = {
    0: (
        [1121323793, 2132340197, 650573022, 491113666, 1914009000, 136066920,
         538649390, 543900309, 83218430, 2055323744, 1063405574, 2063171317,
         112662089, 484341100, 22176595, 165689405, 994799070, 1521006338,
         815790520, 1364975842, 1607623800, 486647401, 1019925346, 1865979095,
         515310588, 1311464923, 1378306777, 841657797, 1274076967, 1699255934,
         1886887022, 746572470, 886844801, 1738666813, 877364454, 1015483871,
         1042445365, 1269355367, 1971942793, 2014273158, 662134670, 661858849,
         1832388975, 1929232157, 1612299883, 1263839795, 1445846058,
         1495676476, 5401878, 1550582273, 859027698, 422151178, 1510022461,
         683793847, 90785425, 2003508049, 1612711244, 247401900, 2089869184,
         1183670469, 2044141100, 383357118, 1955408828, 847625620],
        [889, 557, 800, 956, 58, 236, 787, 0, 725, 618, 4, 108, 130, 761, 958,
         127, 579, 314, 33, 334, 263, 702, 433, 162, 831, 427, 231, 255, 346,
         610, 640, 481, 572, 912, 375, 7, 381, 441, 81, 933, 355, 14, 205, 286,
         968, 180, 873, 587, 430, 737, 976, 205, 779, 555, 105, 544, 429, 984,
         403, 509, 68, 865, 606, 444],
    ),
    1: (
        [1114088974, 777780532, 1330195323, 1104591633, 1917683758, 1009445952,
         1680115891, 2140835849, 901007641, 1989176985, 1640742683, 1450096156,
         345984053, 612871590, 2024105735, 2136305331, 514400338, 1392178214,
         1999631076, 1304701379, 944687314, 687774955, 1851401159, 313906178,
         2090337263, 2049815282, 2098004650, 2096093806, 1681575527,
         1292646009, 1470113097, 58359935, 1633223860, 583072330, 2109909064,
         261421910, 186375517, 1910994981, 384006202, 1815447062, 2055609816,
         1191102473, 708235392, 2098551205, 1991064826, 575633368, 1551845202,
         436335445, 2058314641, 1779899989, 1602413881, 1190110390, 1654299818,
         1329504005, 910303861, 2093049116, 894173214, 18339114, 1365927562,
         2136465681, 1121078928, 1186217206, 830737195, 869802730],
        [331, 611, 507, 156, 804, 18, 76, 437, 953, 814, 284, 586, 210, 293,
         28, 5, 780, 864, 978, 448, 306, 22, 777, 117, 714, 709, 133, 487, 468,
         926, 365, 904, 342, 452, 330, 474, 62, 684, 722, 337, 724, 82, 502,
         493, 180, 286, 756, 892, 737, 945, 801, 501, 722, 617, 701, 13, 566,
         848, 733, 153, 580, 600, 287, 355],
    ),
}


def _scale(name, rid):
    return None if SCALES[name] is None else SCALES[name][rid % 3]


@pytest.mark.parametrize("name,seed", CELLS)
def test_request_stream_is_the_recorded_one(name, seed):
    config = cells.load_config(name)
    assert config["model"]["num_classes"] == CLASSES
    assert config["serving"]["class_conditional"]
    seeds, ids = SPECS[seed]
    got = generate.Requests(config, seed).take(64)
    assert got == [generate.Spec(rid, s, c, _scale(name, rid))
                   for rid, (s, c) in enumerate(zip(seeds, ids))]


@pytest.mark.parametrize("name,seed", CELLS)
def test_program_requests_are_the_recorded_ones(name, seed):
    config = cells.load_config(name)
    seeds, ids = SPECS[seed]
    got = [system.request(config, s)
           for s in generate.Requests(config, seed).take(64)]
    assert got == [Request(rid=rid, seed=s, cfg_scale=_scale(name, rid),
                           extras={"class_ids": c})
                   for rid, (s, c) in enumerate(zip(seeds, ids))]


@pytest.mark.parametrize("name,seed", CELLS)
def test_reference_latents_are_the_recorded_bits(name, seed):
    config = tiny(name)
    specs = generate.Requests(config, seed).take(8)
    want = np.load(LATENTS)
    np.testing.assert_array_equal(
        check.reference_latents(config, seed, specs),
        want[f"{name}__{seed}__ref"])
    if seed == 0:
        np.testing.assert_array_equal(
            check.reference_latents(config, seed, specs,
                                    bits=check.CONTROL_BITS),
            want[f"{name}__{seed}__e4m3"])


@pytest.mark.parametrize("name", list(FLOPS))
def test_flop_count_and_kernel_costs_are_the_recorded_ones(name):
    config = cells.load_config(name)
    assert (cells.cost_module(config["flops"]).flops_per_row_eval(
        config["model"]) == FLOPS[name])
    for kind, cost in COSTS[name].items():
        got = cells.cost_module(KERNEL[kind]).per_call(kind, config,
                                                       ROWS[name])
        assert got == [cost]


@pytest.mark.parametrize("name", list(FLOPS))
def test_step_mfu_and_rooflines_read_the_recorded_formulas(name):
    """On a hand-made window and trace, the readers give, to the last bit,
    what the recorded counts give through the formulas they had."""
    config = cells.load_config(name)
    peaks = cells.peaks("TPU v5 lite")
    ops = {"_flash_attention.3": {"calls": 28001, "seconds": 9.583408528},
           "_flash_attention.9": {"calls": 3, "seconds": 0.0011},
           "_adaln_modulate.14": {"calls": 56002, "seconds": 0.4076},
           "_gate_residual.12": {"calls": 56002, "seconds": 0.3121},
           "_fusion.162": {"calls": 9000, "seconds": 4.08}}
    win = loadgen.Window(t0=10.0, t1=30.25,
                         counters0={"serve_active_slot_ticks": 1000},
                         counters1={"serve_active_slot_ticks": 2601})
    rec = Record(config=config, window=win, setup_s=1.0,
                 rows_per_slot=ROWS[name], peaks=peaks, trace={"ops": ops})

    def least(kind):
        flops, nbytes = COSTS[name][kind]
        return max(flops / peaks["bf16_flops"],
                   nbytes / peaks["hbm_bytes_per_s"])

    flash = (28001 * least("attention") + 3 * least("attention"))
    adaln = 56002 * least("modulate") + 56002 * least("gate_residual")
    assert cells.metric_reader("flash_attention_roofline")(rec) \
        == 100.0 * flash / (9.583408528 + 0.0011)
    assert cells.metric_reader("adaln_modulate_roofline")(rec) \
        == 100.0 * adaln / (0.4076 + 0.3121)
    assert cells.metric_reader("step_mfu")(rec) \
        == 100.0 * (1601 * ROWS[name] * FLOPS[name]) / (30.25 - 10.0) \
        / peaks["bf16_flops"]
