"""The reduction from a profiler trace to busy and idle time, per-op device
time and idle gaps by host span: on hand-made device events, and on a small
trace recorded by a traced benchmark run (a 0.03 s window of a tiny
unguided backlog on the CPU, so it holds host spans and no device plane)."""

import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import trace  # noqa: E402

RECORDED = Path(__file__).resolve().parent / "data" / "cifar_tiny_cpu.xplane.pb"
MS = 1_000_000


def test_merge_takes_the_union():
    assert trace.merge([(5, 7), (0, 2), (1, 3), (7, 9)]) == [[0, 3], [5, 9]]
    assert trace.merge([]) == []


def test_reduce_clips_to_the_window_and_attributes_idle_gaps():
    raw = {"devices": [[("fusion.1", 0 * MS, 4 * MS),      # half outside
                        ("fusion.1", 5 * MS, 7 * MS),
                        ("attn", 6 * MS, 8 * MS),          # overlaps
                        ("late", 30 * MS, 40 * MS)]],      # outside
           "host": [("bench.window", 2 * MS, 20 * MS),
                    ("bench.tick", 2 * MS, 9 * MS),
                    ("bench.submit", 4 * MS, 5 * MS),
                    ("bench.idle", 9 * MS, 20 * MS)]}
    red = trace.reduce(raw)
    assert red["window_s"] == pytest.approx(0.018)
    assert red["busy_s"] == pytest.approx(0.002 + 0.003)   # [2,4) + [5,8)
    assert red["ops"]["fusion.1"] == {"calls": 2, "seconds": pytest.approx(
        0.004)}
    assert "late" not in red["ops"]
    assert red["idle"]["bench.submit"] == pytest.approx(0.001)   # [4, 5)
    assert red["idle"]["bench.tick"] == pytest.approx(0.001)     # [8, 9)
    assert red["idle"]["bench.idle"] == pytest.approx(0.011)     # [9, 20)
    bd = trace.breakdown(red)
    assert bd["device_ops"][0][0] == "fusion.1"
    assert bd["idle_gaps"][0] == ["bench.idle", pytest.approx(0.011)]


def test_reduce_averages_busy_time_over_devices():
    raw = {"devices": [[("a", 0, 10 * MS)], [("a", 0, 5 * MS)]],
           "host": [("bench.window", 0, 10 * MS)]}
    red = trace.reduce(raw)
    assert red["devices"] == 2
    assert red["busy_s"] == pytest.approx(0.0075)


def test_reduce_needs_the_window_span():
    with pytest.raises(ValueError):
        trace.reduce({"devices": [], "host": []})


def test_recorded_trace():
    raw = trace.read(trace.load(str(RECORDED)))
    names = [n for n, _, _ in raw["host"]]
    assert names.count("bench.window") == 1
    assert names.count("bench.tick") == 21
    assert raw["devices"] == []              # recorded on the CPU
    red = trace.reduce(raw)
    assert 0.02 < red["window_s"] < 0.05
    assert red["busy_s"] == 0 and red["ops"] == {}
    w0, w1 = next((s, e) for n, s, e in raw["host"] if n == "bench.window")
    segs = trace.segments([(s, e, n) for n, s, e in raw["host"]
                           if n != "bench.window"])
    assert all(w0 <= s < e <= w1 for s, e, _ in segs)
    assert {n for _, _, n in segs} == {"bench.tick", "bench.submit"}
