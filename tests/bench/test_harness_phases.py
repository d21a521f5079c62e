"""The program's `serve.*` phase spans in the profiler's trace, on the same
clock as the benchmark's `bench.*` spans; the readers of the program's
host-sync counters; and the idle time put down to the program's phases."""

import sys
from collections import Counter
from pathlib import Path

import jax
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import cell as cells  # noqa: E402
from bench import loadgen, phases, system, trace, weights  # noqa: E402
from bench.harness import Record  # noqa: E402
from repro.obs import Tracer, span_stats, validate_trace  # noqa: E402

from test_harness_run import BACKLOG, tiny  # noqa: E402
from test_harness_trace import MS, RECORDED  # noqa: E402

SEED = 2 ** 31 + 11


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """A tiny unguided backlog on the CPU, its window profiled as the
    harness profiles it, with a Tracer attached over the same window."""
    config = tiny("dit-s4")
    served = system.build(config, weights.make_params(config, SEED))
    drv = loadgen.LoadGen(served, BACKLOG, config, SEED, per_slot=1,
                          annotate=True)
    drv.warm_up(ticks=2 * served.sched.program.n_rows + 2, drain=False)
    tracer = Tracer()
    served.sched.tracer = tracer
    logdir = str(tmp_path_factory.mktemp("profile"))
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(logdir, profiler_options=opts)
    win = drv.backlog(0.2)
    jax.profiler.stop_trace()
    served.sched.tracer = None
    profile = trace.load(trace.find_xplane(logdir))
    return win, trace.read(profile), phases.read_program(profile), tracer


def _inside(inner, outers):
    _, s, e = inner
    return any(os <= s and e <= oe for _, os, oe in outers)


def test_program_spans_nest_inside_the_benchmarks(traced):
    win, raw, program, _ = traced
    by = {}
    for ev in program:
        by.setdefault(ev[0], []).append(ev)
    assert {"serve.tick", "serve.admission", "serve.admit_apply",
            "serve.dispatch", "serve.readback", "serve.emit",
            "serve.submit"} <= set(by)
    # admission draws every latent inside its one apply: no span a request
    assert "serve.draw" not in by
    bench_ticks = [ev for ev in raw["host"] if ev[0] == "bench.tick"]
    ticks = by["serve.tick"]
    assert all(_inside(ev, bench_ticks) for ev in ticks)
    assert all(_inside(ev, ticks) for ev in by["serve.admission"])
    assert all(_inside(ev, by["serve.admission"])
               for ev in by["serve.admit_apply"])
    for name in ("serve.dispatch", "serve.readback", "serve.emit"):
        assert all(_inside(ev, ticks) for ev in by[name]), name
    submits = [ev for ev in raw["host"] if ev[0] == "bench.submit"]
    assert all(_inside(ev, submits) for ev in by["serve.submit"])
    # one serve.tick per tick of the window, one serve.admit_apply per
    # tick that admits, each admitted latent drawn on the device
    assert len(ticks) == win.ticks
    admitted = (win.counters1["serve_admitted"]
                - win.counters0["serve_admitted"])
    draws = (win.counters1["serve_device_draws"]
             - win.counters0["serve_device_draws"])
    assert draws == admitted > 0
    assert 0 < len(by["serve.admit_apply"]) <= admitted


def test_tracer_export_carries_the_profilers_spans(traced):
    _, _, program, tracer = traced
    obj = tracer.to_json()
    # attached mid-stream, the tracer saw requests end that began before
    # it: its phase spans alone form a complete trace
    spans = [e for e in obj["traceEvents"] if e["ph"] == "X"]
    assert validate_trace({**obj, "traceEvents": spans}) == []
    stats = span_stats(obj)
    assert {k: v["count"] for k, v in stats.items()} \
        == dict(Counter(n for n, _, _ in program))


def test_draws_are_the_counted_syncs_at_admission(traced):
    """Admission draws each latent on the device and reads nothing back:
    the draws are counted, and no sync or wait is counted at the draw."""
    win, _, _, _ = traced

    def d(k):
        return win.counters1[k] - win.counters0[k]

    assert d("serve_device_draws") == d("serve_admitted") > 0
    assert d('host_syncs{site="draw"}') == 0
    assert d('host_blocked_ns{site="draw"}') == 0
    assert d('host_syncs{site="readback"}') > 0


def test_idle_by_phase_on_the_traced_run(traced):
    _, raw, program, _ = traced
    # the CPU has no device plane: one device that runs nothing makes the
    # whole window idle, for the program's spans to take apart
    idle_dev = {"devices": [[]], "host": raw["host"]}
    window_s = trace.reduce(idle_dev)["window_s"]
    idle = phases.idle_by_phase(idle_dev, program)
    assert set(idle) <= {n for n, _, _ in program} | {"other"}
    assert sum(idle.values()) == pytest.approx(window_s)
    assert "serve.draw" not in idle
    assert idle["serve.admit_apply"] > 0 and idle["serve.dispatch"] > 0
    assert idle["other"] < idle["serve.tick"] + idle["serve.dispatch"]
    share = phases.admission_idle_share(idle_dev, program)
    assert 100 * (idle["serve.admission"] + idle["serve.admit_apply"]) \
        / window_s == pytest.approx(share)
    assert phases.idle_by_phase(raw, program) == {}


def test_phase_reduction_on_hand_made_events():
    raw = {"devices": [[("step", 3 * MS, 6 * MS), ("step", 8 * MS, 9 * MS)]],
           "host": [("bench.window", 0, 10 * MS),
                    ("bench.tick", 0, 10 * MS)]}
    program = [("serve.tick", 0, 10 * MS),
               ("serve.admission", 1 * MS, 4 * MS),
               ("serve.draw", 1 * MS, 2 * MS),
               ("serve.readback", 6 * MS, 8 * MS),
               ("serve.emit", 20 * MS, 30 * MS)]          # outside
    idle = phases.idle_by_phase(raw, program)
    assert idle == {"serve.tick": pytest.approx(0.002),       # [0,1) [9,10)
                    "serve.draw": pytest.approx(0.001),       # [1, 2)
                    "serve.admission": pytest.approx(0.001),  # [2, 3)
                    "serve.readback": pytest.approx(0.002)}   # [6, 8)
    # children included: [1, 3) of the window's 10 ms
    assert phases.admission_idle_share(raw, program) == pytest.approx(20.0)
    # the benchmark's own reduction reads as before
    assert trace.reduce(raw)["idle"] == {"bench.tick": pytest.approx(0.006)}
    # a program without the spans: the idle time is all "other"
    assert phases.idle_by_phase(raw, []) == {"other": pytest.approx(0.006)}
    assert phases.admission_idle_share(raw, []) == 0.0


def test_recorded_trace_reduces_as_before():
    """The fixture was recorded from a program without phase spans: the
    benchmark's reduction of it is unchanged, and the phase reduction
    finds nothing to put the idle time down to."""
    profile = trace.load(str(RECORDED))
    raw = trace.read(profile)
    red = trace.reduce(raw)
    assert red == {"window_s": pytest.approx(0.029039418, abs=1e-9),
                   "busy_s": 0.0, "devices": 0, "ops": {}, "idle": {}}
    assert trace.breakdown(red) == {"device_ops": [], "idle_gaps": []}
    assert phases.read_program(profile) == []
    assert phases.idle_by_phase(raw, []) == {}


def _record(ticks, c0, c1):
    return Record(config={}, window=loadgen.Window(
        ticks=ticks, counters0=c0, counters1=c1), setup_s=1.0,
        rows_per_slot=1, peaks=None)


def test_sync_readers_on_hand_made_records():
    wait = cells.metric_reader("admission_wait_us_per_tick")
    syncs = cells.metric_reader("host_syncs_per_tick")
    c0 = {'host_syncs{site="draw"}': 10, 'host_syncs{site="readback"}': 4,
          'host_syncs{site="recover"}': 0,
          'host_blocked_ns{site="draw"}': 1_000_000}
    c1 = {'host_syncs{site="draw"}': 40, 'host_syncs{site="readback"}': 14,
          'host_syncs{site="recover"}': 1,
          'host_blocked_ns{site="draw"}': 41_000_000}
    rec = _record(10, c0, c1)
    assert wait(rec) == pytest.approx(4000.0)        # 40 ms over 10 ticks
    assert syncs(rec) == pytest.approx(4.1)          # (30 + 10 + 1) / 10
    # a program without the counters, or a window without ticks: no value
    for r in (_record(10, {"serve_ticks": 0}, {"serve_ticks": 10}),
              _record(0, c0, c1)):
        assert wait(r) is None and syncs(r) is None


def test_both_backlog_cells_list_the_sync_metrics():
    for name in ("i256-cfg-backlog", "s4-backlog"):
        got = {m["name"] for m in cells.load_cell(name).per_layer}
        assert {"admission_wait_us_per_tick", "host_syncs_per_tick"} <= got
