"""Serving observability (DESIGN.md §15): tracer, metrics registry, probe.

The load-bearing properties:

* the registry IS the metrics substrate — `run_trace`'s ServeMetrics is
  derived from a registry snapshot delta, and must equal the legacy
  arithmetic recomputed from `sched.completions` here;
* the deterministic snapshot slice (`deterministic_only=True`) is
  bit-identical across pipeline depths 1/2/3 on the same arrival trace;
* attaching a Tracer changes NOTHING about the computation — latents are
  exactly equal with tracing on and off — and the exported trace validates
  against the Chrome trace_event schema;
* zero-completion runs report 0.0 percentiles everywhere (including
  per-tier) instead of crashing np.percentile;
* the metrics artifact round-trips: `obsreport --check`'s re-derivation of
  ServeMetrics from the raw snapshot equals the embedded aggregate.
"""

import json

import numpy as np
import pytest

from repro.engine import EngineSpec, SamplerEngine
from repro.obs import (MetricsRegistry, QualityProbe, Tracer, delta,
                      parse_fullname, probe_selected, render_report,
                      snapshot_percentile, span_stats, validate_metrics,
                      validate_trace, write_metrics_artifact)
from repro.obs.metrics import Histogram
from repro.serving import Request, SlotScheduler, run_trace
from repro.serving.server import serve_metrics_from_snapshot

from test_serving import _cfg_engine, _eps_jx, _tier_specs, _x_T

# ---------------------------------------------------------------------------
# metrics registry primitives
# ---------------------------------------------------------------------------


def test_histogram_buckets_and_percentiles():
    h = Histogram(buckets=(1.0, 2.0, 4.0))
    assert h.percentile(95) == 0.0  # empty -> 0.0, never an exception
    for v in (0.5, 1.0, 3.0, 100.0):
        h.observe(v)
    # bisect_left on upper bounds: 1.0 lands IN the le=1 bucket
    assert h.counts == [2, 0, 1, 1]
    assert h.count == 4 and h.sum == pytest.approx(104.5)
    assert h.percentile(50) == float(np.percentile([0.5, 1.0, 3.0, 100.0], 50))


def test_histogram_sample_cap_sets_truncated_flag():
    h = Histogram(buckets=(1.0,), sample_cap=2)
    for v in (0.1, 0.2, 0.3):
        h.observe(v)
    assert h.samples == [0.1, 0.2] and h.samples_truncated
    assert h.count == 3  # bucket state keeps counting past the cap


def test_registry_get_or_create_and_kind_mismatch():
    reg = MetricsRegistry()
    c = reg.counter("x", {"tier": "fast"})
    assert reg.counter("x", {"tier": "fast"}) is c
    assert reg.counter("x", {"tier": "slow"}) is not c
    with pytest.raises(ValueError, match="already registered"):
        reg.gauge("x", {"tier": "fast"})


def test_snapshot_delta_and_wall_exclusion():
    reg = MetricsRegistry()
    c = reg.counter("ticks")
    g = reg.gauge("wall_s", wall=True)
    h = reg.histogram("lat", buckets=(1.0, 4.0))
    c.inc(3)
    h.observe(2.0)
    snap0 = reg.snapshot()
    c.inc(2)
    g.set(1.5)
    h.observe(0.5)
    d = delta(snap0, reg.snapshot())
    assert d["ticks"]["value"] == 2
    assert d["lat"]["count"] == 1 and d["lat"]["samples"] == [0.5]
    assert d["wall_s"]["value"] == 1.5  # gauges keep the after-value
    # wall metrics are excluded from the deterministic slice
    assert "wall_s" not in reg.snapshot(deterministic_only=True)
    assert "ticks" in reg.snapshot(deterministic_only=True)


def test_fullname_roundtrip_and_exposition():
    reg = MetricsRegistry()
    reg.counter("done", {"tier": "fast"}).inc(7)
    reg.histogram("lat", buckets=(1.0, 2.0), help="latency").observe(1.5)
    snap = reg.snapshot()
    assert parse_fullname('done{tier="fast"}') == ("done", {"tier": "fast"})
    assert all(parse_fullname(full)[0] in ("done", "lat") for full in snap)
    text = reg.exposition()
    assert 'done{tier="fast"} 7' in text
    assert "# TYPE lat histogram" in text
    assert 'lat_bucket{le="+Inf"} 1' in text and "lat_count 1" in text


# ---------------------------------------------------------------------------
# tracer + schema validation
# ---------------------------------------------------------------------------


def test_tracer_events_and_validation(tmp_path):
    tr = Tracer(capacity=64, meta={"arch": "test"})
    t0 = 1000
    tr.complete("tick", t0, t0 + 5000, args={"tick": 0})
    tr.instant("note", args={"k": 1})
    tr.counter("slots", {"busy": 2})
    tr.async_begin("request", 7, args={"tier": "fast"})
    tr.async_instant("admit", 7)
    tr.async_end("request", 7)
    obj = json.loads(json.dumps(tr.to_json()))
    assert validate_trace(obj) == []
    phs = [e["ph"] for e in obj["traceEvents"]]
    assert phs.count("X") == 1 and "b" in phs and "e" in phs
    assert obj["otherData"]["arch"] == "test"
    p = tmp_path / "t.json"
    tr.export(str(p))
    assert validate_trace(json.loads(p.read_text())) == []


def test_validate_trace_names_violations():
    assert validate_trace([]) != []  # not an object
    bad = {"traceEvents": [{"ph": "X", "name": "t", "ts": 0}],  # no dur
           "otherData": {"schema": "repro.obs.trace/v1",
                         "dropped_events": 0}}
    errs = validate_trace(bad)
    assert any("dur" in e for e in errs)
    unbalanced = {"traceEvents": [{"ph": "b", "name": "request", "ts": 0,
                                   "id": "1", "cat": "request"}],
                  "otherData": {"schema": "repro.obs.trace/v1",
                                "dropped_events": 0}}
    assert any("unbalanced" in e for e in validate_trace(unbalanced))


def test_tracer_ring_drops_oldest_and_counts():
    tr = Tracer(capacity=4)
    for i in range(10):
        tr.instant(f"e{i}")
    assert tr.dropped == 6
    names = [e["name"] for e in tr.events()]
    assert names == ["e6", "e7", "e8", "e9"]
    obj = json.loads(json.dumps(tr.to_json()))
    assert obj["otherData"]["dropped_events"] == 6
    # balanced-span validation is skipped once events were dropped
    assert validate_trace(obj) == []


# ---------------------------------------------------------------------------
# scheduler integration: derivation parity, determinism, zero-change tracing
# ---------------------------------------------------------------------------


def _poisson_reqs(n=9, rate=0.5, seed=5):
    from repro.serving import poisson_requests
    return [Request(rid=r.rid, arrival=r.arrival, x_T=_x_T(r.rid))
            for r in poisson_requests(n, rate=rate, seed=seed)]


def _sched(gaussian_dpm, depth=1, **kw):
    eng = SamplerEngine(gaussian_dpm.schedule, eps=_eps_jx(gaussian_dpm))
    program = eng.build_step(EngineSpec(solver="unipc", order=3, nfe=7))
    return SlotScheduler(program, 3, (8,), pipeline_depth=depth, **kw)


def test_registry_derived_metrics_match_legacy_arithmetic(gaussian_dpm):
    """ServeMetrics (now derived from the registry snapshot delta) must equal
    the legacy formulas recomputed from the completion records."""
    sched = _sched(gaussian_dpm)
    m = run_trace(sched, _poisson_reqs())
    cs = sched.completions
    lat = [c.finish_clock - c.arrival for c in cs]
    assert m.requests == 9 and m.completed == len(cs) == 9
    assert m.ticks == m.evals == sched.ticks
    assert m.makespan_ticks == max(c.finish_clock for c in cs)
    assert m.throughput_per_tick == len(cs) / max(m.makespan_ticks, 1.0)
    assert m.latency_ticks_p50 == float(np.percentile(lat, 50))
    assert m.latency_ticks_p95 == float(np.percentile(lat, 95))
    assert m.evals_per_latent == sched.ticks * sched.slots / len(cs)
    assert 0.0 < m.occupancy <= 1.0
    assert m.host_phase_us_per_tick is not None
    split = (m.host_phase_us_per_tick["admission"]
             + m.host_phase_us_per_tick["bookkeeping"])
    assert split == pytest.approx(m.host_us_per_tick)


def test_zero_completion_run_reports_zeros():
    """The np.percentile edge case (satellite): an empty snapshot delta —
    a run that admitted and completed nothing — derives all-zero metrics,
    per-tier included, with no exception anywhere."""
    m = serve_metrics_from_snapshot({}, mode="continuous", slots=4, n_rows=8)
    assert m.completed == 0 and m.ticks == 0
    assert m.occupancy == 0.0 and m.latency_ticks_p50 == 0.0
    assert m.latency_ticks_p95 == 0.0 and m.host_us_per_tick == 0.0
    assert m.throughput_per_tick == 0.0
    # a tier that registered but never completed: empty histogram -> 0.0
    d = {'tier_completed{tier="fast"}': {"type": "counter", "wall": False,
                                         "value": 0},
         'tier_latency_ticks{tier="fast"}': {"type": "histogram",
                                             "wall": False,
                                             "buckets": [1.0], "counts": [0, 0],
                                             "sum": 0.0, "count": 0,
                                             "samples": []}}
    m = serve_metrics_from_snapshot(d, mode="continuous", slots=4, n_rows=8)
    assert m.per_tier == {"fast": {"completed": 0, "evals": 0,
                                   "eval_cost": 0.0,
                                   "latency_ticks_p50": 0.0}}


def test_deterministic_snapshot_identical_across_depths(gaussian_dpm):
    """The registry's deterministic slice is bit-identical at pipeline
    depths 1/2/3 on the same arrival trace — wall-clock metrics are the
    only thing depth may change."""
    snaps = {}
    for depth in (1, 2, 3):
        sched = _sched(gaussian_dpm, depth=depth)
        run_trace(sched, _poisson_reqs())
        snaps[depth] = sched.registry.snapshot(deterministic_only=True)
    assert snaps[1] == snaps[2] == snaps[3]
    assert any(parse_fullname(k)[0] == "latency_ticks" for k in snaps[1])


def test_tracer_changes_nothing_and_trace_validates(gaussian_dpm):
    """Attaching a Tracer is observation only: latents, completion records,
    and deterministic metrics are EXACTLY equal to the untraced run, and the
    emitted trace is schema-valid with balanced request spans."""
    plain = _sched(gaussian_dpm)
    m0 = run_trace(plain, _poisson_reqs())
    tr = Tracer()
    traced = _sched(gaussian_dpm, depth=2, tracer=tr)
    m1 = run_trace(traced, _poisson_reqs())
    assert [c.rid for c in plain.completions] \
        == [c.rid for c in traced.completions]
    for a, b in zip(plain.completions, traced.completions):
        np.testing.assert_array_equal(a.latent, b.latent)
    assert (m0.ticks, m0.latency_ticks_p50, m0.occupancy) \
        == (m1.ticks, m1.latency_ticks_p50, m1.occupancy)
    obj = json.loads(json.dumps(tr.to_json()))
    assert validate_trace(obj) == []
    stats = span_stats(obj)
    assert {"serve.tick", "serve.admission", "serve.admit_apply",
            "serve.dispatch", "serve.readback", "serve.emit",
            "serve.submit"} <= set(stats)
    assert "serve.draw" not in stats    # admission draws on the device
    assert stats["serve.tick"]["count"] == m1.ticks
    begins = sum(1 for e in obj["traceEvents"] if e["ph"] == "b")
    ends = sum(1 for e in obj["traceEvents"] if e["ph"] == "e")
    assert begins == ends == 9


def test_host_syncs_count_every_blocking_read(gaussian_dpm):
    """Every blocking device->host read is counted where it happens: one
    readback per flight that carries completions, whether tick() or flush()
    consumes it; admission draws seed requests' latents on the device and
    reads none back. host_phase_ns keeps tick()'s readback time only."""
    sched = _sched(gaussian_dpm, depth=3)
    reg = sched.registry

    def val(full):
        return reg.snapshot()[full]["value"]

    for rid in range(5):
        sched.submit(Request(rid=rid, seed=rid))
    while sched.queue or sched.active:
        sched.tick()
    # 3 slots, 8 rows: rids 0-2 finish on tick 8, rids 3-4 on tick 16
    assert val("serve_device_draws") == val("serve_admitted") == 5
    assert val('host_syncs{site="draw"}') == 0
    assert val('host_syncs{site="readback"}') == 1       # tick 8's flight
    phase_rb = val('host_phase_ns{phase="readback"}')
    blocked_rb = val('host_blocked_ns{site="readback"}')
    assert phase_rb == blocked_rb > 0
    assert len(sched.flush()) == 2
    assert val('host_syncs{site="readback"}') == 2
    assert val('host_blocked_ns{site="readback"}') > blocked_rb
    assert val('host_phase_ns{phase="readback"}') == phase_rb
    assert val('host_syncs{site="recover"}') == 0
    assert val('host_blocked_ns{site="draw"}') == 0


def test_obs_imports_no_jax():
    import os
    import subprocess
    import sys
    from pathlib import Path

    src = str(Path(__file__).resolve().parents[1] / "src")
    code = "import sys, repro.obs; assert 'jax' not in sys.modules"
    p = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=120,
                       env={**os.environ, "PYTHONPATH": src})
    assert p.returncode == 0, p.stderr


def test_tiered_metrics_ride_the_registry(vp):
    """Per-tier rows come from labelled registry metrics now; the derivation
    must still produce the plan-bank view (each tier's completions, evals,
    eval_cost, latency p50)."""
    eng = _cfg_engine(vp)
    tiers = {k: EngineSpec(solver="unipc", nfe=s.nfe, order=s.order,
                           cfg_scale=2.0)
             for k, s in _tier_specs().items()}
    program = eng.build_bank(tiers)
    sched = SlotScheduler(program, 3, (8,))
    names = ["fast", "balanced", "quality"]
    reqs = [Request(rid=i, arrival=float(i), x_T=_x_T(i), tier=names[i % 3],
                    cfg_scale=2.0)
            for i in range(6)]
    m = run_trace(sched, reqs)
    assert m.completed == 6 and set(m.per_tier) == set(names)
    for t in names:
        cs = [c for c in sched.completions if c.tier == t]
        row = m.per_tier[t]
        assert row["completed"] == len(cs) == 2
        assert row["evals"] == cs[0].evals
        assert row["latency_ticks_p50"] == float(np.percentile(
            [c.finish_clock - c.arrival for c in cs], 50))


# ---------------------------------------------------------------------------
# artifact round-trip (the obsreport --check contract)
# ---------------------------------------------------------------------------


def test_metrics_artifact_roundtrips_exactly(gaussian_dpm, tmp_path):
    """Writing the artifact and re-deriving ServeMetrics from its raw
    snapshot (through JSON) must reproduce the embedded aggregate EXACTLY —
    the acceptance criterion obsreport --check enforces."""
    from repro.launch.obsreport import check_metrics_roundtrip

    sched = _sched(gaussian_dpm, depth=2)
    reg = sched.registry
    snap0 = reg.snapshot()
    rows = []
    m = run_trace(sched, _poisson_reqs(), snapshot_every=3, snapshot_log=rows)
    path = tmp_path / "metrics.json"
    write_metrics_artifact(
        str(path), metrics=delta(snap0, reg.snapshot()),
        serve_metrics=m.row(),
        static={"mode": m.mode, "slots": m.slots, "n_rows": m.n_rows,
                "pipeline_depth": m.pipeline_depth},
        exposition=reg.exposition(), rows=rows)
    obj = json.loads(path.read_text())
    assert validate_metrics(obj) == []
    assert check_metrics_roundtrip(obj) == []
    assert len(obj["rows"]) >= 1
    # periodic rows are the compact sample-free form
    for row in obj["rows"]:
        for full, rec in row["metrics"].items():
            assert "samples" not in rec, full
    report = render_report(metrics=obj)
    assert "where a tick goes" in report and "admission" in report


def test_validate_metrics_names_violations():
    assert validate_metrics([]) != []
    bad = {"schema": "repro.obs.metrics/v1",
           "run": {"metrics": {"h": {"type": "histogram", "buckets": [1.0],
                                     "counts": [1], "count": 2, "sum": 0.5}}},
           "serve_metrics": {}, "exposition": "", "rows": []}
    errs = validate_metrics(bad)
    assert any("length mismatch" in e for e in errs)
    assert any("count != sum" in e for e in errs)
    assert any("serve_metrics" in e
               for e in validate_metrics({"schema": "repro.obs.metrics/v1",
                                          "run": {"metrics": {}}}))


# ---------------------------------------------------------------------------
# quality probe
# ---------------------------------------------------------------------------


def test_probe_selection_is_deterministic_and_proportional():
    sel = [probe_selected(r, 0.25, salt=3) for r in range(4000)]
    assert sel == [probe_selected(r, 0.25, salt=3) for r in range(4000)]
    assert 0.2 < np.mean(sel) < 0.3
    assert not any(probe_selected(r, 0.0) for r in range(100))
    assert all(probe_selected(r, 1.0) for r in range(100))


def test_probe_records_discrepancy_against_reference(gaussian_dpm):
    """End to end on the scheduler: a probe replaying every completion
    against a higher-NFE uniform scan records small-but-nonzero trajectory
    discrepancies per tier, into the registry and the summary."""
    import jax.numpy as jnp

    eng = SamplerEngine(gaussian_dpm.schedule, eps=_eps_jx(gaussian_dpm))
    ref = eng.build(EngineSpec(solver="unipc", order=3, nfe=24))

    def reference_fn(x_T, g=None, extras=None):
        return np.asarray(ref(jnp.asarray(x_T)[None, :]))[0]

    program = eng.build_step(EngineSpec(solver="unipc", order=3, nfe=7))
    probe = QualityProbe(reference_fn, fraction=1.0)
    sched = SlotScheduler(program, 3, (8,), probe=probe)
    run_trace(sched, _poisson_reqs(n=5))
    assert len(probe.results) == 5
    for r in probe.results:
        assert 0.0 < r["discrepancy"] < 0.5
    summ = probe.summary()
    assert summ["default"]["count"] == 5
    assert 0.0 < summ["default"]["mean"] <= summ["default"]["max"]
    snap = sched.registry.snapshot()
    assert snap['probe_requests{tier="default"}']["value"] == 5
    assert snap['probe_discrepancy_hist{tier="default"}']["count"] == 5


def test_probe_fraction_and_max_probes_bound_the_replay(gaussian_dpm):
    calls = []

    def reference_fn(x_T, g=None, extras=None):
        calls.append(1)
        return np.asarray(x_T)

    probe = QualityProbe(reference_fn, fraction=1.0, max_probes=2)
    sched = _sched(gaussian_dpm, probe=probe)
    run_trace(sched, _poisson_reqs(n=6))
    assert len(calls) == 2 and len(probe.results) == 2
    # unselected rids never touch the reference runner
    probe0 = QualityProbe(reference_fn, fraction=0.0)
    sched0 = _sched(gaussian_dpm, probe=probe0)
    run_trace(sched0, _poisson_reqs(n=4))
    assert len(calls) == 2 and probe0.results == []


def test_extras_bytes_count_every_admission_apply(gaussian_dpm, monkeypatch):
    """serve_extras_bytes counts exactly the nbytes of the per-request
    conditioning buffers each admission apply puts on the device: full
    width (slots x shape) for every key, once per tick that admits."""
    from repro.serving import scheduler as sched_mod

    applied = []
    real = sched_mod._apply_admission

    def counting(*args, **kw):
        applied.append(sum(np.asarray(a).nbytes for a in args[10].values()))
        return real(*args, **kw)

    monkeypatch.setattr(sched_mod, "_apply_admission", counting)
    eng = SamplerEngine(gaussian_dpm.schedule, eps=_eps_jx(gaussian_dpm))
    program = eng.build_step(EngineSpec(solver="unipc", order=3, nfe=7))

    def ignore_extras(state, meta, g, extras):
        return program.flight(program.nets, state, meta, g, None)

    init = {"caption": np.zeros((5, 4), np.float32), "caption_len": 1}
    sched = SlotScheduler(program, 3, (8,), extras_init=init,
                          step_override=ignore_extras)
    for rid in range(5):
        sched.submit(Request(rid=rid, seed=rid, extras={
            "caption": np.full((5, 4), rid, np.float32),
            "caption_len": rid + 1}))
    while sched.queue or sched.active:
        sched.tick()
    sched.flush()
    # 3 slots: rids 0-2 admitted on the first tick, 3-4 once they finish
    assert len(applied) == 2
    assert applied == [3 * 5 * 4 * 4 + 3 * 4] * 2
    got = sched.registry.snapshot()["serve_extras_bytes"]["value"]
    assert got == sum(applied)
