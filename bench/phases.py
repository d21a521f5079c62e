"""The device's idle time put down to the serving program's own phases.

The scheduler opens a `jax.profiler` annotation named `serve.<phase>`
around each of its phases (`repro.obs.trace.phase`): `serve.tick`, and
inside it `serve.admission` (holding `serve.admit_apply`, which also draws
the admitted latents on the device), `serve.dispatch`, `serve.readback`,
`serve.emit`;
besides, `serve.submit` and `serve.recover`. They lie on the host plane of
the `.xplane.pb`, on the device trace's clock, beside the benchmark's own
`bench.*` spans, which `bench/trace.py` reads. Here the same reduction,
`trace.reduce`, puts the idle gaps down to the innermost `serve.*` span
instead. A program without these spans gives no events, and every idle
gap then goes to "other".
"""

from __future__ import annotations

from bench import trace

PREFIX = "serve."
ADMISSION = "serve.admission"


def read_program(profile) -> list:
    """The program's `serve.*` host spans: [(name, start, end)] in ns."""
    return [(e.name, e.start_ns, e.start_ns + e.duration_ns)
            for plane in profile.planes
            if not trace.DEVICE_PLANE.match(plane.name)
            for line in plane.lines for e in line.events
            if e.name.startswith(PREFIX)]


def _reduce(raw: dict, spans: list) -> dict:
    """`trace.reduce` of `raw`'s window and devices, with `spans` as the
    host spans that idle time is put down to."""
    window = [ev for ev in raw["host"] if ev[0] == trace.WINDOW]
    return trace.reduce({"devices": raw["devices"], "host": window + spans})


def idle_by_phase(raw: dict, program: list) -> dict:
    """{innermost span name, or "other": idle seconds} over the window."""
    return _reduce(raw, program)["idle"]


def admission_idle_share(raw: dict, program: list):
    """Share (%) of the window in which the device runs no operation while
    the host is inside a `serve.admission` span, its children included."""
    red = _reduce(raw, [ev for ev in program if ev[0] == ADMISSION])
    if red["window_s"] <= 0:
        return None
    return 100.0 * red["idle"].get(ADMISSION, 0.0) / red["window_s"]
