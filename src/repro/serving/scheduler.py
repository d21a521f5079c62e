"""Request-level slot scheduler for continuous-batching diffusion serving.

The engine compiles a `StepProgram` (per-slot step function over the solver
table, `SamplerEngine.build_step`); this module owns everything request-shaped
around it: a fixed set of B slots, a FIFO admission queue, per-request
seed / cfg-scale / NFE-budget bookkeeping, and finished-latent emission.

One `tick()` = one batched model eval: admit queued requests into free slots
(write the request's initial latent, zero the slot's eval ring, set its
guidance scale), dispatch the step program once for the whole batch, then
emit every slot that just executed its last row. Because admission resets the
ring and the zero-padded warm-up rows null empty ring slots, a request
admitted mid-flight reproduces the uniform `build()` scan for its own
(solver, order, nfe, seed, cfg-scale) exactly — the parity property
`tests/test_serving.py` pins across solvers.

The dispatched program is `StepProgram.step_flight` (DESIGN.md §13): the
per-slot row / budget / busy counters live on device, so the host never
ships a rebuilt `idx` vector — it only scatters admissions in and reads the
per-slot done mask back. Completion readback is a *trailing stream*: each
tick with predicted completions issues ONE batched gather of the finished
slots' latents plus an async host copy, and the concrete values are consumed
`pipeline_depth - 1` ticks later. `pipeline_depth=1` (the default) is the
synchronous loop — dispatch, then consume the same tick's readback before
returning — while depth >= 2 keeps that many ticks in flight, overlapping
host bookkeeping and admission with device execution (JAX async dispatch).
Both depths run the identical compiled program over the identical admission
schedule, so finished latents, completion order, and tick-clock metrics are
bit-identical across depths (`tests/test_async_serving.py`).

Idle slots park on row 0 (an identity update), so the batch shape — and the
compiled program — never changes. `gang=True` degrades admission to
sequential full-batch serving (admit only when *every* slot is free): the
baseline the benchmarks compare continuous batching against.
"""

from __future__ import annotations

import time
from collections import deque
from dataclasses import dataclass, field, replace as dc_replace
from functools import partial
from typing import Deque, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..engine.compiler import DONE_NONFINITE
from ..engine.engine import StepProgram
from ..obs.metrics import MetricsRegistry
from ..obs.trace import phase
from .faults import FaultInjector, FaultPlan
from .resilience import (DEFAULT_RESILIENCE, FAIL_NONFINITE,
                         REJECT_EXPIRED, REJECT_QUEUE_FULL, Rejection,
                         ResilienceConfig, fallback_tier,
                         validate_resilience)

# fixed upper-bound buckets for the scheduler's streaming histograms
# (DESIGN.md §15): tick-denominated and depth-invariant, so the bucket
# counts are part of the deterministic metrics slice
QUEUE_DEPTH_BUCKETS = (0, 1, 2, 4, 8, 16, 32, 64, 128)
BUSY_SLOT_BUCKETS = (0, 1, 2, 4, 8, 16, 32, 64)
OCCUPANCY_BUCKETS = (0.125, 0.25, 0.375, 0.5, 0.625, 0.75, 0.875, 1.0)
LATENCY_TICK_BUCKETS = (1, 2, 4, 8, 16, 32, 64, 128, 256, 512)
EVAL_COST_BUCKETS = (1, 2, 4, 8, 12, 16, 24, 32, 48, 64)
HOST_PHASES = ("admission", "dispatch", "readback", "bookkeeping")
# where the host blocks on a device->host read: the quality probe's draw
# of a replayed request's initial latent (admission draws on the device,
# with no read), the trailing readback of a flight, the desync recovery's
# meta read
SYNC_SITES = ("draw", "readback", "recover")

# resilience / fault-injection event counters (DESIGN.md §16). Registered
# lazily — on the first event of each kind — so a fault-free run's metrics
# snapshot is exactly the pre-resilience snapshot.
EVENT_COUNTER_HELP = {
    "serve_rejected": "requests shed before admission (by reason)",
    "serve_shed_degraded": "requests remapped to the shed tier at submit",
    "serve_retries": "non-finite completions re-admitted on a fallback tier",
    "serve_failed": "failed completions emitted (retry budget exhausted)",
    "serve_desync_recoveries": "host/device desync recoveries",
    "serve_requeued": "in-flight requests requeued by desync recovery",
    "fault_injected": "injected faults that fired (by kind)",
}


def key_words(seed: int) -> np.ndarray:
    """The raw threefry key words of `jax.random.PRNGKey(seed)`, derived
    in numpy so that building them needs no device op: the seed's low 32
    bits, and with x64 on its next 32 (with it off, PRNGKey truncates the
    seed to 32 bits). `_apply_admission` draws from them on the device."""
    seed = int(seed)
    hi = (seed >> 32) & 0xFFFFFFFF if jax.config.jax_enable_x64 else 0
    return np.array([hi, seed & 0xFFFFFFFF], np.uint32)


def _normals(keys, sample_shape, dtype):
    """One standard-normal latent per row of `keys` (uint32[n, 2] key
    words): row i equals `jax.random.normal(PRNGKey(seed_i), ...)`."""
    return jax.vmap(lambda k: jax.random.normal(
        jax.random.wrap_key_data(k), sample_shape, dtype))(keys)


@partial(jax.jit, static_argnames=("has_cache", "uses_cfg"))
def _apply_admission(state, meta, g, extras,
                     mask, drawn, keys, x_new, meta_new, g_new, ex_new,
                     *, has_cache, uses_cfg):
    """Fold one tick's admissions into the device state in ONE fixed-shape
    dispatch: the host builds full-width (B-wide) masked update buffers in
    numpy and this compiled apply selects them in. A seed request's x_T is
    drawn here from its slot's key words (`drawn` marks those slots), a
    given x_T comes in `x_new`; the draw runs for all B slots, so no shape
    depends on the admissions. The executable compiles once per (B, sample
    shape) — eager per-count scatters would recompile for every distinct
    admission count. Module-level so the compile cache is shared across
    scheduler instances."""
    x, E = state[0], state[1]
    mx = mask.reshape(mask.shape + (1,) * (x.ndim - 1))
    dx = drawn.reshape(mx.shape)
    x = jnp.where(dx, _normals(keys, x.shape[1:], x.dtype),
                  jnp.where(mx, x_new, x))
    mE = mask.reshape((1,) + mask.shape + (1,) * (E.ndim - 2))
    E = jnp.where(mE, 0.0, E)  # fresh rings -> warm-up from order 1
    if has_cache:
        # a reused slot must not inherit the previous request's deep
        # features; zeroed cache + the span's full init row reproduce the
        # uniform cached scan exactly (DESIGN.md §12)
        C = state[2]
        mC = mask.reshape(mask.shape + (1,) * (C.ndim - 1))
        state = (x, E, jnp.where(mC, 0.0, C))
    else:
        state = (x, E)
    meta = jnp.where(mask[None, :], meta_new, meta)
    if uses_cfg:
        g = jnp.where(mask, g_new, g)
    extras = {k: jnp.where(mask.reshape(mask.shape + (1,) * (v.ndim - 1)),
                           ex_new[k], v)
              for k, v in extras.items()}
    return state, meta, g, extras


@jax.jit
def _gather_rows(x, idx):
    """Fixed-width readback gather: `idx` is padded to B so the compiled
    shape is count-independent (one compile per (B, sample shape), ever)."""
    return x[idx]


@jax.jit
def _poison_slot(x, slot):
    """Overwrite one slot's latent with NaN — fault injection only
    (serving/faults.py); never on the clean path."""
    return x.at[slot].set(jnp.nan)


@jax.jit
def _bump_row(meta, slot, delta):
    """Corrupt one slot's on-device row counter — fault injection only."""
    return meta.at[0, slot].add(delta)


@dataclass
class Request:
    """One sampling request: a latent to generate under per-request knobs.

    seed draws the initial latent (or pass `x_T` explicitly); `cfg_scale`
    overrides the program's nominal guidance scale for this request only
    (cfg-enabled programs); `extras` are per-request model conditioning
    scalars (e.g. {"class_ids": 7}) scattered into the scheduler's per-slot
    extras state at admission — the scheduler must be constructed with a
    matching `extras_init`; `arrival` is the request's arrival time in tick
    units — the trace driver (`server.run_trace`) submits it once the clock
    reaches it. The NFE budget is the compiled grid's (n_rows evals, one per
    tick); per-request consumption is bookkept on the `Completion`.
    """

    rid: int
    seed: int = 0
    cfg_scale: Optional[float] = None
    arrival: float = 0.0
    x_T: Optional[object] = None
    extras: Optional[dict] = None
    # quality tier for plan-bank programs (`SamplerEngine.build_bank`):
    # selects which tuned plan's row span this request steps through. Must
    # name a tier of the program's bank; None on single-plan programs.
    tier: Optional[str] = None
    # admission deadline in tick-clock units past `arrival`: a request still
    # queued when its deadline passes is expired at admission time instead
    # of served late (None = the scheduler's ResilienceConfig.default_ttl,
    # itself None = no deadline). Already-admitted requests always run to
    # completion — the deadline bounds queue wait, not service.
    ttl: Optional[float] = None


@dataclass
class Completion:
    """A finished request with its latent and bookkeeping."""

    rid: int
    latent: np.ndarray
    arrival: float
    admit_tick: int
    finish_tick: int     # executed-step counter when this request finished
    finish_clock: float  # simulated clock time (== finish_tick unless the
                         # trace driver fast-forwarded over idle gaps)
    evals: int           # rows executed = model evals this request consumed
    tier: Optional[str] = None  # the plan-bank tier served (None: single plan)
    # evals-per-latent in FULL-eval units: == evals for uncached programs;
    # below it when the request's row span scheduled shallow feature-reuse
    # evals (StepProgram.span_cost, DESIGN.md §12)
    eval_cost: float = 0.0
    # resilience provenance (DESIGN.md §16): ok=False marks a latent that
    # failed the on-device finite check with the retry budget exhausted
    # (fail_reason says why); retries counts non-finite re-admissions,
    # requeues counts desync-recovery re-admissions; first_tier is the
    # originally requested tier when retry fallback or shed-degrade moved
    # the request off it (None when it was served as requested).
    ok: bool = True
    retries: int = 0
    requeues: int = 0
    first_tier: Optional[str] = None
    fail_reason: Optional[str] = None

    @property
    def latency_ticks(self) -> float:
        """Queue wait + service, in tick units (one tick = one batched eval),
        on the same clock `arrival` is on."""
        return self.finish_clock - self.arrival


@dataclass
class _Flight:
    """One dispatched-but-not-yet-consumed tick: the trailing-readback
    record. `mask` is the device done mask, `lat` the one batched gather of
    the finished slots' latents (both with async host copies already in
    flight); everything else is host metadata stamped at dispatch time, so
    latency metrics are correct no matter how late the flight is consumed."""

    tick: int
    clock: float
    mask: object = None                 # device (B,) bool done mask
    lat: object = None                  # device (B, *sample) gather, padded
                                        # to full width — rows [0, n_done)
                                        # are the finished slots in order
    slots: np.ndarray = field(default_factory=lambda: np.zeros(0, np.int64))
    reqs: List[Request] = field(default_factory=list)
    admits: np.ndarray = field(default_factory=lambda: np.zeros(0, np.int64))
    budgets: np.ndarray = field(default_factory=lambda: np.zeros(0, np.int64))
    offs: np.ndarray = field(default_factory=lambda: np.zeros(0, np.int64))


class SlotScheduler:
    """Fixed-B continuous batching over a compiled `StepProgram`.

    `pipeline_depth` is the number of ticks kept in flight (DESIGN.md §13):
    1 = the synchronous loop (every tick's readback is consumed before
    `tick()` returns), N >= 2 dispatches up to N ticks ahead and consumes
    readbacks N-1 ticks late. Admission bookkeeping is host-predicted (the
    solver grid is deterministic), so the admission schedule — and therefore
    every latent — is identical at every depth; the device done mask is
    verified against the prediction at consumption time.
    """

    def __init__(self, program: StepProgram, slots: int,
                 sample_shape: Tuple[int, ...], dtype=jnp.float32,
                 gang: bool = False, step_override=None,
                 extras_init: Optional[dict] = None,
                 pipeline_depth: int = 1,
                 registry: Optional[MetricsRegistry] = None,
                 tracer=None, probe=None,
                 resilience: Optional[ResilienceConfig] = None,
                 faults: Optional[FaultPlan] = None):
        if pipeline_depth < 1:
            raise ValueError(f"pipeline_depth must be >= 1, "
                             f"got {pipeline_depth}")
        self.program = program
        self.slots = slots
        self.sample_shape = tuple(sample_shape)
        self.dtype = dtype
        self.gang = gang
        self.pipeline_depth = int(pipeline_depth)
        self.state = program.init_state(slots, self.sample_shape, dtype)
        self.meta = program.init_meta(slots)
        self.g = program.init_g(slots)
        # per-slot model conditioning: one (slots, *shape) array per key —
        # a class id or caption length is a scalar a slot, a caption an
        # array of its own shape — seeded from extras_init and overwritten
        # at admission from Request.extras: conditioning is per-REQUEST,
        # never slot-positional. Explicit dtypes: AOT-compiled signatures
        # must not drift weak->strong
        def _col(v):
            v = np.asarray(v)
            dt = (jnp.int32 if np.issubdtype(v.dtype, np.integer)
                  else jnp.float32)
            return jnp.broadcast_to(jnp.asarray(v, dt), (slots,) + v.shape)

        self.extras = {k: _col(v) for k, v in (extras_init or {}).items()}
        self._extras_init = dict(extras_init or {})
        self.queue: Deque[Request] = deque()
        self.slot_req: List[Optional[Request]] = [None] * slots
        # host mirror of the on-device meta counters, all vectorized numpy:
        # needed for admission (which slots are free), completion prediction
        # (which flight a request's latent rides home on), and the Completion
        # metadata. The device counters stay authoritative for the compiled
        # program's idx; the done mask is cross-checked at consumption.
        self._busy = np.zeros(slots, bool)
        self.slot_row = np.zeros(slots, np.int64)    # next row (tier-relative)
        self.slot_admit = np.zeros(slots, np.int64)
        # plan-bank bookkeeping: each slot's row span in the stacked table.
        # Single-plan programs keep offset 0 / budget n_rows for every slot.
        self.slot_off = np.zeros(slots, np.int64)
        self.slot_budget = np.full(slots, program.n_rows, np.int64)
        self.ticks = 0           # batched step calls = batched model evals
        self.evals = 0           # always == ticks (the CI smoke invariant)
        self.active_slot_ticks = 0
        self.clock: Optional[float] = None  # trace driver's simulated time;
                                            # None -> clock follows ticks
        self.completions: List[Completion] = []
        self._inflight: Deque[_Flight] = deque()
        # resilience policy (DESIGN.md §16): the default config is inert —
        # unbounded queue, no TTL, no retries — so a scheduler built without
        # one behaves bit-identically to the pre-resilience loop until a
        # fault actually fires. `rejections` partitions submissions together
        # with `completions`; `events` is the deterministic resilience /
        # fault ledger (plain tuples, compared across chaos runs).
        self.resilience = validate_resilience(
            resilience if resilience is not None else DEFAULT_RESILIENCE,
            program)
        self.rejections: List[Rejection] = []
        self.events: List[tuple] = []
        self._injector = (FaultInjector(faults, ledger=self.events)
                          if faults else None)
        self._rstate: Dict[int, dict] = {}  # rid -> retry/requeue provenance
        self._recoveries = 0
        # quality-probe replays, timed apart so that they stay out of the
        # host_phase_ns split (below)
        self._probe_ns = 0
        # observability (DESIGN.md §15): the registry is always on — it is
        # the one accounting substrate ServeMetrics is derived from — while
        # the tracer and quality probe are opt-in (None = zero work: every
        # call site is `if self.tracer is not None`-guarded).
        self.registry = registry if registry is not None else MetricsRegistry()
        self.tracer = tracer
        self.probe = probe
        if probe is not None:
            if probe.registry is None:
                probe.registry = self.registry
            if probe.tracer is None:
                probe.tracer = tracer
        r = self.registry
        self._m_ticks = r.counter(
            "serve_ticks", help="executed batched step calls")
        self._m_evals = r.counter(
            "serve_evals", help="batched model evals (== serve_ticks)")
        self._m_active = r.counter(
            "serve_active_slot_ticks", help="busy-slot ticks")
        self._m_submitted = r.counter(
            "serve_submitted", help="requests submitted")
        self._m_admitted = r.counter(
            "serve_admitted", help="requests admitted into slots")
        self._m_completed = r.counter(
            "serve_completed", help="requests completed")
        self._m_queue = r.histogram(
            "queue_depth", QUEUE_DEPTH_BUCKETS,
            help="queued requests per executed tick (post-admission)")
        self._m_busy = r.histogram(
            "busy_slots", BUSY_SLOT_BUCKETS,
            help="busy slots per executed tick")
        self._m_occ = r.histogram(
            "occupancy_frac", OCCUPANCY_BUCKETS,
            help="busy-slot fraction per executed tick")
        self._m_latency = r.histogram(
            "latency_ticks", LATENCY_TICK_BUCKETS,
            help="request latency (queue wait + service) in ticks")
        self._m_cost = r.histogram(
            "request_eval_cost", EVAL_COST_BUCKETS,
            help="evals-per-latent (full-eval units) per completion")
        # host time of tick() by phase (DESIGN.md §15.3): admission = the
        # _admit() call, dispatch = the step call itself (inline device
        # execution on runtimes without async dispatch), readback = time
        # blocked in the readbacks tick() consumes, bookkeeping = the rest
        # of tick(), so the four tile it
        self._m_phase = {p: r.counter("host_phase_ns", {"phase": p},
                                      wall=True,
                                      help="host ns per tick phase")
                         for p in HOST_PHASES}
        # every blocking device->host read, counted where it happens (in
        # tick(), flush() or drain() alike)
        self._m_syncs = {s: r.counter("host_syncs", {"site": s},
                                      help="blocking device->host reads")
                         for s in SYNC_SITES}
        self._m_blocked = {s: r.counter("host_blocked_ns", {"site": s},
                                        wall=True,
                                        help="host ns blocked in device->host "
                                             "reads")
                           for s in SYNC_SITES}
        self._m_extras_bytes = r.counter(
            "serve_extras_bytes",
            help="bytes of per-request conditioning (extras) the admission "
                 "apply puts on the device")
        self._m_device_draws = r.counter(
            "serve_device_draws",
            help="admitted requests whose x_T the admission apply drew "
                 "on the device from the seed")
        # the dispatched program takes the engine's eps bundle (the weights)
        # as its first argument, so they are never compiled in as constants.
        # step_override replaces the dispatched flight step — signature
        # step(state, meta, g, extras) -> (state, meta, done), and the done
        # mask must be consistent with the meta counters (it is verified
        # against the host prediction whenever a completion is consumed)
        self._nets = program.nets
        self._flight = (program.flight if step_override is None
                        else lambda _nets, *args: step_override(*args))
        self._np_dtype = np.dtype(dtype)
        self._x_zero = jnp.zeros((slots,) + self.sample_shape, dtype)
        self._extras_np = {k: (v.shape[1:], np.dtype(v.dtype))
                           for k, v in self.extras.items()}

    # -- queue / slots -------------------------------------------------------
    def _count_event(self, name: str, labels: Optional[dict] = None,
                     n: int = 1) -> None:
        """Bump a lazily-registered resilience/fault counter."""
        self.registry.counter(name, labels,
                              help=EVENT_COUNTER_HELP[name]).inc(n)

    def submit(self, req: Request) -> Optional[Rejection]:
        """Queue a request, or shed it under overload control.

        Returns None when the request was accepted, or the typed
        `Rejection` handed back to the traffic source when the bounded
        queue shed it (also appended to `self.rejections`). Malformed
        requests — bad tier tag, unknown extras, guidance on an unguided
        program — still raise: those are programmer errors, not load."""
        with phase("submit", self.tracer, rid=req.rid):
            return self._submit(req)

    def _submit(self, req: Request) -> Optional[Rejection]:
        if (req.cfg_scale is not None and float(req.cfg_scale) != 0.0
                and not self.program.uses_cfg):
            raise ValueError(
                f"request rid={req.rid} carries cfg_scale={req.cfg_scale} "
                f"but the step program was compiled without guidance; "
                f"build the engine spec with cfg_scale != 0")
        unknown = set(req.extras or {}) - set(self.extras)
        if unknown:
            raise ValueError(
                f"request rid={req.rid} carries extras {sorted(unknown)} the "
                f"scheduler was not constructed for; pass extras_init with "
                f"matching keys")
        self.program.resolve_tier(req.tier)  # reject bad tier tags at submit
        self._m_submitted.inc()
        cfg = self.resilience
        if (cfg.max_queue is not None
                and len(self.queue) >= cfg.max_queue):
            return self._reject(req, REJECT_QUEUE_FULL)
        if (cfg.shed_policy == "degrade"
                and cfg.degrade_watermark is not None
                and len(self.queue) >= cfg.degrade_watermark
                and req.tier != cfg.degrade_tier):
            # shed by degrading instead of dropping: past the watermark new
            # requests are remapped to the cheap tier, recording provenance
            self._rprov(req.rid)["first_tier"] = req.tier
            req = dc_replace(req, tier=cfg.degrade_tier)
            self.events.append(("shed_degrade", req.arrival, req.rid))
            self._count_event("serve_shed_degraded")
        self.queue.append(req)
        if self.tracer is not None:
            self.tracer.async_begin("request", req.rid,
                                    args={"tier": req.tier,
                                          "arrival": req.arrival})
        return None

    def _rprov(self, rid: int) -> dict:
        """This rid's resilience provenance record (created on first use;
        stamped onto its Completion and dropped at emission)."""
        return self._rstate.setdefault(
            rid, {"retries": 0, "requeues": 0, "first_tier": None})

    def _reject(self, req: Request, reason: str,
                clock: Optional[float] = None) -> Rejection:
        rej = Rejection(rid=req.rid, reason=reason, arrival=req.arrival,
                        clock=req.arrival if clock is None else clock,
                        tier=req.tier)
        self.rejections.append(rej)
        self.events.append(("reject", rej.clock, req.rid, reason))
        self._rstate.pop(req.rid, None)
        self._count_event("serve_rejected", {"reason": reason})
        if self.tracer is not None:
            if reason == REJECT_EXPIRED:
                # the lifecycle span opened at submit: close it as expired
                self.tracer.async_end("request", req.rid,
                                      args={"rejected": reason,
                                            "tier": req.tier})
            else:
                # queue_full sheds before the span opens: a lone instant
                self.tracer.instant("reject", cat="request",
                                    args={"rid": req.rid, "reason": reason})
        return rej

    @property
    def active(self) -> int:
        return int(self._busy.sum())

    @property
    def in_flight(self) -> int:
        """Dispatched ticks whose readback has not been consumed yet."""
        return len(self._inflight)

    @property
    def occupancy(self) -> float:
        """Mean fraction of slots doing useful work per tick."""
        return (self.active_slot_ticks / (self.ticks * self.slots)
                if self.ticks else 0.0)

    def _synced(self, site: str, t0_ns: int) -> None:
        """Count one blocking device->host read at `site` that began at
        `t0_ns` (perf_counter_ns)."""
        self._m_syncs[site].inc()
        self._m_blocked[site].inc(time.perf_counter_ns() - t0_ns)

    def _draw(self, req: Request) -> np.ndarray:
        """The request's initial latent as host numpy, for the quality
        probe's replay (serving draws it inside the admission apply). A
        seed's latent is drawn on the device from the same key words
        `_apply_admission` uses and read back: a counted blocking read. A
        given x_T is host data."""
        if req.x_T is not None:
            return np.asarray(req.x_T, self._np_dtype)
        x = jax.random.normal(
            jax.random.wrap_key_data(jnp.asarray(key_words(req.seed))),
            self.sample_shape, self.dtype)
        t0 = time.perf_counter_ns()
        x = np.asarray(x)
        self._synced("draw", t0)
        return x

    def _expired(self, req: Request, admit_now: float) -> bool:
        """Deadline check at admission time (DESIGN.md §16): a queued
        request whose TTL elapsed before a slot freed is expired, never
        served late. Admitted requests are exempt by construction — this
        is only consulted on the queue->slot edge."""
        ttl = req.ttl if req.ttl is not None else self.resilience.default_ttl
        return ttl is not None and admit_now - req.arrival > ttl

    def _admit(self) -> None:
        if self.gang and self._busy.any():
            return  # sequential full-batch baseline: drain before refilling
        if not self.queue:
            return
        free = np.flatnonzero(~self._busy)
        if free.size == 0:
            return
        # the admission clock: the simulated time this tick's admissions
        # happen at (the trace driver advances `clock` to now+1 pre-tick).
        # A skew fault shifts it — the chaos stand-in for a stalled host.
        admit_now = (float(self.ticks) if self.clock is None
                     else self.clock - 1.0)
        if self._injector is not None:
            skew = self._injector.take_skew(self.ticks + 1)
            if skew:
                admit_now += skew
                self.events.append(("fault_skew", self.ticks + 1, skew))
                self._count_event("fault_injected", {"kind": "skew"})
        reqs: List[Request] = []
        while self.queue and len(reqs) < free.size:
            r = self.queue.popleft()
            if self._expired(r, admit_now):
                self._reject(r, REJECT_EXPIRED, clock=admit_now)
                continue
            reqs.append(r)
        n = len(reqs)
        if n == 0:
            return
        taken = free[:n]
        offs = np.empty(n, np.int64)
        budgets = np.empty(n, np.int64)
        for j, r in enumerate(reqs):
            offs[j], budgets[j] = self.program.resolve_tier(r.tier)
            self.slot_req[int(taken[j])] = r
        # vectorized host bookkeeping: one fancy-indexed write per array
        self._busy[taken] = True
        self.slot_row[taken] = 0
        self.slot_off[taken] = offs
        self.slot_budget[taken] = budgets
        self.slot_admit[taken] = self.ticks
        self._m_admitted.inc(n)
        if self.tracer is not None:
            # the admit instant opens the request's step segment: rows
            # [offset, offset + budget) execute over the next `budget` ticks
            for j, r in enumerate(reqs):
                self.tracer.async_instant(
                    "admit", r.rid,
                    args={"slot": int(taken[j]), "tick": self.ticks,
                          "offset": int(offs[j]), "budget": int(budgets[j]),
                          "tier": r.tier})
        # full-width masked update buffers, built host-side in numpy; the
        # jitted apply draws the seed requests' latents and folds latents +
        # meta counters + guidance + extras into the device state in ONE
        # fixed-shape dispatch per tick. A seed crosses from the host as its
        # key words, and only a request's own x_T as a latent: a tick with
        # none reuses a resident zero buffer, not a fresh full-width upload
        B = self.slots
        mask = np.zeros(B, bool)
        mask[taken] = True
        keys = np.zeros((B, 2), np.uint32)
        drawn = np.zeros(B, bool)
        x_new = None
        for s, r in zip(taken, reqs):
            if r.x_T is None:
                keys[s] = key_words(r.seed)
                drawn[s] = True
            else:
                if x_new is None:
                    x_new = np.zeros((B,) + self.sample_shape,
                                     self._np_dtype)
                x_new[s] = np.asarray(r.x_T, self._np_dtype)
        # placed like the resident buffer, so both hit one executable
        x_new = self._x_zero if x_new is None else jax.device_put(x_new)
        self._m_device_draws.inc(int(drawn.sum()))
        # on-device counters: row 0, the tier's span, busy
        meta_new = np.zeros((4, B), np.int32)
        meta_new[1, taken] = offs
        meta_new[2, taken] = budgets
        meta_new[3, taken] = 1
        g_new = np.zeros(B, np.float32)
        if self.program.uses_cfg:
            g_new[taken] = [float(r.cfg_scale) if r.cfg_scale is not None
                            else float(self.program.spec.cfg_scale or 0.0)
                            for r in reqs]
        ex_new = {k: np.zeros((B,) + shape, dt)
                  for k, (shape, dt) in self._extras_np.items()}
        for k in ex_new:
            ex_new[k][taken] = [(r.extras or {}).get(k, self._extras_init[k])
                                for r in reqs]
        self._m_extras_bytes.inc(sum(a.nbytes for a in ex_new.values()))
        with phase("admit_apply", self.tracer):
            self.state, self.meta, self.g, self.extras = _apply_admission(
                tuple(self.state), self.meta, self.g, self.extras,
                mask, drawn, keys, x_new, meta_new, g_new, ex_new,
                has_cache=self.program.cache is not None,
                uses_cfg=self.program.uses_cfg)

    # -- the serving step ----------------------------------------------------
    def tick(self) -> List[Completion]:
        """Admit, dispatch ONE batched step, consume due readbacks.

        At pipeline_depth=1 the returned completions are this tick's; at
        depth N they are the completions of the tick dispatched N-1 ticks
        ago (its readback has had N-1 device ticks to land). The call is
        one `serve.tick` span, numbered with the tick it steps."""
        with phase("tick", self.tracer, step=self.ticks + 1) as tk:
            return self._tick(tk)

    def _tick(self, tk: phase) -> List[Completion]:
        tr = self.tracer
        p0 = self._probe_ns
        rb0 = self._m_blocked["readback"].value
        with phase("admission", tr) as adm:
            self._admit()
        adm_ns = adm.t1 - adm.t0
        busy = self._busy
        if not busy.any():
            self._m_phase["admission"].inc(adm_ns)
            self._m_phase["bookkeeping"].inc(time.perf_counter_ns() - adm.t1)
            return []
        self.ticks += 1
        self.evals += 1
        n_busy = int(busy.sum())
        self.active_slot_ticks += n_busy
        self._m_ticks.inc()
        self._m_evals.inc()
        self._m_active.inc(n_busy)
        self._m_queue.observe(len(self.queue))
        self._m_busy.observe(n_busy)
        self._m_occ.observe(n_busy / self.slots)
        if self._injector is not None:
            self._inject()
        # dispatch: idx construction and row advance happen on device
        # (StepProgram.step_flight); nothing tick-varying crosses the host
        # boundary here. Timed separately — the call is device time (inline
        # execution on runtimes without async dispatch), not bookkeeping.
        with phase("dispatch", tr) as dsp:
            self.state, self.meta, mask = self._flight(
                self._nets, self.state, self.meta, *self._step_tail())
        flight = _Flight(
            tick=self.ticks,
            clock=(float(self.ticks) if self.clock is None else self.clock))
        # host prediction of this tick's completions (the grid is
        # deterministic): vectorized row advance + budget compare
        self.slot_row[busy] += 1
        done_mask = busy & (self.slot_row >= self.slot_budget)
        if done_mask.any():
            slots_done = np.flatnonzero(done_mask)
            flight.mask = mask
            flight.slots = slots_done
            flight.reqs = [self.slot_req[int(s)] for s in slots_done]
            flight.admits = self.slot_admit[slots_done].copy()
            flight.budgets = self.slot_budget[slots_done].copy()
            flight.offs = self.slot_off[slots_done].copy()
            # the trailing readback stream: ONE batched gather of the
            # finished slots' latents, host copy started immediately; the
            # concrete values are consumed up to depth-1 ticks later. The
            # gather is dispatched before the next tick's donated step, so
            # it reads this tick's output before the buffers are recycled.
            # Indices are padded to full width so the compiled gather shape
            # is count-independent; rows past n_done are discarded.
            idx = np.full(self.slots, slots_done[-1], np.int32)
            idx[:slots_done.size] = slots_done
            lat = _gather_rows(self.state[0], idx)
            lat.copy_to_host_async()
            mask.copy_to_host_async()
            flight.lat = lat
            # free the slots now (host prediction): the next dispatch may
            # re-admit into them without draining the pipeline
            for s in slots_done:
                self.slot_req[int(s)] = None
            self._busy[done_mask] = False
            self.slot_row[done_mask] = 0
            self.slot_off[done_mask] = 0
        self._inflight.append(flight)
        done: List[Completion] = []
        while len(self._inflight) > self.pipeline_depth - 1:
            done.extend(self._consume(self._inflight.popleft()))
        t1 = time.perf_counter_ns()
        dsp_ns = dsp.t1 - dsp.t0
        rb_ns = self._m_blocked["readback"].value - rb0
        book_ns = (t1 - tk.t0 - adm_ns - dsp_ns - rb_ns
                   - (self._probe_ns - p0))
        self._m_phase["admission"].inc(adm_ns)
        self._m_phase["dispatch"].inc(dsp_ns)
        self._m_phase["readback"].inc(rb_ns)
        self._m_phase["bookkeeping"].inc(book_ns)
        if tr is not None:
            tk.args.update(tick=self.ticks, busy=n_busy,
                           queue=len(self.queue), emitted=len(done))
            tr.counter("slots", {"busy": n_busy, "queue": len(self.queue)},
                       ts_ns=tk.t0)
        return done

    def _inject(self) -> None:
        """Fire the armed faults due this tick (serving/faults.py), after
        admission and before dispatch, directly on device state — the
        compiled step program itself is never altered, so chaos tests
        exercise the real serving path. `self.ticks` already names the tick
        about to dispatch; `slot_row` still holds the row about to run."""
        inj = self._injector
        for s in np.flatnonzero(self._busy):
            req = self.slot_req[int(s)]
            fault = inj.take_nan(req.rid, int(self.slot_row[s]))
            if fault is not None:
                x = _poison_slot(self.state[0], jnp.int32(int(s)))
                self.state = (x,) + tuple(self.state[1:])
                self.events.append(("fault_nan", self.ticks, req.rid,
                                    int(self.slot_row[s])))
                self._count_event("fault_injected", {"kind": "nan"})
                if self.tracer is not None:
                    self.tracer.async_instant(
                        "fault_nan", req.rid,
                        args={"tick": self.ticks,
                              "step": int(self.slot_row[s])})
        mf = inj.take_meta(self.ticks)
        if mf is not None:
            slot = mf.slot
            if slot is None:
                busy = np.flatnonzero(self._busy)
                slot = int(busy[0]) if busy.size else None
            if slot is not None:
                self.meta = _bump_row(self.meta, jnp.int32(slot),
                                      jnp.int32(mf.delta))
                self.events.append(("fault_meta", self.ticks, slot,
                                    mf.delta))
                self._count_event("fault_injected", {"kind": "meta"})
                if self.tracer is not None:
                    self.tracer.instant("fault_meta", cat="tick",
                                        args={"tick": self.ticks,
                                              "slot": slot,
                                              "delta": mf.delta})

    def _consume(self, f: _Flight) -> List[Completion]:
        """Materialize one flight's readback: verify the on-device done mask
        against the host prediction and emit the finished latents."""
        if not f.slots.size:
            return []
        with phase("readback", self.tracer):
            t0 = time.perf_counter_ns()
            mask_np = np.asarray(f.mask)   # blocks until the tick executed
            lat_np = np.asarray(f.lat)     # ONE batched device_get per tick
            self._synced("readback", t0)
        got = np.flatnonzero(mask_np)
        if not np.array_equal(got, f.slots):
            if self.resilience.recovery == "raise":
                raise RuntimeError(
                    f"on-device done mask {got.tolist()} disagrees with the "
                    f"host completion prediction {f.slots.tolist()} at tick "
                    f"{f.tick} — scheduler bookkeeping desynchronized from "
                    f"the compiled step program")
            return self._recover(f, got)
        with phase("emit", self.tracer):
            emitted = self._emit(f, mask_np, lat_np)
        if self.probe is not None:
            # replay a sampled fraction against the high-NFE reference; the
            # replay is device work, not scheduler bookkeeping — timed apart
            # so it never pollutes the per-phase host accounting. Failed
            # completions are never probed (their latent is non-finite).
            pp0 = time.perf_counter_ns()
            for req, c in emitted:
                if c.ok and self.probe.selected(c.rid):
                    self.probe.observe(req, c, self._draw(req))
            self._probe_ns += time.perf_counter_ns() - pp0
        return [c for _, c in emitted]

    def _emit(self, f: _Flight, mask_np: np.ndarray,
              lat_np: np.ndarray) -> List[Tuple[Request, Completion]]:
        """Turn a verified flight's readback into completions (or
        retries); returns (request, completion) pairs."""
        # on-device output validation (DESIGN.md §16): the done mask is
        # coded, and DONE_NONFINITE marks a finished slot whose latent
        # failed the finite check inside the compiled step. Those requests
        # re-admit on the fallback chain while retry budget remains; only
        # exhaustion emits a (marked-failed) completion.
        bad = mask_np[f.slots] == DONE_NONFINITE
        cfg = self.resilience
        emitted: List[Tuple[Request, Completion]] = []
        for j, req in enumerate(f.reqs):
            if bad[j]:
                prov = self._rprov(req.rid)
                if prov["retries"] < cfg.max_retries:
                    self._retry(req, f, prov)
                    continue
            prov = self._rstate.pop(req.rid, None) or {}
            c = Completion(
                rid=req.rid, latent=lat_np[j], arrival=req.arrival,
                admit_tick=int(f.admits[j]), finish_tick=f.tick,
                finish_clock=f.clock, evals=int(f.budgets[j]),
                tier=req.tier,
                eval_cost=self.program.span_cost(int(f.offs[j]),
                                                 int(f.budgets[j])),
                ok=not bool(bad[j]),
                retries=int(prov.get("retries", 0)),
                requeues=int(prov.get("requeues", 0)),
                first_tier=prov.get("first_tier"),
                fail_reason=FAIL_NONFINITE if bad[j] else None)
            if not c.ok:
                self.events.append(("failed", f.tick, c.rid))
                self._count_event("serve_failed")
            emitted.append((req, c))
        done = [c for _, c in emitted]
        self.completions.extend(done)
        reg = self.registry
        for c in done:
            self._m_completed.inc()
            self._m_latency.observe(c.latency_ticks)
            self._m_cost.observe(c.eval_cost)
            if c.tier is not None:
                lbl = {"tier": c.tier}
                reg.counter("tier_completed", lbl,
                            help="completions per quality tier").inc()
                reg.gauge("tier_evals", lbl,
                          help="evals per request of this tier").set(c.evals)
                reg.gauge("tier_eval_cost", lbl,
                          help="evals-per-latent (full-eval units) of this "
                               "tier").set(c.eval_cost)
                reg.histogram("tier_latency_ticks", LATENCY_TICK_BUCKETS,
                              lbl, help="per-tier request latency in "
                                        "ticks").observe(c.latency_ticks)
        if self.tracer is not None:
            for c in done:
                args = {"tier": c.tier, "evals": c.evals,
                        "eval_cost": c.eval_cost,
                        "latency_ticks": c.latency_ticks,
                        "admit_tick": c.admit_tick,
                        "finish_tick": c.finish_tick}
                if not c.ok or c.retries or c.requeues:
                    args.update(ok=c.ok, retries=c.retries,
                                requeues=c.requeues,
                                fail_reason=c.fail_reason)
                self.tracer.async_end("request", c.rid, args=args)
        return emitted

    def _retry(self, req: Request, f: _Flight, prov: dict) -> None:
        """Re-admit a request whose finished latent failed validation:
        seed and x_T preserved (the retry re-draws the identical initial
        latent), tier advanced along the fallback chain, and the request
        put at the queue FRONT — it has waited longest. Bookkept as a
        re-admission, not a new submission."""
        nxt = fallback_tier(self.resilience, req.tier)
        if nxt != req.tier and prov["first_tier"] is None:
            prov["first_tier"] = req.tier
        prov["retries"] += 1
        self.events.append(("retry", f.tick, req.rid, req.tier, nxt))
        self._count_event("serve_retries")
        if self.tracer is not None:
            self.tracer.async_instant(
                "retry", req.rid,
                args={"tick": f.tick, "from": req.tier, "to": nxt,
                      "attempt": prov["retries"]})
        self.queue.appendleft(req if nxt == req.tier
                              else dc_replace(req, tier=nxt))

    def _recover(self, f: _Flight, got: np.ndarray) -> List[Completion]:
        """Desync recovery (DESIGN.md §16): the device done mask disagreed
        with the host's predicted completion schedule. Drain the pipeline
        (every in-flight readback is suspect), re-derive the host slot
        mirrors from the authoritative device `meta` counters — slots whose
        host and device bookkeeping still agree keep running untouched —
        and requeue every affected request to re-serve from scratch (seed
        preserved, so a recovered request's latent still reproduces the
        clean run). Returns no completions; the requeued work re-emits
        through the normal path."""
        with phase("recover", self.tracer, tick=f.tick):
            return self._resync(f, got)

    def _resync(self, f: _Flight, got: np.ndarray) -> List[Completion]:
        self._recoveries += 1
        if self._recoveries > self.resilience.max_recoveries:
            raise RuntimeError(
                f"desync recovery limit ({self.resilience.max_recoveries}) "
                f"exhausted: on-device done mask {got.tolist()} still "
                f"disagrees with the host completion prediction "
                f"{f.slots.tolist()} at tick {f.tick} — the step program "
                f"and scheduler bookkeeping cannot re-synchronize")
        affected: List[Request] = list(f.reqs)
        while self._inflight:
            affected.extend(self._inflight.popleft().reqs)
        t0 = time.perf_counter_ns()
        meta_dev = np.asarray(self.meta)  # authoritative device counters
        self._synced("recover", t0)
        nr = self.program.n_rows
        for s in range(self.slots):
            host_busy = bool(self._busy[s])
            dev_busy = bool(meta_dev[3, s])
            if not host_busy and not dev_busy:
                continue
            if (host_busy and dev_busy
                    and int(meta_dev[0, s]) == int(self.slot_row[s])
                    and int(meta_dev[1, s]) == int(self.slot_off[s])
                    and int(meta_dev[2, s]) == int(self.slot_budget[s])):
                continue  # mirrors agree: the slot keeps running
            req = self.slot_req[s]
            if req is not None:
                affected.append(req)
            self.slot_req[s] = None
            self._busy[s] = False
            self.slot_row[s] = 0
            self.slot_off[s] = 0
            self.slot_budget[s] = nr
            meta_dev[:, s] = (0, 0, nr, 0)
        self.meta = jnp.asarray(meta_dev)
        # requeue at the queue front in original arrival order: recovered
        # requests were in service before anything still queued
        affected.sort(key=lambda r: (r.arrival, r.rid))
        for r in reversed(affected):
            self._rprov(r.rid)["requeues"] += 1
            self.queue.appendleft(r)
        self.events.append(("desync", f.tick,
                            tuple(r.rid for r in affected)))
        self._count_event("serve_desync_recoveries")
        if affected:
            self._count_event("serve_requeued", n=len(affected))
        if self.tracer is not None:
            self.tracer.instant(
                "desync_recover", cat="tick",
                args={"tick": f.tick, "got": got.tolist(),
                      "predicted": f.slots.tolist(),
                      "requeued": [r.rid for r in affected]})
            for r in affected:
                self.tracer.async_instant("requeue", r.rid,
                                          args={"tick": f.tick})
        return []

    def flush(self) -> List[Completion]:
        """Consume every in-flight readback (blocking). A no-op at
        pipeline_depth=1; the async trace driver calls it once the arrival
        stream is exhausted. May leave work REQUEUED (a consumed readback
        can trigger a retry or a desync recovery) — drivers must re-check
        `queue`/`active` after flushing, as `drain` and `run_trace` do."""
        done: List[Completion] = []
        while self._inflight:
            done.extend(self._consume(self._inflight.popleft()))
        return done

    def drain(self) -> List[Completion]:
        """Tick until every queued and in-flight request has finished —
        including requests the resilience layer requeued mid-drain."""
        out: List[Completion] = []
        while True:
            while self.queue or self.active:
                out.extend(self.tick())
            out.extend(self.flush())
            if not (self.queue or self.active):
                return out

    def _step_tail(self):
        """Trailing step args after (state, meta) — identical for every tick
        and for the AOT lowering, so compiled signatures always match."""
        return (self.g if self.program.uses_cfg else None,
                self.extras if self.extras else None)

    # -- AOT compile (DESIGN.md §9; the serve-timing fix) --------------------
    def aot_compile(self) -> float:
        """Lower + compile the flight step ahead of time and swap the
        compiled executable in; returns the compile seconds. Keeps the first
        tick's timing honest — compile is no longer folded into execution."""
        t0 = time.perf_counter()
        compiled = self._flight.lower(self._nets, self.state, self.meta,
                                      *self._step_tail()).compile()
        dt = time.perf_counter() - t0
        self._flight = compiled
        return dt

    def compiled_text(self) -> str:
        """HLO text of the AOT-compiled flight step (after `aot_compile`)."""
        return self._flight.as_text()
