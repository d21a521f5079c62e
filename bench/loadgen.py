"""The wall-clock load generator: submits requests when they are due, ticks the
scheduler, and stamps every request from its due time to the moment its
latent is handed back to the host.

It drives `SlotScheduler.submit` / `tick` / `flush` directly, one thread,
one process. With tracing on, each call into the scheduler and each sleep
sits in a `jax.profiler.TraceAnnotation`, so the device's idle gaps can be
put down to what the host was doing.
"""

from __future__ import annotations

import contextlib
import time
from dataclasses import dataclass, field

import jax
import numpy as np

from bench import system
from bench.traffic import generate

clock = time.perf_counter

# one lowering per executable built or loaded from the persistent cache
_LOWERING = "/jax/core/compile/jaxpr_to_mlir_module_duration"
_compiles = {"on": False, "n": 0}


def _on_duration(event: str, duration: float, **kw) -> None:
    if _compiles["on"] and event == _LOWERING:
        _compiles["n"] += 1


jax.monitoring.register_event_duration_secs_listener(_on_duration)


@dataclass
class Window:
    """What one measured window saw. Times are host `perf_counter` seconds."""
    t0: float = 0.0
    t1: float = 0.0              # end of the window (open loop: of the drain)
    ticks: int = 0               # ticks dispatched inside the window
    due: dict = field(default_factory=dict)       # rid -> due time
    submit: dict = field(default_factory=dict)    # rid -> submit time
    admit: dict = field(default_factory=dict)     # rid -> admitting tick's time
    done: dict = field(default_factory=dict)      # rid -> delivery time
    ok: dict = field(default_factory=dict)        # rid -> completion ok
    counters0: dict = field(default_factory=dict)
    counters1: dict = field(default_factory=dict)
    compiles: int = 0
    sample: list = field(default_factory=list)    # [(Spec, latent)]
    slot: dict = field(default_factory=dict)      # rid -> slot that served it
    closed_loop: bool = True
    undelivered: int = 0         # open loop: due in the window, never came


class LoadGen:
    def __init__(self, served, traffic: dict, config: dict, seed: int,
                 per_slot: int, annotate: bool = False):
        self.sched = served.sched
        self.traffic = traffic
        self.config = config
        self.requests = generate.Requests(config, seed)
        self.pending = {}                          # rid -> Spec, in flight
        self.rng = np.random.default_rng(np.random.SeedSequence([seed, 3]))
        self.k = int(per_slot)
        self.annotate = annotate
        self.slots = int(config["serving"]["slots"])
        self.rec: Window | None = None
        self._seen: dict = {}        # slot -> requests it delivered, window
        self._kept: dict = {}        # slot -> [(Spec, latent)], its sample
        self._slot: dict = {}        # rid -> slot, requests in flight
        self._stamp: dict = {}       # tick number -> time of its tick() call
        self._due: dict = {}         # rid -> due time, every request
        self._submitted: dict = {}   # rid -> submit time, every request

    # -- plumbing ------------------------------------------------------------
    def span(self, name: str):
        return (jax.profiler.TraceAnnotation(name) if self.annotate
                else contextlib.nullcontext())

    def _submit(self, spec, due: float) -> None:
        self.pending[spec.rid] = spec
        with self.span("bench.submit"):
            rej = self.sched.submit(system.request(self.config, spec))
        if rej is not None:
            raise RuntimeError(f"request {spec.rid} refused: {rej}")
        self._due[spec.rid] = due
        self._submitted[spec.rid] = clock()
        if self.rec is not None:
            self.rec.due[spec.rid] = due

    def _deliver(self, done: list, t: float) -> None:
        rec = self.rec
        for c in done:
            spec = self.pending.pop(c.rid)
            due = self._due.pop(c.rid)
            sub = self._submitted.pop(c.rid)
            slot = self._slot.pop(c.rid, -1)
            if rec is None or (not rec.closed_loop and c.rid not in rec.due):
                continue
            rec.due[c.rid] = due
            rec.submit[c.rid] = sub
            rec.done[c.rid] = t
            rec.ok[c.rid] = bool(c.ok)
            rec.admit[c.rid] = self._stamp.get(c.admit_tick, np.nan)
            # a reservoir sample, drawn from the seed, of each slot's
            # delivered requests: every slot is checked
            rec.slot[c.rid] = slot
            seen = self._seen[slot] = self._seen.get(slot, 0) + 1
            kept = self._kept.setdefault(slot, [])
            if len(kept) < self.k:
                kept.append((spec, np.asarray(c.latent)))
            else:
                j = int(self.rng.integers(0, seen))
                if j < self.k:
                    kept[j] = (spec, np.asarray(c.latent))

    def _tick(self) -> None:
        before = self.sched.ticks
        t = clock()
        with self.span("bench.tick"):
            done = self.sched.tick()
        if self.sched.ticks > before:
            # requests admitted in this call carry admit_tick == before
            self._stamp[before] = t
        for s, r in enumerate(self.sched.slot_req):
            if r is not None and r.rid not in self._slot:
                self._slot[r.rid] = s
        self._deliver(done, clock())

    def _flush(self) -> None:
        with self.span("bench.flush"):
            done = self.sched.flush()
        self._deliver(done, clock())

    def _sleep_until(self, t_wake: float) -> None:
        with self.span("bench.idle"):
            dt = t_wake - clock()
            if dt > 1e-3:
                time.sleep(dt - 5e-4)
            while clock() < t_wake:
                pass

    # -- phases --------------------------------------------------------------
    def warm_up(self, ticks: int, drain: bool) -> None:
        """Run the cell's own shapes through every call the window makes:
        admission (latent draw, the fixed-shape admission apply), the step,
        the readback gather and its copy. A backlog cell keeps going into
        its window from here; an open-loop cell drains first, so its
        window starts idle, and also passes through flush and the idle
        path.

        The slots fill over the first n_rows ticks, slots / n_rows of them
        a tick, so that a full backlog admits and delivers a few requests
        every tick, as a server that has run for a while does, and not all
        of them on one tick in n_rows."""
        n_rows = self.sched.program.n_rows
        sent = 0
        for t in range(ticks):
            want = min(self.slots, -(-(t + 1) * self.slots // n_rows))
            while sent < want or len(self.sched.queue) < (
                    self.slots if t >= n_rows else 0):
                self._submit(self.requests.take()[0], clock())
                sent += 1
            self._tick()
        if drain:
            while self.sched.queue or self.sched.active:
                self._tick()
            self._flush()
            self._flush()
            self._sleep_until(clock() + 0.01)
        jax.block_until_ready(self.sched.state)

    def _open_record(self, closed: bool) -> Window:
        self.rec = Window(closed_loop=closed,
                          counters0=system.counters(self.sched))
        self._seen, self._kept = {}, {}
        self._ticks0 = self.sched.ticks
        _compiles.update(on=True, n=0)
        return self.rec

    def _close_record(self) -> Window:
        rec = self.rec
        _compiles["on"] = False
        rec.compiles = _compiles["n"]
        rec.ticks = self.sched.ticks - self._ticks0
        rec.counters1 = system.counters(self.sched)
        rec.sample = [x for s in sorted(self._kept) for x in self._kept[s]]
        self.rec = None
        return rec

    def backlog(self, seconds: float) -> Window:
        """Closed backlog: the queue never falls below its depth. The
        window counts what is delivered inside it."""
        depth = generate.backlog_depth(self.traffic, self.slots)
        rec = self._open_record(closed=True)
        with self.span("bench.window"):
            rec.t0 = clock()
            end = rec.t0 + seconds
            while True:
                now = clock()
                if now >= end:
                    break
                while len(self.sched.queue) < depth:
                    self._submit(self.requests.take()[0], now)
                self._tick()
            rec.t1 = clock()
        return self._close_record()

    def open_loop(self, seconds: float, due: np.ndarray,
                  grace: float = 60.0) -> Window:
        """Open loop: request k is submitted once the clock reaches its due
        time, whatever the server is doing. After the last arrival the
        generator serves on until every request due in the window has been
        delivered, or `grace` seconds past the window's end."""
        specs = self.requests.take(len(due))
        rec = self._open_record(closed=False)
        i, n = 0, len(due)
        with self.span("bench.window"):
            rec.t0 = clock()
            due_abs = rec.t0 + np.asarray(due, np.float64)
            give_up = rec.t0 + seconds + grace
            while True:
                now = clock()
                while i < n and due_abs[i] <= now:
                    self._submit(specs[i], float(due_abs[i]))
                    i += 1
                if self.sched.queue or self.sched.active:
                    self._tick()
                elif self.sched.in_flight:
                    self._flush()
                elif i < n:
                    self._sleep_until(float(due_abs[i]))
                else:
                    break
                if now > give_up:
                    break
            rec.t1 = clock()
        rec.undelivered = sum(1 for r in rec.due if r not in rec.done)
        return self._close_record()
