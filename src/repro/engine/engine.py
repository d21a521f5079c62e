"""SamplerEngine: spec → weight table → jitted scan, with fused CFG serving.

The single entry point every launcher and benchmark builds on:

    engine = SamplerEngine(schedule, eps=eps_fn,
                           eps_stacked=stacked_fn,    # for cfg_scale != 0
                           eps_uncond=uncond_fn)      # for the loop reference
    run = engine.build(EngineSpec(solver="dpmpp", order=2, nfe=10,
                                  cfg_scale=2.0, thresholding=True))
    x0 = run(x_T)

`build` is the whole-trajectory path (one uniform batch, one scan);
`build_step` compiles the same table into a per-slot `StepProgram` — the
continuous-batching step function `repro.serving`'s scheduler drives, where
every slot gathers its own table row and guidance scale (DESIGN.md §9).

Weights are program arguments. `launch.sample.build_engine` binds the param
tree into each eps callable with `jax.tree_util.Partial`, whose bound
arguments are pytree leaves, and every jitted program here takes the bundle
of callables (`SamplerEngine.nets`) as its first argument. So the weights
reach XLA as inputs, never as HLO constants: the lowered program does not
grow with the model, HBM holds one copy of the weights, and compile-cache
keys do not hash weight values.

`build` compiles the solver's weight table (registry-driven — see
`compiler.py`), wraps the eps-network into the table's prediction type, and
jits one `unipc_sample_scan` over the result. Conditional sampling (the
paper's Table 9 setting) is fused into that same scan:

* **CFG** runs as ONE batched network call per step — cond and uncond stacked
  along the batch (`cfg_model_fused`) instead of `cfg_model`'s two sequential
  evals — with the guidance scale (possibly a schedule) riding the table as
  a per-eval column.
* **Dynamic thresholding** percentiles are likewise a per-eval table column,
  applied to the x0-prediction inside the model wrapper.
"""

from __future__ import annotations

from dataclasses import dataclass, replace as dc_replace
from functools import partial
from typing import Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.tree_util import Partial

from ..core.coeffs import SolverTable, eval_cost_rows, stack_step_rows
from ..core.unipc import step_fn_over_rows, unipc_sample_scan
from ..diffusion.guidance import cfg_model, cfg_model_fused, dynamic_threshold
from ..diffusion.process import eps_to_x0
from ..diffusion.schedules import NoiseSchedule
from ..parallel.sharding import shard
from .compiler import (apply_model_cols, build_loop, compile_table,
                       flag_done, step_guidance_profile)
from .specs import EngineSpec, SOLVERS


@dataclass(frozen=True)
class CacheSpec:
    """Shape contract for the feature-reuse cache (DESIGN.md §12).

    `shape` is the per-sample cache layout ((patch_tokens, d_model) for the
    DiT's deep-feature delta), `block` the static boundary the wired cached
    eps-net was built with (first `block` of `n_blocks` blocks recompute on
    shallow evals). The engine validates every spec's `cache_block` against
    `block` the same way `eval_dtype` is handshaken — the net-side closure
    and the engine-side state cannot silently disagree.
    """

    shape: Tuple[int, ...]
    block: int
    n_blocks: int
    dtype: str = "float32"

    def zeros(self, slots: int):
        return jnp.zeros((slots,) + tuple(self.shape), jnp.dtype(self.dtype))


@dataclass
class StepProgram:
    """A compiled per-slot step program — what the serving scheduler drives.

    step_flight(state, meta[, g, extras]) -> (state, meta, done) advances
    every busy slot by one table row: `state = (x, E)` with x (B, *sample)
    and E the (K+1, B, *sample) eval ring — or `(x, E, C)` for feature-reuse
    programs, C the (B, *cache) deep-feature cache that must live (and be
    donated) with the rest of the slot state (DESIGN.md §12) — and `g` (B,)
    float32 the per-slot guidance scale (only for cfg-enabled programs).
    Slot batches are sharded over the data axis via the active
    `parallel.sharding` rules (SERVE_RULES on the mesh; a no-op
    single-device), so the same tick loop runs everywhere. One batched model
    eval per call — a request admitted at tick tau and stepped through rows
    0..n_rows-1 reproduces the uniform `build()` scan for its own (solver,
    order, nfe, seed, cfg-scale) exactly.

    The per-slot bookkeeping lives on device as `meta` (DESIGN.md §13), a
    (4, B) int32 array of [row, offset, budget, busy] rows (`init_meta`).
    The program derives each slot's table index from its own counters
    (`offset + row` while busy, the parked init row 0 otherwise), advances
    them, and emits the per-slot `done` mask — the tick a busy slot executes
    its last budgeted row. The mask is a coded int32 per slot
    (`compiler.DONE_IDLE` / `DONE_OK` / `DONE_NONFINITE`): completion folds
    an on-device finite-check of the slot's latent, so the serving layer
    learns at emission — not from a host-side scan — whether the request's
    output is usable (DESIGN.md §16). The host never rebuilds `idx`: it only
    scatters admissions into `meta` and reads the tiny done mask back, which
    is what lets the serving scheduler keep several ticks in flight.
    """

    # the jitted program: flight(nets, state, meta, g, extras). `nets` is
    # the engine's eps bundle (`SamplerEngine.nets`) — the weights ride in
    # as an argument; `step_flight` binds it for callers
    flight: Callable
    nets: dict
    n_rows: int          # total table rows (single plan: ticks per request)
    table: SolverTable   # single-plan programs; first tier's table for banks
    spec: EngineSpec
    uses_cfg: bool
    ring: int            # eval-ring slots carried per sample, K + 1
    # plan banks (`SamplerEngine.build_bank`): tier name -> (row_offset,
    # n_rows) span in the stacked table. None for single-plan programs.
    tiers: Optional[Dict[str, Tuple[int, int]]] = None
    # feature reuse: the cache contract (None for uncached programs) and the
    # per-row eval cost (n_rows,) in fractions of a full denoiser eval —
    # 1.0 everywhere without caching, cache_block/n_blocks on reuse rows.
    cache: Optional[CacheSpec] = None
    row_cost: Optional[np.ndarray] = None

    def step_flight(self, state, meta, g=None, extras=None):
        return self.flight(self.nets, state, meta, g, extras)

    def resolve_tier(self, tier: Optional[str]) -> Tuple[int, int]:
        """(row_offset, rows_to_run) for a request's tier tag. Single-plan
        programs take untagged requests only; bank programs require a tag."""
        if self.tiers is None:
            if tier is not None:
                raise ValueError(
                    f"request tagged tier={tier!r} but the step program was "
                    f"compiled from a single plan; build it with "
                    f"SamplerEngine.build_bank")
            return 0, self.n_rows
        if tier is None:
            raise ValueError(f"this program is a plan bank; tag requests "
                             f"with tier= one of {sorted(self.tiers)}")
        if tier not in self.tiers:
            raise ValueError(f"unknown tier {tier!r}; this plan bank serves "
                             f"{sorted(self.tiers)}")
        return self.tiers[tier]

    def init_state(self, slots: int, sample_shape: Tuple[int, ...],
                   dtype=jnp.float32):
        """Zeroed slot state: every slot idle on the init row. Cached
        programs carry the feature cache as a third state array."""
        shape = tuple(sample_shape)
        state = (jnp.zeros((slots,) + shape, dtype),
                 jnp.zeros((self.ring, slots) + shape, dtype))
        if self.cache is not None:
            state = state + (self.cache.zeros(slots),)
        return state

    def span_cost(self, offset: int, n: int) -> float:
        """Total eval cost (full-eval units) of rows offset..offset+n-1 —
        a request's evals-per-latent when (offset, n) is its tier span."""
        if self.row_cost is None:
            return float(n)
        return float(np.sum(self.row_cost[offset:offset + n]))

    def tier_eval_cost(self, tier: Optional[str]) -> float:
        """Evals-per-latent for a tier tag (or the whole single-plan span)."""
        return self.span_cost(*self.resolve_tier(tier))

    def init_g(self, slots: int):
        """Per-slot guidance scales, seeded with the spec's nominal scale."""
        return jnp.full((slots,), float(self.spec.cfg_scale or 0.0),
                        jnp.float32)

    def init_meta(self, slots: int):
        """Zeroed on-device slot counters for `step_flight`: a (4, slots)
        int32 array of [row, offset, budget, busy] rows. Every slot starts
        idle (busy = 0, parked on the init row); budget is seeded with the
        full table so an un-admitted slot can never trip the done mask."""
        meta = np.zeros((4, slots), np.int32)
        meta[2] = self.n_rows
        return jnp.asarray(meta)


@dataclass
class SamplerEngine:
    """Solver-agnostic sampling engine over one eps-network.

    eps:         (x, t) -> eps-hat, conditioning captured (the cond branch).
    eps_stacked: (xx, t) -> eps-hat on a 2B batch whose conditioning is
                 [cond; null] — required for cfg_scale != 0 (fused CFG).
    eps_uncond:  (x, t) -> eps-hat with null conditioning — only needed for
                 `build_loop`'s reference path (sequential, two evals/step).
    eps_cached:  (x, t, cache, reuse) -> (eps-hat, cache') — the feature-reuse
                 eval (DESIGN.md §12), wired by
                 `launch.sample.build_engine(cache_block=...)` together with
                 `cache_spec`; only dit-family models support it.
    eval_dtype:  the precision the wired eps-net actually computes in —
                 `launch.sample.build_engine(eval_dtype=...)` sets it when
                 it casts the net; `model_fn` rejects specs that disagree,
                 so the net-side cast and the engine-side fp32 boundary
                 (DESIGN.md §11.3) cannot silently desynchronize.
    cache_spec:  the cache-state contract matching `eps_cached`; its `block`
                 is handshaken against every spec's `cache_block` exactly
                 like `eval_dtype`.

    The eps callables may be plain functions (analytic models) or
    `jax.tree_util.Partial`s binding a param tree; `nets` hands them to the
    compiled programs as one argument.
    """

    schedule: NoiseSchedule
    eps: Callable
    eps_stacked: Optional[Callable] = None
    eps_uncond: Optional[Callable] = None
    eval_dtype: str = "float32"
    eps_cached: Optional[Callable] = None
    cache_spec: Optional["CacheSpec"] = None
    # quantized-tier contract (DESIGN.md §14), handshaken like eval_dtype:
    # "none" or the models.quant tier the wired eps-net's params were
    # quantized for (`launch.sample.build_engine(quant=...)` sets it)
    quant: str = "none"

    def nets(self) -> dict:
        """The wired eps callables as one pytree argument: a Partial's
        bound params are leaves (traced inputs of the program), a plain
        function is a leafless Partial. Never re-wrap a Partial — the outer
        one would hide the inner's leaves and bake them back in."""
        fns = {"eps": self.eps, "eps_stacked": self.eps_stacked,
               "eps_uncond": self.eps_uncond, "eps_cached": self.eps_cached}
        return {k: f if isinstance(f, Partial) else Partial(f)
                for k, f in fns.items() if f is not None}

    # -- table ---------------------------------------------------------------
    def compile(self, spec: EngineSpec,
                table: Optional[SolverTable] = None) -> SolverTable:
        """Compile the spec's weight table and attach its per-eval model
        columns (guidance schedule, thresholding percentile). Pass `table`
        to skip the registry compiler and use an externally lowered table —
        a tuned `SolverPlan` — with the same conditioning knobs applied."""
        spec = spec.resolve()
        tab = table if table is not None else compile_table(spec, self.schedule)
        return apply_model_cols(tab, spec)

    # -- model ---------------------------------------------------------------
    def model_fn(self, spec: EngineSpec, tab: SolverTable,
                 nets: Optional[dict] = None) -> Callable:
        """Wrap the eps-net into the table's prediction type, consuming the
        per-eval model columns the table carries (g, tq). Any further keyword
        arguments (per-slot conditioning from a StepProgram's extras, e.g.
        class ids) pass through to the eps-net. `nets` is the (traced)
        `nets()` bundle inside a compiled program; None uses the engine's
        own callables.

        `spec.eval_dtype` is the network-eval precision boundary (DESIGN.md
        §11): the state is cast down on the way into the eps-net and the
        prediction cast back up, so solver state, combine weights, and the
        eps↔x0 conversion stay fp32 whatever the network runs in. (For the
        network itself to *compute* in bf16 the model config's activation
        dtype must match — `launch.sample.build_engine(eval_dtype=...)`
        wires both ends.)"""
        spec = self.check_wiring(spec, tab)
        nets = self.nets() if nets is None else nets
        if spec.cache_block:
            return self._cached_model_fn(spec, tab, nets["eps_cached"])
        if spec.cfg_scale:
            eps = cfg_model_fused(nets["eps_stacked"])  # (x, t, g, **extra)
        else:
            eps_c = nets["eps"]
            eps = lambda x, t, g=None, **extra: eps_c(x, t, **extra)

        schedule = self.schedule
        if spec.eval_dtype != "float32":
            # the precision boundary: state down-cast into the net, the
            # prediction back up to fp32 — only wrapped for reduced-precision
            # eval so the fp32 default (and the fp64 exactness tests) keep
            # the eps-net's native dtypes end to end
            eval_dtype = jnp.dtype(spec.eval_dtype)
            inner = eps
            eps = lambda x, t, g=None, **extra: inner(
                x.astype(eval_dtype), t, g, **extra).astype(jnp.float32)

        def model(x, t, g=None, tq=None, **extra):
            e = eps(x, t, g, **extra)
            if tab.prediction == "noise":
                return e
            x0 = eps_to_x0(schedule, x, t, e)
            if tq is not None:
                x0 = dynamic_threshold(x0, tq)
            return x0

        return model

    def check_wiring(self, spec: EngineSpec, tab: SolverTable) -> EngineSpec:
        """The spec <-> wired-net handshakes `model_fn` relies on; raised
        when a program is built, before anything is traced."""
        spec = spec.resolve()
        if spec.eval_dtype != self.eval_dtype:
            raise ValueError(
                f"spec.eval_dtype={spec.eval_dtype!r} but this engine's "
                f"eps-net was wired for {self.eval_dtype!r}; pass the same "
                f"eval_dtype to build_engine and the EngineSpec")
        if spec.quant != self.quant:
            raise ValueError(
                f"spec.quant={spec.quant!r} but this engine's eps-net was "
                f"wired for {self.quant!r}; the quantized param tree is "
                f"bound into the net — pass the same quant to build_engine "
                f"and the EngineSpec")
        if spec.cache_block:
            if self.eps_cached is None or self.cache_spec is None:
                raise ValueError(
                    f"spec.cache_block={spec.cache_block} but this engine has "
                    f"no cached eps-net; wire one with "
                    f"build_engine(cache_block={spec.cache_block})")
            if spec.cache_block != self.cache_spec.block:
                raise ValueError(
                    f"spec.cache_block={spec.cache_block} but the engine's "
                    f"cached eps-net was wired for cache boundary "
                    f"{self.cache_spec.block}; the boundary is baked into "
                    f"the compiled program — pass the same cache_block to "
                    f"build_engine and the EngineSpec")
            return spec
        if "cache_reuse" in (tab.model_cols or {}):
            raise ValueError(
                "this table carries a cache_reuse column (a cached plan) but "
                "spec.cache_block=0; build the engine and spec with the "
                "plan's cache_block so its shallow steps actually reuse the "
                "feature cache instead of silently paying full evals")
        if spec.cfg_scale and self.eps_stacked is None:
            raise ValueError("cfg_scale != 0 needs eps_stacked (a 2B "
                             "cond+uncond batched eps-net)")
        return spec

    def _cached_model_fn(self, spec: EngineSpec, tab: SolverTable,
                         eps_cached: Callable) -> Callable:
        """The feature-reuse model wrapper: (x, t, cache=..., cache_reuse=...,
        tq=..., **extra) -> (prediction, cache'). `cache_reuse` arrives from
        the table's `cache_reuse` model column when the plan schedules
        shallow steps; a plain registry table has no such column and every
        eval runs full (reuse = 0) — the bit-identity parity path."""
        schedule = self.schedule
        if spec.eval_dtype != "float32":
            eval_dtype = jnp.dtype(spec.eval_dtype)
            inner = eps_cached

            def eps_cached(x, t, cache, reuse, **extra):
                e, c = inner(x.astype(eval_dtype), t, cache, reuse, **extra)
                return e.astype(jnp.float32), c

        def model(x, t, cache, cache_reuse=None, tq=None, **extra):
            reuse = jnp.asarray(0.0 if cache_reuse is None else cache_reuse,
                                jnp.float32)
            e, cache = eps_cached(x, t, cache, reuse, **extra)
            if tab.prediction == "noise":
                return e, cache
            x0 = eps_to_x0(schedule, x, t, e)
            if tq is not None:
                x0 = dynamic_threshold(x0, tq)
            return x0, cache

        return model

    # -- run functions -------------------------------------------------------
    def build(self, spec: EngineSpec, jit: bool = True,
              table: Optional[SolverTable] = None) -> Callable:
        """spec -> run_fn(x_T) -> x0: the scan-compiled production path.
        Pass `table` (from a prior `compile`) to skip recompiling it."""
        spec = spec.resolve()
        tab = table if table is not None else self.compile(spec)
        self.check_wiring(spec, tab)
        cache_spec = self.cache_spec if spec.cache_block else None

        def run(nets, x_T):
            cache0 = (cache_spec.zeros(x_T.shape[0]) if cache_spec is not None
                      else None)
            return unipc_sample_scan(
                self.model_fn(spec, tab, nets), x_T, tab,
                fused_update=spec.fused_update, cache0=cache0)

        return partial(jax.jit(run) if jit else run, self.nets())

    def build_step(self, spec: EngineSpec, jit: bool = True,
                   table: Optional[SolverTable] = None,
                   donate: bool = True) -> StepProgram:
        """spec -> StepProgram: the per-slot step function for continuous
        batching (DESIGN.md §9). The same table rows `build` scans uniformly,
        gathered per slot; the guidance scale becomes per-slot state
        (multiplied by the table's schedule profile) so every request can
        carry its own cfg scale through one compiled program.

        `donate` (default on) donates the slot-state buffers (x, E) to the
        jitted step, so each tick's state update reuses the previous tick's
        HBM allocation instead of round-tripping a fresh one — the state is
        the whole slot batch plus the eval ring, the largest serving-resident
        tensors after the params. Callers must treat the passed-in state as
        consumed (the scheduler always does); `donate=False` keeps the
        allocating behavior for aliasing callers and the parity test."""
        spec = spec.resolve()
        tab = table if table is not None else self.compile(spec)
        return self._step_program({"_": (spec, tab)}, tiers=None, jit=jit,
                                  donate=donate)

    def build_bank(self, tier_specs: Dict[str, EngineSpec],
                   tables: Optional[Dict[str, SolverTable]] = None,
                   jit: bool = True, donate: bool = True) -> StepProgram:
        """Compile several plans into ONE servable step program (§10).

        tier_specs: {tier_name: EngineSpec} in serving-priority order; every
        tier may differ in solver / order / NFE budget (and tuned `tables`
        entries may replace the registry compile per tier), but all tiers
        must share prediction type and guidance configuration — the bank is
        one compiled program, one model wrapper, one eval ring. The stacked
        row table (`core.stack_step_rows`) gives each tier a contiguous row
        span; `StepProgram.tiers` maps tier -> (offset, n_rows) and the
        scheduler admits `Request(tier=...)` onto per-slot row offsets, so
        fast/balanced/quality requests coexist in one batch.
        """
        if not tier_specs:
            raise ValueError("build_bank needs at least one tier spec")
        stray = set(tables or {}) - set(tier_specs)
        if stray:
            raise ValueError(f"tables carry tiers {sorted(stray)} not in "
                             f"tier_specs {sorted(tier_specs)}; a typo'd "
                             f"key would silently serve the untuned "
                             f"registry table")
        items = {}
        for name, tspec in tier_specs.items():
            tspec = tspec.resolve()
            tab = (tables or {}).get(name)
            items[name] = (tspec, self.compile(tspec, table=tab))
        return self._step_program(items, tiers=True, jit=jit, donate=donate)

    def _step_program(self, items, tiers, jit, donate=True) -> StepProgram:
        """Shared lowering for build_step (single plan) and build_bank."""
        names = list(items)
        spec0, tab0 = items[names[0]]
        uses_cfg = bool(spec0.cfg_scale)
        cached = bool(spec0.cache_block)
        for name, (s, t) in items.items():
            if bool(s.cfg_scale) != uses_cfg or (
                    uses_cfg and float(s.cfg_scale) != float(spec0.cfg_scale)):
                raise ValueError(
                    f"bank tiers must share the nominal guidance scale; tier "
                    f"{name!r} has cfg_scale={s.cfg_scale}, expected "
                    f"{spec0.cfg_scale} (per-request scales stay free)")
            if s.fused_update != spec0.fused_update:
                raise ValueError("bank tiers must agree on fused_update")
            if s.eval_dtype != spec0.eval_dtype:
                raise ValueError("bank tiers must agree on eval_dtype (one "
                                 "compiled program, one model wrapper)")
            if s.quant != spec0.quant:
                raise ValueError(
                    f"bank tiers must agree on quant (one quantized param "
                    f"tree serves the whole program); tier {name!r} has "
                    f"quant={s.quant!r}, expected {spec0.quant!r}")
            if s.cache_block != spec0.cache_block:
                raise ValueError(
                    f"bank tiers must agree on cache_block (the boundary is "
                    f"static in the compiled eps-net); tier {name!r} has "
                    f"cache_block={s.cache_block}, expected "
                    f"{spec0.cache_block}")
            if not cached and "cache_reuse" in (t.model_cols or {}):
                raise ValueError(
                    f"tier {name!r} carries a cached plan (cache_reuse "
                    f"column) but the bank specs have cache_block=0; set "
                    f"cache_block on every tier spec (and the engine) to "
                    f"serve it")
        self.check_wiring(spec0, tab0)
        profs, step_tabs = [], {}
        for name, (s, t) in items.items():
            if uses_cfg:
                # the scan's absolute g column is replaced by per-slot state
                # x schedule profile; the core step must not gather it
                profs.append(np.asarray(step_guidance_profile(t, s),
                                        np.float64))
                cols = {k: v for k, v in (t.model_cols or {}).items()
                        if k != "g"}
                t = dc_replace(t, model_cols=cols)
            if cached and "cache_reuse" not in (t.model_cols or {}):
                # a bank may mix cached plans with plain tiers: a tier
                # without a reuse schedule runs every eval full (all-zero
                # column), keeping the stacked tables' column sets equal
                cols = dict(t.model_cols or {})
                cols["cache_reuse"] = np.zeros(len(t.timesteps))
                t = dc_replace(t, model_cols=cols)
            step_tabs[name] = t
        rows_np, spans = stack_step_rows(step_tabs)
        n_rows = len(rows_np["t"])
        rows = {k: jnp.asarray(v, jnp.float32) for k, v in rows_np.items()}
        prof = (jnp.asarray(np.concatenate(profs), jnp.float32)
                if uses_cfg else None)
        row_cost = (eval_cost_rows(rows_np, cache_block=spec0.cache_block,
                                   n_blocks=self.cache_spec.n_blocks)
                    if cached else None)

        def _shard_state(*state):
            x, E = state[:2]
            x = shard(x, "batch", *([None] * (x.ndim - 1)))
            E = shard(E, None, "batch", *([None] * (E.ndim - 2)))
            if len(state) == 2:
                return x, E
            C = state[2]
            return x, E, shard(C, "batch", *([None] * (C.ndim - 1)))

        def flight(nets, state, meta, g=None, extras=None):
            # on-device bookkeeping (DESIGN.md §13): the slot's table index
            # is derived from its own counters, never shipped from the host
            row, off, budget, busy = meta
            live = busy > 0
            idx = jnp.where(live, off + row, 0).astype(jnp.int32)
            kw = dict(extras) if extras else {}
            if uses_cfg:
                gs = (jnp.full(idx.shape, float(spec0.cfg_scale), jnp.float32)
                      if g is None else jnp.asarray(g, jnp.float32))
                kw["g"] = gs * prof[jnp.clip(idx, 0, n_rows - 1)]
            core_step = step_fn_over_rows(
                self.model_fn(spec0, tab0, nets), rows, sign=tab0.sign,
                fused_update=spec0.fused_update, cached=cached)
            state = _shard_state(*core_step(_shard_state(*state), idx,
                                            model_kwargs=kw or None))
            row = row + 1
            done = live & (row >= budget)
            live = live & ~done
            # finished / idle slots park back on the init row (idx 0, an
            # identity update) so the next tick leaves their latent intact
            # until the trailing readback collects it
            meta = jnp.stack([jnp.where(live, row, 0),
                              jnp.where(live, off, 0),
                              budget, live.astype(jnp.int32)])
            # the done mask carries the on-device output validation: a coded
            # int32 per slot (DONE_IDLE / DONE_OK / DONE_NONFINITE, see
            # compiler.flag_done) so a non-finite latent is flagged the tick
            # it finishes, inside the compiled step, at no host cost
            return state, meta, flag_done(done, state[0])

        if jit:
            # donate the slot state (arg 1): the tick's (x, E) update writes
            # into the previous tick's buffers instead of fresh HBM — safe
            # because every caller replaces its state reference with the
            # step's return value (bit-identity pinned in tests/test_serving).
            # For cached programs the feature cache C rides in the same
            # donated tuple: it is per-slot trajectory state exactly like the
            # eval ring, so it must live (and be recycled) with it. The
            # (tiny) meta counters (arg 2) live and recycle with the state
            # across in-flight ticks. The weights (arg 0) are never donated.
            flight = jax.jit(flight,
                             donate_argnums=(1, 2) if donate else ())
        return StepProgram(flight=flight, nets=self.nets(), n_rows=n_rows,
                           table=tab0, spec=spec0, uses_cfg=uses_cfg,
                           ring=rows_np["w_pred"].shape[-1] + 1,
                           tiers=dict(spans) if tiers else None,
                           cache=self.cache_spec if cached else None,
                           row_cost=row_cost)

    def build_loop(self, spec: EngineSpec) -> Callable:
        """The python-loop GridSolver reference for the same spec — identical
        math on the same grid, sequential CFG (two evals per step)."""
        spec = spec.resolve()
        if spec.cfg_scale and spec.cfg_schedule != "constant":
            raise ValueError("loop reference supports constant cfg only")
        eps = self.eps
        if spec.cfg_scale:
            if self.eps_uncond is None:
                raise ValueError("loop reference with cfg needs eps_uncond")
            eps = cfg_model(self.eps, self.eps_uncond, spec.cfg_scale)
        schedule = self.schedule
        if spec.prediction == "noise":
            if spec.thresholding:
                raise ValueError("thresholding needs a data-prediction solver")
            model = eps
        else:
            def model(x, t):
                x0 = eps_to_x0(schedule, x, t, eps(x, t))
                if spec.thresholding:
                    x0 = dynamic_threshold(x0, spec.threshold_percentile)
                return x0
        return build_loop(spec, self.schedule, model)

    @staticmethod
    def solvers():
        """Registered solver names (the --solver choices everywhere)."""
        return sorted(SOLVERS)
