"""Faults planted under the timed path, to show that `correct` catches them.

Never used by a benchmark run: `bench/limits.py` reads each fault at a
cell's own size on the chip, and tests/bench runs them at a small size.

- `unchanged_state`: every step returns the latents it was given.
- `half_batch`: the step advances only the first half of the slots; the
  rest keep their latents.
- `altered_answer`: the readback gathers each finished slot's neighbour,
  so every delivered latent is another slot's.
"""

from __future__ import annotations

import jax.numpy as jnp

FAULTS = ("unchanged_state", "half_batch", "altered_answer")


def _broken_flight(flight, fault):
    def step(nets, state, meta, g, extras):
        x_old = jnp.copy(state[0])
        new, meta, done = flight(nets, state, meta, g, extras)
        x = new[0]
        if fault == "unchanged_state":
            x = x_old
        else:
            keep = jnp.arange(x.shape[0]) < x.shape[0] // 2
            x = jnp.where(keep.reshape((-1,) + (1,) * (x.ndim - 1)), x, x_old)
        return (x,) + tuple(new[1:]), meta, done
    return step


def plant(served, fault: str, patch) -> None:
    """Break `served` (a bench.system.Served) with `fault`. `patch(obj,
    name, value)` replaces an attribute of the program's modules, and is
    the caller's to undo (pytest's monkeypatch.setattr, or a plain setattr
    in a process that ends after the reading)."""
    if fault == "altered_answer":
        import repro.serving.scheduler as sch

        gather = sch._gather_rows
        patch(sch, "_gather_rows",
              lambda x, idx: gather(x, (idx + 1) % x.shape[0]))
    elif fault in ("unchanged_state", "half_batch"):
        served.sched._flight = _broken_flight(served.sched._flight, fault)
    else:
        raise ValueError(f"unknown fault {fault!r}; have {FAULTS}")
