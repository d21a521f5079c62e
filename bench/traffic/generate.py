"""The one traffic generator: reads a mix's data file, draws from the seed.

A mix is `bench/traffic/<name>.json` with a `kind`. Each kind is a module of
its own, `bench/traffic/gen_<kind>.py`, found by name; a new kind is a new
module, and a new mix of a kind that exists is a data file alone. A kind's
module states `OPEN`:

- a closed kind (`OPEN = False`, `gen_backlog`) gives `depth(traffic,
  slots)`: the load generator keeps that many requests queued at every tick,
  and a request is due when it is put in the queue;
- an open kind (`OPEN = True`, `gen_poisson`, `gen_gamma`) gives
  `gaps(traffic, n, rng)`: n inter-arrival gaps of the mix's shape. The
  generator draws them from the mix's `gap_seed`, n = round(rate x
  seconds), and scales them to fill the window: one fixed multiset per
  mix. The run's seed only permutes them, so every seed gets the same work
  and the same bursts, in another order.

Per-request inputs (the request's latent seed, its conditioning, its
guidance scale) come from the run's seed. The conditioning is the
configuration's: its file names a kind, `"conditioning": {"kind": ...}`,
and each kind is a module of its own, `bench/traffic/cond_<kind>.py`, found
by name. A kind gives

- `draw(config, rng)`: the hashable scalar a request's `Spec` carries, drawn
  from the run's stream right after the request's latent seed;
- `extras(config, spec)` and `extras_init(config)`: the program's per-request
  conditioning and its value in a slot that holds no request;
- `reference_inputs(config, specs)`: the keyword arguments, stacked over the
  requests, that the configuration's reference `sample` takes.

Arrays a kind needs (a caption's embedding) are made from the request's own
seed where they are used, so the program and the reference each make them
and neither takes them from the other.
"""

from __future__ import annotations

import importlib
import re
from dataclasses import dataclass
from typing import Hashable, Optional

import numpy as np

_KIND = re.compile(r"^[a-z][a-z0-9_]*$")


@dataclass(frozen=True)
class Spec:
    """One request as the traffic sends it."""
    rid: int
    seed: int
    cond: Hashable          # what the conditioning kind drew for it
    cfg_scale: Optional[float]


class Requests:
    """Deterministic per-request inputs: the k-th request of a seed is the
    same whatever the timing."""

    def __init__(self, config: dict, seed: int):
        serving = config["serving"]
        self.scales = list(serving["cfg_scales"]) if serving["guided"] else []
        self.config = config
        self.cond = conditioning(config)
        self.rng = np.random.default_rng(np.random.SeedSequence([seed, 1]))
        self.next_rid = 0

    def take(self, n: int = 1) -> list:
        out = []
        for _ in range(n):
            rid = self.next_rid
            self.next_rid += 1
            s = int(self.rng.integers(0, 2 ** 31 - 1))
            c = self.cond.draw(self.config, self.rng)
            g = self.scales[rid % len(self.scales)] if self.scales else None
            out.append(Spec(rid, s, c, g))
        return out


def _module(prefix: str, what: str, k):
    """bench/traffic/<prefix>_<k>.py, the module of `what` kind `k`."""
    if not isinstance(k, str) or not _KIND.match(k):
        raise ValueError(f"{what} kind must be a lower-case name, got {k!r}")
    try:
        return importlib.import_module(f"bench.traffic.{prefix}_{k}")
    except ModuleNotFoundError as e:
        raise ValueError(f"no module bench/traffic/{prefix}_{k}.py for "
                         f"{what} kind {k!r}") from e


def conditioning(config: dict):
    """The module of the configuration's conditioning kind,
    bench/traffic/cond_<kind>.py."""
    return _module("cond", "conditioning",
                   config.get("conditioning", {}).get("kind"))


def kind(traffic: dict):
    """The module of the mix's kind, bench/traffic/gen_<kind>.py."""
    return _module("gen", "traffic", traffic.get("kind"))


def is_open(traffic: dict) -> bool:
    return bool(kind(traffic).OPEN)


def backlog_depth(traffic: dict, slots: int) -> int:
    return int(kind(traffic).depth(traffic, slots))


def gaps(traffic: dict, seconds: float) -> np.ndarray:
    """The mix's inter-arrival gaps for a window, before the seed's
    permutation: round(rate x seconds) of them, summing to `seconds`."""
    n = max(1, int(round(float(traffic["rate"]) * seconds)))
    rng = np.random.default_rng(int(traffic.get("gap_seed", 0)))
    g = np.asarray(kind(traffic).gaps(traffic, n, rng), np.float64)
    return g * (seconds / g.sum())


def arrivals(traffic: dict, seconds: float, seed: int) -> np.ndarray:
    """Due times in [0, seconds) of the open-loop window, seconds from its
    start."""
    g = np.random.default_rng(np.random.SeedSequence([seed, 2])).permutation(
        gaps(traffic, seconds))
    return np.concatenate([[0.0], np.cumsum(g)[:-1]])
