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

Per-request inputs (the request's latent seed, class id, guidance scale)
come from the run's seed.
"""

from __future__ import annotations

import importlib
import re
from dataclasses import dataclass
from typing import Optional

import numpy as np

_KIND = re.compile(r"^[a-z][a-z0-9_]*$")


@dataclass(frozen=True)
class Spec:
    """One request as the traffic sends it."""
    rid: int
    seed: int
    class_id: Optional[int]
    cfg_scale: Optional[float]


class Requests:
    """Deterministic per-request inputs: the k-th request of a seed is the
    same whatever the timing."""

    def __init__(self, config: dict, seed: int):
        serving = config["serving"]
        self.scales = list(serving["cfg_scales"]) if serving["guided"] else []
        self.classes = (config["model"]["num_classes"]
                        if serving["class_conditional"] else 0)
        self.rng = np.random.default_rng(np.random.SeedSequence([seed, 1]))
        self.next_rid = 0

    def take(self, n: int = 1) -> list:
        out = []
        for _ in range(n):
            rid = self.next_rid
            self.next_rid += 1
            s = int(self.rng.integers(0, 2 ** 31 - 1))
            c = int(self.rng.integers(0, self.classes)) if self.classes else None
            g = self.scales[rid % len(self.scales)] if self.scales else None
            out.append(Spec(rid, s, c, g))
        return out


def kind(traffic: dict):
    """The module of the mix's kind, bench/traffic/gen_<kind>.py."""
    k = traffic.get("kind")
    if not isinstance(k, str) or not _KIND.match(k):
        raise ValueError(f"traffic kind must be a lower-case name, got {k!r}")
    try:
        return importlib.import_module(f"bench.traffic.gen_{k}")
    except ModuleNotFoundError as e:
        raise ValueError(f"no generator bench/traffic/gen_{k}.py for "
                         f"traffic kind {k!r}") from e


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
