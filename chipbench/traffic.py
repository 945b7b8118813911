"""One general request generator, driven by a traffic file
(``traffic/<name>.json``) and ``--seed``.

Lengths and gaps between arrivals are drawn by stratified sampling: a run
takes its values from a fixed grid of quantiles of the stated distribution,
and the seed only shuffles their order (a fresh shuffle for each pass over
the grid). So every seed offers the same amount of work and the spread
between seeds is the system's, not the sampler's. Token ids, the order of
tenants and which requests carry an adapter come from the seed too.

A traffic file holds:

  loop          "closed" (``clients`` each send the next request when the
                last one completes) or "open" (``rate_hz`` Poisson arrivals)
  prompt_len    {"median", "sigma", "min", "max"}: lognormal, clipped
  output_len    the same, for the tokens each request decodes
  adapter_share share of requests that name a tenant (0 = none)
  lead_in_s     open loop: seconds of arrivals before the window opens
  settle_s      closed loop: seconds the full batch runs before the window
"""
from __future__ import annotations

import dataclasses
import json
import math
from pathlib import Path
from statistics import NormalDist
from typing import Iterator, List, Optional

import numpy as np

#: Quantile grid size. It bounds the distinct prompt lengths a cell can
#: send, so that set-up can warm every shape the program compiles for one.
GRID = 128


@dataclasses.dataclass
class Req:
    index: int
    prompt: np.ndarray          # int32 token ids
    max_new: int
    tenant: Optional[int]       # adapter index, None for the base model
    due: float = 0.0            # open loop: seconds after the start


def load(path) -> dict:
    return json.loads(Path(path).read_text())


def lognormal_grid(spec: dict, n: int = GRID) -> np.ndarray:
    """``n`` stratified lognormal lengths (ints), clipped to [min, max]."""
    nd = NormalDist(math.log(spec["median"]), spec["sigma"])
    q = [nd.inv_cdf((i + 0.5) / n) for i in range(n)]
    x = np.rint(np.exp(np.asarray(q)))
    return np.clip(x, spec["min"], spec["max"]).astype(np.int64)


def exponential_grid(rate_hz: float, n: int = GRID) -> np.ndarray:
    """``n`` stratified gaps of a Poisson process of ``rate_hz``."""
    q = (np.arange(n) + 0.5) / n
    return -np.log1p(-q) / rate_hz


def share_grid(share: float, n: int = GRID) -> np.ndarray:
    flags = np.zeros(n, bool)
    flags[:int(round(share * n))] = True
    return flags


class _Stream:
    """Endless draws from a grid, reshuffled by ``rng`` on every pass."""

    def __init__(self, grid: np.ndarray, rng: np.random.Generator):
        self.grid, self.rng, self.order, self.i = grid, rng, None, len(grid)

    def next(self):
        if self.i == len(self.grid):
            self.order, self.i = self.rng.permutation(self.grid), 0
        self.i += 1
        return self.order[self.i - 1]


def requests(traffic: dict, seed: int, vocab: int,
             tenants: int = 0) -> Iterator[Req]:
    """The endless, seeded request sequence of ``traffic``. In an open loop
    each request carries its due time; in a closed loop the harness hands
    the requests to clients in order."""
    rng_p, rng_o, rng_a, rng_t, rng_tok, rng_g = (
        np.random.default_rng(s)
        for s in np.random.SeedSequence(seed).spawn(6))
    plen = _Stream(lognormal_grid(traffic["prompt_len"]), rng_p)
    olen = _Stream(lognormal_grid(traffic["output_len"]), rng_o)
    share = traffic.get("adapter_share", 0.0)
    if share and not tenants:
        raise ValueError("traffic names adapters but the configuration "
                         "registers no tenants")
    ad = _Stream(share_grid(share), rng_a)
    gaps = (_Stream(exponential_grid(traffic["rate_hz"]), rng_g)
            if traffic["loop"] == "open" else None)
    t = 0.0
    i = 0
    while True:
        length = int(plen.next())
        tenant = int(rng_t.integers(tenants)) if ad.next() else None
        req = Req(i, rng_tok.integers(0, vocab, size=length, dtype=np.int32),
                  int(olen.next()), tenant)
        if gaps is not None:
            t += float(gaps.next())
            req.due = t
        yield req
        i += 1


def prompt_lengths(traffic: dict) -> List[int]:
    """Every prompt length the traffic can send."""
    return sorted({int(n) for n in lognormal_grid(traffic["prompt_len"])})


def take(it: Iterator[Req], n: int) -> List[Req]:
    return [next(it) for _ in range(n)]


def residual_start(first: List[Req]) -> None:
    """Stagger a closed loop's first requests, one per client: client ``c``
    of ``n`` decodes ``(c + 1) / n`` of its drawn length, as if it had been
    running for a while. Completions then arrive at the steady rate from
    the first ticks, and the set-up need not wait out a synchronised wave."""
    n = len(first)
    for c, req in enumerate(first):
        req.max_new = max(1, math.ceil(req.max_new * (c + 1) / n))
