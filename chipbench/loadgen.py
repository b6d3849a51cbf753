"""Load generator: a traffic-mix file turned into requests with due times.

A mix file (``chipbench/traffic/<name>.json``) holds only parameters:

* ``arrival`` -- ``{"process": "poisson", "rate": r}``: open loop, ``r``
  requests per second;
* ``prompt`` / ``output`` -- lognormal lengths ``{"mean", "cv", "min",
  "max"}``; ``max_total`` caps prompt + output;
* ``population_seed`` -- fixes the set of sizes and gaps;
* ``block`` -- positions per block of the window (below).

Every ``--seed`` gets the same set of lengths and the same set of gaps in
another order, so each seed offers the same work. The population is dealt
into consecutive blocks of ``block`` positions, like shares of short and
long requests (and gaps) to each, and a seed orders each block's share
anew. So every stretch of the window carries the same work whatever the
seed, and which requests the window's end cuts short does not depend on
it. The lognormal arithmetic
is that of the repo's ``serving/workload.py`` (``_lognormal``, the
``sharegpt`` and ``longcontext`` families); the open-loop gaps are
exponential, rescaled so that the ``N = round(rate * seconds)`` arrivals
all fall inside the window.
"""

from __future__ import annotations

import dataclasses
import json
import math
from pathlib import Path
from typing import List

import numpy as np

__all__ = ["Planned", "load_mix", "plan_requests"]


@dataclasses.dataclass(frozen=True)
class Planned:
    req_id: int
    due: float                # seconds after the window opens
    prompt_len: int
    output_len: int


def load_mix(path: Path) -> dict:
    mix = json.loads(Path(path).read_text())
    proc = mix["arrival"]["process"]
    if proc != "poisson":
        raise ValueError(f"{path}: unknown arrival process {proc!r}")
    return mix


def _lognormal(rng, mean: float, cv: float, size: int) -> np.ndarray:
    sigma2 = math.log(1.0 + cv * cv)
    mu = math.log(mean) - 0.5 * sigma2
    return rng.lognormal(mu, math.sqrt(sigma2), size=size)


def _lengths(mix: dict, rng, n: int):
    p, o = mix["prompt"], mix["output"]
    prompt = np.clip(_lognormal(rng, p["mean"], p["cv"], n).astype(np.int64),
                     p["min"], p["max"])
    out = _lognormal(rng, o["mean"], o["cv"], n).astype(np.int64)
    cap = np.minimum(o.get("max", mix["max_total"]), mix["max_total"] - prompt)
    return prompt, np.clip(out, o["min"], cap)


def n_requests(mix: dict, seconds: float) -> int:
    return max(1, int(round(mix["arrival"]["rate"] * seconds)))


def _blocks(key: np.ndarray, block: int) -> List[List[int]]:
    """Indices of ``key`` dealt by size, to and fro, into consecutive
    blocks of about ``block``, so that each block holds a like share of
    small and large values."""
    nb = -(-len(key) // block)
    out: List[List[int]] = [[] for _ in range(nb)]
    for i, idx in enumerate(np.argsort(key, kind="stable")):
        row, col = divmod(i, nb)
        out[col if row % 2 == 0 else nb - 1 - col].append(int(idx))
    return out


def plan_requests(mix: dict, seed: int, seconds: float,
                  id_base: int) -> List[Planned]:
    """The window's requests, sorted by due time; ids from ``id_base``."""
    n = n_requests(mix, seconds)
    block = mix["block"]
    pop = np.random.default_rng(mix["population_seed"])
    prompt, out = _lengths(mix, pop, n)
    gaps = pop.exponential(1.0, size=n)
    rng = np.random.default_rng(seed)
    order = np.concatenate([rng.permutation(b) for b in _blocks(out, block)])
    prompt, out = prompt[order], out[order]
    gaps = gaps[np.concatenate([rng.permutation(b)
                                for b in _blocks(gaps, block)])]
    # N arrivals in [0, T]: the N-th lands where N uniform points put
    # their largest on average, T * N / (N + 1)
    due = np.cumsum(gaps) * (seconds * n / (n + 1)) / gaps.sum()
    return [Planned(id_base + i, float(due[i]), int(prompt[i]), int(out[i]))
            for i in range(n)]

