"""The one traffic generator: a mix's data file in, a schedule out.

A mix (``traffic/<name>.json``) names an arrival process and a popularity:

  arrival   {"process": "poisson"}  open loop at the cell's ``rate_qps``
            {"process": "closed", "outstanding": C}  C queries in flight
  popularity {"kind": "distinct"}   every query new
            {"kind": "hot", "hot_share": h, "hot_set": H, "zipf_s": s}
            a share h of arrivals drawn Zipf(s) over H hot queries, the
            rest new

Every seed gets the same work at the same times.  The window holds exactly
``round(rate * seconds)`` arrivals whose gaps are the exponential law's
quantiles, in an order drawn once from the mix's ``schedule_seed``, and the
same arrivals are hot.  The run's seed draws what arrives: the queries,
which hot query each hot arrival repeats, and the weights.  (With the gaps
in a seed's own order, the order alone moved the 95th percentile by up to
a third between seeds while two runs of one seed agreed within a few
percent.)
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional

import numpy as np


def rng_for(seed: int, stream: int) -> np.random.Generator:
    """An independent stream per purpose, for any non-negative seed."""
    return np.random.default_rng([int(seed), int(stream)])


def seed32(seed: int, stream: int) -> int:
    """A 31-bit key for APIs that take a small integer seed."""
    return int(np.random.SeedSequence([int(seed), int(stream)])
               .generate_state(1)[0] & 0x7FFFFFFF)


def exponential_gaps(n: int, span: float) -> np.ndarray:
    """n gaps at the quantiles (i + 1/2) / n of an exponential law, scaled
    to sum to ``span``: a Poisson process's gaps without its count noise."""
    if n <= 0:
        return np.zeros(0)
    u = (np.arange(n) + 0.5) / n
    g = -np.log1p(-u)
    return g * (span / g.sum())


@dataclasses.dataclass
class Schedule:
    """What the window offers.

    ``arrivals`` are offsets (s) from the window's open, sorted; the first
    ``n_counted`` lie inside the window and are the ones measured, the rest
    keep the load on until the counted ones are answered.  ``slots[i]``
    is the query slot of arrival i: ``>= 0`` a hot query's index in the
    hot set, ``-1`` a new query.  A closed loop has no arrivals.
    """
    arrivals: np.ndarray
    n_counted: int
    slots: np.ndarray
    outstanding: Optional[int] = None

    @property
    def n_hot(self) -> int:
        return int((self.slots[: self.n_counted] >= 0).sum())


def zipf_draws(rng: np.random.Generator, n: int, size: int, s: float
               ) -> np.ndarray:
    """n draws of ranks 0..size-1 with P(rank r) proportional to (r+1)^-s."""
    p = (np.arange(1, size + 1, dtype=np.float64)) ** -float(s)
    return rng.choice(size, size=n, p=p / p.sum())


def _hot_at(pop: Dict[str, Any], n: int, rng: np.random.Generator
            ) -> np.ndarray:
    """Which of n arrivals are hot."""
    if pop["kind"] == "distinct":
        return np.zeros(n, bool)
    if pop["kind"] != "hot":
        raise ValueError(f"unknown popularity kind {pop['kind']!r}")
    hot = np.zeros(n, bool)
    hot[rng.permutation(n)[: int(round(float(pop["hot_share"]) * n))]] = True
    return hot


def schedule(mix: Dict[str, Any], params: Dict[str, Any], seconds: float,
             seed: int, *, tail_s: float = 30.0) -> Schedule:
    """The window's arrivals for mix ``mix`` and cell numbers ``params``."""
    arrival = mix["arrival"]
    if arrival["process"] == "closed":
        return Schedule(np.zeros(0), 0, np.zeros(0, np.int64),
                        outstanding=int(arrival["outstanding"]))
    if arrival["process"] != "poisson":
        raise ValueError(f"unknown arrival process {arrival['process']!r}")
    fixed = rng_for(int(mix["schedule_seed"]), 1)
    rate = float(params["rate_qps"])
    n = max(1, int(round(rate * seconds)))
    n_tail = int(np.ceil(rate * tail_s))
    gaps = np.concatenate([fixed.permutation(exponential_gaps(n, seconds)),
                           fixed.permutation(exponential_gaps(n_tail, tail_s))])
    # arrival i at the end of gap i: the n counted lie in (0, seconds]
    arrivals = np.cumsum(gaps)
    arrivals[n - 1] = min(arrivals[n - 1], seconds * (1 - 1e-9))
    pop = mix["popularity"]
    hot = np.concatenate([_hot_at(pop, n, fixed), _hot_at(pop, n_tail, fixed)])
    slots = np.full(n + n_tail, -1, np.int64)
    if hot.any():
        slots[hot] = zipf_draws(rng_for(seed, 1), int(hot.sum()),
                                int(pop["hot_set"]), float(pop["zipf_s"]))
    return Schedule(arrivals, n, slots)


def hot_set_size(mix: Dict[str, Any]) -> int:
    pop = mix["popularity"]
    return int(pop["hot_set"]) if pop["kind"] == "hot" else 0


def due(arrivals: np.ndarray, start: int, now: float, cap: int) -> List[int]:
    """Indices of arrivals from ``start`` whose time has come, at most cap."""
    end = int(np.searchsorted(arrivals, now, side="right"))
    return list(range(start, min(end, start + cap)))
