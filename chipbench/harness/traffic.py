"""One general open-loop traffic generator, driven by a mix's parameters.

Every seed gets the same work in another order: arrivals are a stratified
Poisson process (the gaps are the exponential distribution's quantiles at
``(i + 0.5) / N``, shuffled by the seed), and tenant draws are a stratified
Zipf sample (quantiles of the Zipf CDF, shuffled), mapped onto tenants
through a seeded permutation of the population.  So a run's count of
tickets, of recommends and of arrivals per rank is fixed by the mix and the
window, and the seed only decides which workload is hot and in what order
the work comes.

Mix parameters (a mix file under ``traffic/`` holds them):

``pattern``               ``"recurring"`` (all tenants open in set-up, tickets
                          on open sessions) or ``"onboard"`` (each arrival is
                          a new tenant whose first ticket opens its session)
``ticket_rate_per_s``     mean ticket (or new-tenant) arrival rate
``zipf_s``                Zipf exponent of the tenant draw (recurring)
``slo``, ``n_probes``     the ticket's SLO class and probe count
``recommend_after_ticket`` one ``recommend`` when each ticket completes
``recommend_rate_per_s``  background ``recommend`` stream, Zipf over tenants
``warm_tenants``          onboard: tenants opened in set-up, never arriving
``max_groups``            the most tenants one round can coalesce at this
                          load: the warm-up builds the group buckets up to it
"""

from __future__ import annotations

import dataclasses

import numpy as np


@dataclasses.dataclass(frozen=True)
class Event:
    due_s: float  # seconds after the window opens
    kind: str  # "ticket" | "recommend"
    tenant: int


def _rng(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng(np.random.SeedSequence([int(seed), stream]))


def arrival_times(n: int, seconds: float, rng) -> np.ndarray:
    """``n`` stratified-Poisson arrival times inside ``[0, seconds)``."""
    if n <= 0:
        return np.zeros(0)
    q = (np.arange(n) + 0.5) / n
    gaps = -np.log1p(-q)
    rng.shuffle(gaps)
    t = np.cumsum(gaps)
    return t * (seconds * n / (n + 1)) / t[-1]


def zipf_ranks(n: int, population: int, s: float, rng) -> np.ndarray:
    """``n`` stratified draws of a 0-based Zipf(s) rank, in seeded order."""
    w = 1.0 / np.arange(1, population + 1) ** s
    cdf = np.cumsum(w) / w.sum()
    q = (np.arange(n) + 0.5) / n
    ranks = np.minimum(np.searchsorted(cdf, q), population - 1)
    rng.shuffle(ranks)
    return ranks


def schedule(mix: dict, seed: int, seconds: float, population: int,
             rate: float | None = None, skip: int = 0) -> list:
    """The window's events in due order.  ``rate`` overrides the mix's
    ticket rate and ``skip`` passes over new tenants an earlier window
    already onboarded (the sweep)."""
    rate = float(mix["ticket_rate_per_s"] if rate is None else rate)
    order = _rng(seed, 0).permutation(population)  # rank -> tenant
    events = []
    n = int(round(rate * seconds))
    times = arrival_times(n, seconds, _rng(seed, 1))
    if mix["pattern"] == "onboard":
        warm = int(mix.get("warm_tenants", 0))
        pool = order[warm + skip:]
        if n > len(pool):
            raise ValueError(
                f"onboard: {n} arrivals but only {len(pool)} new tenants")
        tenants = pool[:n]
    elif mix["pattern"] == "recurring":
        tenants = order[zipf_ranks(n, population, mix["zipf_s"],
                                   _rng(seed, 2))]
    else:
        raise ValueError(f"unknown traffic pattern {mix['pattern']!r}")
    events += [Event(float(t), "ticket", int(w))
               for t, w in zip(times, tenants)]
    m = int(round(float(mix.get("recommend_rate_per_s", 0.0)) * seconds))
    if m:
        rtimes = arrival_times(m, seconds, _rng(seed, 3))
        rten = order[zipf_ranks(m, population, mix.get("zipf_s", 1.0),
                                _rng(seed, 4))]
        events += [Event(float(t), "recommend", int(w))
                   for t, w in zip(rtimes, rten)]
    events.sort(key=lambda e: e.due_s)
    return events


def warm_tenants(mix: dict, seed: int, population: int) -> list:
    """Tenants opened in set-up: all of them for recurring traffic, the
    first ``warm_tenants`` of the seeded order for onboarding."""
    order = _rng(seed, 0).permutation(population)
    if mix["pattern"] == "onboard":
        return [int(w) for w in order[:int(mix.get("warm_tenants", 0))]]
    return [int(w) for w in range(population)]
