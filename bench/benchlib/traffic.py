"""The one general traffic generator. A mix is a JSON file of parameters
(`bench/traffic/<name>.json`, with a cell's own overrides from
`bench/cells/<cell>.json`); nothing here knows a mix by name, so a new mix
made of the choices below is a data file alone.

  loop           "open"    each request sent when it is due, whatever the
                           server is doing
                 "closed"  `in_flight` requests outstanding, topped up as
                           they complete
  rate_rps       open: mean arrivals per second
  arrivals       open: how the gaps between arrivals are drawn
                 "stratified"  exactly round(rate x seconds) gaps, the
                               exponential distribution's quantiles scaled
                               to span the window, shuffled by the seed:
                               every seed the same work in another order
                 "poisson"     independent exponential gaps from the seed;
                               the count varies from seed to seed
  burst          open, optional: {"every_s": P, "for_s": B, "factor": k};
                 for B seconds of every P the arrival rate is k times the
                 rest's, the mean still `rate_rps`
  tenant_choice  which tenant each request queries
                 "uniform"      every tenant the same share, in an order
                                drawn from the seed
                 "round_robin"  every round of `tenants` requests visits
                                each tenant once, in an order from the seed
                 "zipf"         the tenant of popularity rank i gets a share
                                proportional to 1 / i**`zipf_exponent`;
                                which tenant holds which rank, and the
                                order, come from the seed
  in_flight      closed: requests outstanding

Of a given number of requests each tenant's share is an exact count
(largest remainders), so the seed changes which request comes when, never
how many each tenant gets.
"""
from __future__ import annotations

from typing import Dict, List, Sequence, Tuple

import numpy as np

KEYS = {"loop", "rate_rps", "arrivals", "burst", "tenant_choice",
        "zipf_exponent", "in_flight"}
# a closed loop cycles through this many rounds of tenants
CLOSED_ROUNDS = 64


def _rng(seed: int) -> np.random.Generator:
    # [seed, 1]: a stream of its own, apart from the graphs' `seed + i`
    return np.random.default_rng([int(seed), 1])


def _exact_counts(weights: np.ndarray, n: int) -> np.ndarray:
    """`n` split in proportion to `weights`, by largest remainders."""
    share = n * weights / weights.sum()
    counts = np.floor(share).astype(int)
    rest = np.argsort(-(share - counts), kind="stable")[:n - counts.sum()]
    counts[rest] += 1
    return counts


def tenant_sequence(params: Dict, n: int, tenants: int,
                    rng: np.random.Generator) -> np.ndarray:
    """The tenant of each of `n` successive requests."""
    choice = params.get("tenant_choice", "uniform")
    if choice == "uniform":
        return rng.permutation(np.arange(n) % tenants)
    if choice == "round_robin":
        rounds = -(-n // tenants)
        return np.concatenate([rng.permutation(tenants)
                               for _ in range(rounds)])[:n]
    if choice == "zipf":
        s = float(params["zipf_exponent"])
        counts = _exact_counts(1.0 / np.arange(1, tenants + 1) ** s, n)
        by_rank = rng.permutation(tenants)
        return rng.permutation(np.repeat(by_rank, counts))
    raise ValueError(f"unknown tenant_choice {choice!r}")


def _arrival_times(params: Dict, seconds: float,
                   rng: np.random.Generator) -> np.ndarray:
    """Due offsets in [0, seconds) at a constant mean rate, sorted."""
    rate = float(params["rate_rps"])
    kind = params.get("arrivals", "stratified")
    if kind == "stratified":
        n = max(1, int(round(rate * seconds)))
        q = (np.arange(n) + 0.5) / n
        gaps = -np.log1p(-q)
        gaps *= seconds / gaps.sum()
        gaps = rng.permutation(gaps)
        return np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
    if kind == "poisson":
        expect = rate * seconds
        gaps = rng.exponential(1.0 / rate,
                               int(expect + 8 * np.sqrt(expect) + 16))
        t = np.cumsum(gaps)
        while t[-1] < seconds:
            t = np.concatenate([t, t[-1] + np.cumsum(
                rng.exponential(1.0 / rate, len(t)))])
        return t[t < seconds]
    raise ValueError(f"unknown arrivals {kind!r}")


def _bursts(times: np.ndarray, burst: Dict, seconds: float) -> np.ndarray:
    """Map arrivals at a constant rate onto a rate that is `factor` times
    higher for `for_s` of every `every_s` seconds, with the same mean:
    offsets are read as shares of the expected count and placed where the
    bursty rate's cumulative count reaches them."""
    every, on, k = (float(burst[x]) for x in ("every_s", "for_s", "factor"))
    if not (0 < on < every and k > 0):
        raise ValueError(f"burst needs 0 < for_s < every_s and factor > 0, "
                         f"got {burst}")
    edges = np.unique(np.concatenate([
        np.arange(0.0, seconds, every), np.arange(on, seconds, every),
        [seconds]]))
    mid = (edges[:-1] + edges[1:]) / 2
    rate = np.where(mid % every < on, k, 1.0)
    cum = np.concatenate([[0.0], np.cumsum(rate * np.diff(edges))])
    return np.interp(times / seconds * cum[-1], cum, edges)


def open_schedule(params: Dict, seconds: float, tenants: int,
                  seed: int) -> List[Tuple[float, int]]:
    """[(due offset in seconds from the window's start, tenant)], sorted."""
    if float(params["rate_rps"]) <= 0 or seconds <= 0:
        raise ValueError(f"open loop needs a positive rate and window, got "
                         f"{params['rate_rps']} req/s over {seconds} s")
    rng = _rng(seed)
    due = _arrival_times(params, seconds, rng)
    if "burst" in params:
        due = _bursts(due, params["burst"], seconds)
    tenant = tenant_sequence(params, len(due), tenants, rng)
    return [(float(d), int(k)) for d, k in zip(due, tenant)]


def closed_order(params: Dict, tenants: int, seed: int,
                 rounds: int = CLOSED_ROUNDS) -> List[int]:
    """Tenant of each successive closed-loop request; the loop cycles
    through it."""
    return [int(k) for k in tenant_sequence(params, rounds * tenants,
                                            tenants, _rng(seed))]


def check(params: Dict) -> None:
    """Refuse a parameter the generator would not read."""
    unknown = set(params) - KEYS
    if unknown:
        raise ValueError(f"unknown traffic parameters {sorted(unknown)}; "
                         f"the generator reads {sorted(KEYS)}")


def drive(eng, sch, phases, params: Dict, tenants: int, seconds: float,
          seed: int) -> Tuple[float, Sequence]:
    """Run one window of the mix through the scheduler: (window start on
    the engine clock, every request sent)."""
    from . import serve
    loop = params.get("loop")
    if loop == "open":
        return serve.open_loop(eng, sch, phases, open_schedule(
            params, seconds, tenants, seed), seconds)
    if loop == "closed":
        return serve.closed_loop(eng, sch, phases, closed_order(
            params, tenants, seed), int(params["in_flight"]), seconds)
    raise ValueError(f"unknown loop {loop!r}")
