"""Open-loop generator: Poisson arrivals, Zipf tenant popularity, bounded
Pareto draw sizes, all read from a mix file (``kind: open_zipf``).

Every seed gets the same work in another order: the inter-arrival gaps,
the draw sizes and the number of draws of each popularity rank are fixed
multisets (stratified quantiles, largest-remainder counts), and the seed
only permutes them and decides which tenant of each core holds which rank.
Rank ``r`` lives on core ``r % n_cores``, so each core's share of the
draws is the same whatever the seed (the first core holds the hottest
tenant).
"""
from __future__ import annotations

import asyncio
import dataclasses

import numpy as np


@dataclasses.dataclass
class Plan:
    due: np.ndarray        # (n,) seconds after the start of the traffic
    tenant: np.ndarray     # (n,) global tenant index, core-major
    words: np.ndarray      # (n,) words per draw
    window: tuple          # (t0, t1) seconds: draws due in it are measured
    deadline_ms: float
    slo: str


def zipf_counts(n: int, n_ranks: int, s: float) -> np.ndarray:
    """Draws per popularity rank: ``n * p_r`` with ``p_r ~ (r + 1)**-s``,
    rounded by largest remainder so the counts sum to ``n``."""
    p = (np.arange(n_ranks) + 1.0) ** -float(s)
    exact = n * p / p.sum()
    counts = np.floor(exact).astype(np.int64)
    short = n - int(counts.sum())
    order = np.argsort(-(exact - counts), kind="stable")
    counts[order[:short]] += 1
    return counts


def pareto_sizes(n: int, lo: float, hi: float, alpha: float) -> np.ndarray:
    """``n`` stratified quantiles of the bounded Pareto on [lo, hi]."""
    u = (np.arange(n) + 0.5) / n
    x = lo / (1.0 - u * (1.0 - (lo / hi) ** alpha)) ** (1.0 / alpha)
    return np.clip(np.rint(x), lo, hi).astype(np.int64)


def make(mix: dict, n_cores: int, seed: int, seconds: float) -> Plan:
    per_core = int(mix["tenants_per_core"])
    warm = float(mix["warmup_s"])
    total = warm + float(seconds)
    n = int(round(float(mix["rate_per_s"]) * total))
    rng = np.random.default_rng(seed)
    u = (np.arange(n) + 0.5) / n
    gaps = -np.log1p(-u)
    gaps *= total / gaps.sum()              # the last draw is due at `total`
    due = np.cumsum(rng.permutation(gaps))
    words = rng.permutation(pareto_sizes(
        n, mix["size_min_words"], mix["size_max_words"], mix["pareto_alpha"]))
    ranks = rng.permutation(np.repeat(
        np.arange(n_cores * per_core),
        zipf_counts(n, n_cores * per_core, mix["zipf_s"])))
    slot = np.stack([rng.permutation(per_core) for _ in range(n_cores)])
    core = ranks % n_cores
    tenant = core * per_core + slot[core, ranks // n_cores]
    return Plan(due=due, tenant=tenant, words=words, window=(warm, total),
                deadline_ms=float(mix["deadline_ms"]), slo=mix["slo"])


async def drive(plan: Plan, session) -> None:
    """Submit every draw at its due time; never waits for an answer."""
    due, tenant, words = plan.due, plan.tenant, plan.words
    i, n = 0, len(due)
    while i < n:
        now = session.now()
        if due[i] > now:
            await asyncio.sleep(due[i] - now)
            continue
        with session.span("bench.submit"):
            while i < n and due[i] <= now:
                session.submit(int(tenant[i]), int(words[i]), float(due[i]),
                               plan.deadline_ms, plan.slo)
                i += 1
