"""Closed-loop generator (``kind: closed``): every tenant keeps a fixed
number of equal draws outstanding, and submits the next one the moment
one resolves.  The first submissions go in tenant order whatever the
seed, so every seed gets the same work; the seed draws the tenants'
streams (``Session``)."""
from __future__ import annotations

import asyncio
import dataclasses

import numpy as np


@dataclasses.dataclass
class Plan:
    order: np.ndarray      # tenants in the order of their first submission
    outstanding: int
    words: int
    window: tuple          # (t0, t1) seconds: draws submitted in it count
    deadline_ms: float
    slo: str


def make(mix: dict, n_cores: int, seed: int, seconds: float) -> Plan:
    n = n_cores * int(mix["tenants_per_core"])
    warm = float(mix["warmup_s"])
    return Plan(order=np.arange(n),
                outstanding=int(mix["outstanding"]),
                words=int(mix["draw_words"]), window=(warm, warm + seconds),
                deadline_ms=float(mix["deadline_ms"]), slo=mix["slo"])


async def drive(plan: Plan, session) -> None:
    """Keep ``outstanding`` draws in flight per tenant until the window
    closes; a refused draw is retried a millisecond later."""
    stop = plan.window[1]
    loop = asyncio.get_running_loop()

    def go(t: int) -> None:
        if session.now() >= stop:
            return
        fut = session.submit(t, plan.words, session.now(), plan.deadline_ms,
                             plan.slo)
        if fut is None:
            loop.call_later(1e-3, go, t)
        else:
            fut.add_done_callback(lambda _f, t=t: go(t))

    with session.span("bench.submit"):
        for t in plan.order:
            for _ in range(plan.outstanding):
                go(int(t))
    await asyncio.sleep(max(0.0, stop - session.now()))
