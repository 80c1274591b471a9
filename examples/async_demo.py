"""Async serving demo — uncoordinated tenants, coalesced gang launches.

Part 1: eight tenant coroutines independently ``await draw(...)`` small
requests against a four-core oscillator farm.  Nobody calls ``flush()``;
the front-end's background flusher coalesces everything that is queued
when either the earliest deadline expires or a full round of demand
accumulates, and fires ONE planner-shaped gang launch for the whole
group.  The demo prints the launch count next to the draw count — the
whole point is the gap between the two — and verifies a tenant's words
against the sync solo path.

Part 2 walks the production serving tier end to end:

* **admission control** — a token-bucket rate limit and a queued-rows
  ceiling reject over-limit submits with a typed ``Overloaded`` carrying
  a ``retry_after_ms`` hint (fail fast, honest backoff);
* **SLO classes** — a ``slo="latency"`` draw forbids the padded launch
  shape on a skewed group, ``slo="bulk"`` forces it; the farm counts the
  decisions its planner was forced into;
* **journaled crash recovery** — every flush appends one small position
  record; the demo "crashes" the serving process mid-stream, rebuilds a
  farm from weights + journal alone, and proves the recovered streams
  continue bit-identically.

Run:  PYTHONPATH=src python examples/async_demo.py
"""
import asyncio
import pathlib
import sys
import tempfile

import numpy as np

sys.path.insert(0, str(pathlib.Path(__file__).parent.parent / "src"))

from repro.core.dse import Candidate  # noqa: E402
from repro.prng.stream import default_params  # noqa: E402
from repro.serve.admission import (AdmissionController,  # noqa: E402
                                   Overloaded)
from repro.serve.async_frontend import AsyncOscillatorFarm  # noqa: E402
from repro.serve.farm import OscillatorFarm  # noqa: E402
from repro.serve.journal import replay_journal  # noqa: E402

SYSTEMS = ("lorenz", "chen", "rossler", "chua")     # gang-compatible 3-D
CAND = Candidate(i_dim=3, h_dim=8, p=1, compute_unit="vpu",
                 dtype_bytes=4, unroll=4, t_block=64)
N_TENANTS_PER_CORE = 2
ROUNDS = 3
WORDS = 1024                                        # 8 rows of 128 lanes


def build_farm(gang=True, register=True):
    farm = OscillatorFarm(gang=gang)
    for name in SYSTEMS:
        farm.add_core(name, default_params(system=name), config=CAND,
                      lanes_per_client=128, backend="pallas_interpret")
        if register:
            for j in range(N_TENANTS_PER_CORE):
                farm.register(name, f"tenant{j}", seed=100 + j)
    return farm


async def tenant(af, core, client, log):
    """One tenant: draws in its own loop, never coordinates with anyone."""
    for r in range(ROUNDS):
        words = await af.draw(core, client, WORDS, deadline_ms=10)
        log[(core, client)].append(words)
        print(f"  round {r}: {core:8s}/{client} got {words.size} words "
              f"(head={words[:2]})")


async def main():
    farm = build_farm()
    log = {(c, f"tenant{j}"): []
           for c in SYSTEMS for j in range(N_TENANTS_PER_CORE)}
    n_draws = len(log) * ROUNDS

    # threshold = one full round of demand; 10 ms deadline as backstop
    async with AsyncOscillatorFarm(
            farm, auto_flush_rows=len(SYSTEMS) * WORDS // 128) as af:
        print(f"=== {len(log)} tenants x {ROUNDS} rounds, nobody calls "
              f"flush() ===")
        await asyncio.gather(*(tenant(af, core, client, log)
                               for core, client in log))
        stats = af.deadline_stats()

    print(f"\n{n_draws} draws served in {farm.launches} kernel launches "
          f"({farm.gang_launches} gang-scheduled) — "
          f"{n_draws / farm.launches:.1f} draws amortized per launch")
    print(f"deadline misses: p50={stats['p50_miss_ms']:.2f} ms, "
          f"p99={stats['p99_miss_ms']:.2f} ms over "
          f"{int(stats['served_requests'])} requests")

    # transparency: async-delivered words == the sync gang=False solo path
    solo = build_farm(gang=False)
    core, client = "lorenz", "tenant0"
    mine = np.concatenate(log[(core, client)])
    assert np.array_equal(mine, solo.draw(core, client, mine.size)), \
        "async words diverged from the solo path!"
    print(f"verified: {core}/{client} bit-identical to the sync solo path "
          f"({mine.size} words)")

    await production_tier()
    print("async demo complete.")


async def production_tier():
    """Admission + SLO + journaled crash recovery, end to end."""
    print("\n=== production tier: admission, SLO classes, crash "
          "recovery ===")
    tmp = tempfile.mkdtemp(prefix="hennc_demo_")
    jpath = pathlib.Path(tmp) / "farm.journal"

    # -- the serving process (it is about to "crash") ----------------------
    farm = build_farm(register=False)
    admission = AdmissionController(rate_words_per_s=200_000,
                                    burst_words=8_192,
                                    max_queued_rows=256)
    delivered = []
    async with AsyncOscillatorFarm(farm, admission=admission,
                                   journal=jpath) as af:
        # registrations go through the front-end so the journal records
        # each tenant's seed — recovery re-derives the identical stream
        af.register("lorenz", "tenant0", seed=100)
        af.register("chen", "tenant0", seed=100)

        # SLO classes shape the launch, never the words: the latency draw
        # on a skewed group forbids the padded group-max shape
        lat, bulk = await asyncio.gather(
            af.draw("lorenz", "tenant0", 256, deadline_ms=5, slo="latency"),
            af.draw("chen", "tenant0", 4096, deadline_ms=5, slo="bulk"))
        delivered += [("lorenz", lat), ("chen", bulk)]
        print(f"slo demo: latency draw {lat.size} words + bulk draw "
              f"{bulk.size} words; planner decisions {farm.plan_decisions}, "
              f"slo-forced {farm.slo_forced}")

        # admission: a draw past the burst allowance fails FAST with a
        # typed error and an honest backoff hint — it never queues
        try:
            await af.draw("lorenz", "tenant0", 100_000, deadline_ms=5)
        except Overloaded as e:
            print(f"admission: rejected ({e.scope} scope), "
                  f"retry_after_ms={e.retry_after_ms:.1f}")

        delivered.append(("lorenz",
                          await af.draw("lorenz", "tenant0", 300,
                                        deadline_ms=5)))
        print(f"journal: {af.journal.seq} flushes recorded at {jpath}")
        # ... and here the process dies: queued-but-unflushed demand is
        # lost (the tenant retries), everything flushed is recoverable

    # -- the recovered process: weights + journal, no crashed memory ------
    farm2 = build_farm(register=False)
    info = replay_journal(farm2, jpath)
    print(f"recovery: replayed {info['clients']} tenants to flush "
          f"#{info['flushes']} ({info['rows_replayed']} word rows "
          f"recomputed, torn_tail={info['torn_tail']})")

    # the recovered streams CONTINUE bit-identically: a solo farm that
    # served the same pre-crash draws agrees on what comes next
    solo = build_farm(gang=False, register=False)
    solo.register("lorenz", "tenant0", seed=100)
    solo.register("chen", "tenant0", seed=100)
    for core, words in delivered:
        ref = solo.draw(core, "tenant0", words.size)
        assert np.array_equal(words, ref), "pre-crash stream diverged!"
    for core in ("lorenz", "chen"):
        cont = farm2.draw(core, "tenant0", 500)
        ref = solo.draw(core, "tenant0", 500)
        assert np.array_equal(cont, ref), \
            f"{core} stream diverged after recovery!"
        print(f"verified: {core}/tenant0 continues bit-identically "
              f"after crash recovery (500 words)")


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    asyncio.run(main())
