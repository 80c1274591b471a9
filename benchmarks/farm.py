"""Cross-system oscillator-farm benchmark (BENCH_farm.json).

Sections:

* ``systems`` — one row per registered chaotic system: the registry-trained
  oscillator drawn through the fused ``ops.chaotic_bits`` path with that
  system's DSE-selected solution (the same Pareto point ``generate_farm``
  freezes into the committed farm cores), reporting words/s.  Each row also
  carries the NIST-subset quarantine verdict for the core's serving dtype
  (``repro.prng.quality``): a quarantined system ships in the farm but a
  rollout can exclude it.

* ``gang`` — the launch-overhead killer measured end to end: the largest
  gang-compatible core group (same i_dim/h_dim/dtype/config — the four 3-D
  systems) served through ``OscillatorFarm`` with gang scheduling ON vs
  OFF, at two operating points: ``coalesced`` (small tenant flushes, the
  traffic gangs exist for) and ``bulk`` (full time-block flushes).

* ``async`` — the asyncio front-end (``serve/async_frontend.py``) at the
  coalesced operating point: every tenant independently ``await draw()``s
  a small request (no manual flush coordination anywhere) and the
  deadline/threshold flusher coalesces them into one gang launch per
  round.  Reported against two sync baselines: ``per_draw`` (one launch
  per draw — what uncoordinated tenants pay without the front-end) and
  ``manual_flush`` (hand-coordinated request+flush — the coordination
  optimum the front-end is supposed to recover).  Words/s plus p50/p99
  deadline-miss latency (ms past each request's deadline at delivery).

* ``async_offload`` — the production-tier proof: with every launch padded
  to a known duration, a foreign thread measures ingress round-trips
  through the event loop WHILE a launch is in flight.  Executor offload
  (PR 6) must keep p99 under 10% of the launch duration where the on-loop
  baseline pins near 100%, words must stay bit-identical across offload
  on/off/solo, and a low queued-rows ceiling must shed overload with
  typed ``Overloaded`` rejects while admitted futures all resolve.

* ``planner`` — the demand-shaped launch planner vs the PR 3 padded
  group-max gang policy.  ``skewed`` is the operating point the planner
  exists for (one hot tenant drawing 128 word rows per flush, three cold
  tenants at 8 — the group-max policy makes the cold cores compute 16x
  overdraw); ``uniform`` checks the no-regression side (the planner must
  keep picking the padded launch).  The ``GangCostModel`` is fitted from
  real launches first, so decisions reflect this machine's launch
  overhead.

* ``sharded`` — device-sharded gang launches: the gang group's coalesced
  operating point at every available forced host device count (the CI
  sharded leg forces 4 via ``XLA_FLAGS``), gated on bit-identity to the
  1-device gang path, launches/flush invariance as devices scale, and
  words/s scaling where the host has the CPUs to show it.

* ``lattice`` — block-coupled oscillator lattices (the MXU arm of the
  design space): a 32-node ring of Chen cores (I=96, H=256) drawn through
  the fused path with each compute unit's DSE-selected solution, reporting
  vpu-vs-mxu words/s next to the cycle-model prediction (``select_config``
  must pick mxu on this shape for the gate to pass), a >= 24-member
  stacked-gang bit-identity check against solo lattice draws, and the
  stacked-layout VMEM cliff: the core count where one
  ``chaotic_ann_gang_stacked_pallas`` launch exceeds the VMEM budget and
  the planner must fall back to the lane-concat layout.

* ``resilience`` — the self-healing layer under a seeded fault storm:
  words/s and p99 round latency before / during / after a 10%-transient
  launch-failure storm with one poisoned core (its monitor samples
  bit-masked so the online NIST gate condemns it).  Gated on the PR 9
  acceptance bars: quarantine + standby rotation within 3 flushes,
  degraded throughput >= 0.5x clean, and every delivered word
  bit-identical to fault-free solo runs (rotation split included).

All timed flushes separate warmup/compile from steady state: the first
flush (XLA compiles here) is reported as ``ms_first_flush``, steady-state
``words_per_s`` starts after one further warm flush.  Delivered words are
verified bit-identical to ``gang=False`` before any timing.

CPU interpret mode: numbers are functional-relative, not TPU performance;
relative ordering (and the gang/planner ratios) is still meaningful.
"""
import asyncio
import json
import pathlib
import time

import jax.numpy as jnp
import numpy as np

from repro.core.chaotic import SYSTEMS
from repro.core.dse import (CostModel, GangCostModel, LatencyModel,
                            measure_candidate, select)
from repro.kernels.ops import chaotic_bits
from repro.prng.stream import _splitmix_seeds, default_params
from repro.serve.farm import OscillatorFarm, _compat_key
from repro.serve.tracer import TIMERS

try:
    from benchmarks.common import emit, time_fn
except ModuleNotFoundError:          # invoked as `python benchmarks/farm.py`
    from common import emit, time_fn

LANES_PER_CLIENT = 128
HOT_ROWS, COLD_ROWS = 128, 8      # the skewed-demand operating point
UNIFORM_ROWS = 16
ASYNC_ROWS = 8                    # small per-tenant async draws (coalesced)
ASYNC_DEADLINE_MS = 5.0


def _system_rows(n_streams, n_steps, p, lm, cm, nist_words):
    """Per-system fused-draw words/s + quarantine verdicts."""
    table = {}
    n_words = (n_steps // 2) * n_streams
    for name in sorted(SYSTEMS):
        params = {k: jnp.asarray(v)
                  for k, v in default_params(system=name).items()}
        i_dim, h_dim = params["w1"].shape
        cand = select(i_dim, h_dim, "pareto", p=p,
                      latency_model=lm, cost_model=cm)
        dtype = jnp.dtype(cand.dtype_name)
        x0 = _splitmix_seeds(jnp.uint32(1), n_streams, i_dim).astype(dtype)

        def draw():
            words, _ = chaotic_bits(params, x0, n_steps,
                                    backend="pallas_interpret", config=cand)
            return words

        us = time_fn(draw, n_iters=3, warmup=1)
        words_per_s = n_words / (us / 1e6)
        if nist_words:
            from repro.prng.quality import nist_gate
            gate = nist_gate(name, cand.dtype_name, n_words=nist_words,
                             backend="pallas_interpret")
            quarantined, failed = gate["quarantined"], gate["failed_tests"]
        else:
            quarantined, failed = None, None      # smoke mode: not gated
        table[name] = {
            "i_dim": i_dim, "h_dim": h_dim,
            "dtype": cand.dtype_name, "compute_unit": cand.compute_unit,
            "s_block": cand.s_block, "t_block": cand.t_block,
            "unroll": cand.unroll,
            "words_per_s": words_per_s,
            "modeled_samples_per_s": measure_candidate(cand)["samples_per_sec"],
            "quarantined": quarantined,
            "nist_failed_tests": failed,
        }
        emit(f"farm/{name}_words_per_s", us,
             f"I={i_dim};H={h_dim};dtype={cand.dtype_name};"
             f"words_per_s={words_per_s:.3e};quarantined={quarantined}")
    return table


def _compatible_group(p, lm, cm):
    """Largest set of systems sharing one gang-compatibility key."""
    groups = {}
    for name in sorted(SYSTEMS):
        params = default_params(system=name)
        i_dim, h_dim = params["w1"].shape
        cand = select(i_dim, h_dim, "pareto", p=p,
                      latency_model=lm, cost_model=cm)
        groups.setdefault((i_dim, h_dim, cand), []).append(name)
    (i_dim, h_dim, cand), members = max(groups.items(),
                                        key=lambda kv: len(kv[1]))
    return members, cand


def _build_farm(group, cand, n_clients, gang, mesh=None, **farm_kw):
    farm = OscillatorFarm(gang=gang, **farm_kw)
    for name in group:
        farm.add_core(name, default_params(system=name), config=cand,
                      dtype=jnp.dtype(cand.dtype_name),
                      lanes_per_client=LANES_PER_CLIENT,
                      backend="pallas_interpret", mesh=mesh)
        for j in range(n_clients):
            farm.register(name, f"c{j}", seed=100 + j)
    return farm


def _flush_once(farm, group, n_clients, words_by_core):
    for name in group:
        for j in range(n_clients):
            farm.request(name, f"c{j}", words_by_core[name])
    return farm.flush()


def _interleaved_flushes(farms, group, n_clients, words_by_core, n_iters,
                         cold):
    """Time flushes of several farms, interleaved so host drift cancels.

    ``cold=True`` is cold-start timing: every flush pays its demand's
    launches.  Repeating identical skewed traffic would let the padded
    group-max policy turn overdraw into prefetch (cold tenants are served
    from buffer for the next t_block//2 / rows flushes), measuring buffer
    amortization instead of launch shaping — the uniform point's regime.
    So each iteration restores the same post-registration snapshot first
    and every timed flush serves the full demand vector with cold
    buffers: the launch-shape cost the planner actually optimizes.
    Restore and request queueing happen OUTSIDE the timed region.

    Returns {label: {ms_first_flush, ms_per_flush, launches_per_flush}}:
    the first flush (XLA compiles, caches build) apart from the
    steady-state median.
    """
    snaps = ({label: farm.snapshot() for label, farm in farms.items()}
             if cold else None)
    launches = {}

    def once(label):
        farm = farms[label]
        if cold:
            farm.restore(snaps[label])
        for name in group:
            for j in range(n_clients):
                farm.request(name, f"c{j}", words_by_core[name])
        l0 = farm.launches
        t0 = time.perf_counter()
        farm.flush()
        dt = (time.perf_counter() - t0) * 1e3
        launches[label] = float(farm.launches - l0)
        return dt

    first = {label: once(label) for label in farms}   # compile + caches
    for label in farms:                               # warm
        once(label)
    ts = {label: [] for label in farms}
    for _ in range(n_iters):
        for label in farms:
            ts[label].append(once(label))
    out = {}
    for label in farms:
        s = sorted(ts[label])
        out[label] = {"ms_first_flush": first[label],
                      "ms_per_flush": s[len(s) // 2],
                      "launches_per_flush": launches[label]}
    return out


def _assert_bit_identical(a, b):
    for core in a:
        for client in a[core]:
            np.testing.assert_array_equal(a[core][client],
                                          b[core][client])


def _gang_section(n_streams, p, lm, cm, smoke):
    group, cand = _compatible_group(p, lm, cm)
    n_clients = max(1, n_streams // LANES_PER_CLIENT)
    uniform = {name: 16 * LANES_PER_CLIENT + 37 for name in group}

    # Bit-identity gate before any timing: same traffic, both launch modes.
    farms = {g: _build_farm(group, cand, n_clients, g) for g in (True, False)}
    outs = {g: _flush_once(farms[g], group, n_clients, uniform)
            for g in (True, False)}
    _assert_bit_identical(outs[True], outs[False])
    key = _compat_key(farms[True].services[group[0]])

    protocols = {"coalesced": 16}
    if not smoke:
        protocols["bulk"] = cand.t_block // 2
    n_iters = 3 if smoke else 9
    result = {
        "group": group,
        "compat_key": {"i_dim": cand.i_dim, "h_dim": cand.h_dim,
                       "dtype": cand.dtype_name,
                       "compute_unit": cand.compute_unit,
                       "s_block": cand.s_block, "t_block": cand.t_block,
                       "unroll": cand.unroll,
                       "full_key": [str(x) for x in key]},
        "n_streams_per_core": n_clients * LANES_PER_CLIENT,
        "bit_identical": True,
        "protocols": {},
    }
    for proto, rows in protocols.items():
        words = {name: rows * LANES_PER_CLIENT for name in group}
        words_per_flush = len(group) * n_clients * rows * LANES_PER_CLIENT
        gang_farms = {g: _build_farm(group, cand, n_clients, g)
                      for g in (True, False)}
        timings = _interleaved_flushes(gang_farms, group, n_clients, words,
                                       n_iters, cold=False)
        stats = {g: dict(timings[g],
                         words_per_s=words_per_flush
                         / (timings[g]["ms_per_flush"] / 1e3))
                 for g in (True, False)}
        stats[True]["dispatch_misses"] = gang_farms[True].dispatch_misses
        speedup = (stats[True]["words_per_s"] /
                   stats[False]["words_per_s"])
        result["protocols"][proto] = {
            "rows_per_client_flush": rows,
            "words_per_flush": words_per_flush,
            "gang": stats[True],
            "per_core": stats[False],
            "speedup": speedup,
        }
        emit(f"farm/gang_{proto}", stats[True]["ms_per_flush"] * 1e3,
             f"group={len(group)};speedup={speedup:.2f}x;"
             f"gang_words_per_s={stats[True]['words_per_s']:.3e};"
             f"per_core_words_per_s={stats[False]['words_per_s']:.3e}")
    result["speedup"] = max(pr["speedup"]
                            for pr in result["protocols"].values())
    return result


def _async_section(n_streams, p, lm, cm, smoke):
    """Uncoordinated async tenants vs per-draw and manual-flush baselines.

    Operating point: every tenant draws ``ASYNC_ROWS`` word rows per round
    with a ``ASYNC_DEADLINE_MS`` deadline and no flush calls anywhere; the
    front-end's row threshold is one full round of demand, so the launch
    fires the moment the round's last tenant submits (the deadline is the
    stragglers' backstop).  ``per_draw`` serves the same traffic one
    ``farm.draw`` (= one launch) at a time; ``manual_flush`` queues the
    whole round by hand and flushes once — the coordination optimum.
    Deadline-miss latency is measured per request at delivery time.
    """
    from repro.serve.async_frontend import (AsyncOscillatorFarm,
                                            percentile)

    group, cand = _compatible_group(p, lm, cm)
    n_clients = max(1, n_streams // LANES_PER_CLIENT)
    tenants = [(name, f"c{j}") for name in group for j in range(n_clients)]
    words_per_draw = ASYNC_ROWS * LANES_PER_CLIENT
    words_per_round = len(tenants) * words_per_draw
    round_rows = len(group) * ASYNC_ROWS     # launch rows of one full round
    n_rounds = 3 if smoke else 9

    # --- bit-identity gate: async-delivered words == gang=False solo ------
    gate_farm = _build_farm(group, cand, n_clients, True)
    delivered = {}

    async def _round(af):
        futs = [af.submit(core, cl, words_per_draw,
                          deadline_ms=ASYNC_DEADLINE_MS)
                for core, cl in tenants]
        return list(await asyncio.gather(*futs))

    async def _gate():
        async with AsyncOscillatorFarm(gate_farm,
                                       auto_flush_rows=round_rows) as af:
            for _ in range(2):               # round 2 hits warmed caches
                for (core, cl), w in zip(tenants, await _round(af)):
                    delivered.setdefault((core, cl), []).append(
                        np.asarray(w))

    asyncio.run(_gate())
    solo = _build_farm(group, cand, n_clients, False)
    for (core, cl), chunks in delivered.items():
        mine = np.concatenate(chunks)
        np.testing.assert_array_equal(mine, solo.draw(core, cl, mine.size))

    # --- async timing ------------------------------------------------------
    stats = {}
    farm = _build_farm(group, cand, n_clients, True)
    times, first, miss = [], [None], [0.0, 0.0, 0.0]

    async def _bench():
        async with AsyncOscillatorFarm(farm,
                                       auto_flush_rows=round_rows) as af:
            t0 = time.perf_counter()
            await _round(af)                               # compile
            first[0] = (time.perf_counter() - t0) * 1e3
            await _round(af)                               # warm
            n_before = len(af.miss_samples_ms())
            for _ in range(n_rounds):
                t0 = time.perf_counter()
                await _round(af)
                times.append((time.perf_counter() - t0) * 1e3)
            timed = af.miss_samples_ms()[n_before:]
            miss[0] = percentile(timed, 0.50)
            miss[1] = percentile(timed, 0.99)
            miss[2] = max(timed)

    l0 = farm.launches
    asyncio.run(_bench())
    ts = sorted(times)
    stats["async"] = {
        "ms_first_round": first[0],
        "ms_per_round": ts[len(ts) // 2],
        "words_per_s": words_per_round / (ts[len(ts) // 2] / 1e3),
        "launches_per_round": (farm.launches - l0) / (n_rounds + 2),
        "p50_miss_ms": miss[0], "p99_miss_ms": miss[1],
        "max_miss_ms": miss[2],
    }

    # --- sync baselines ----------------------------------------------------
    def _baseline(mode):
        bfarm = _build_farm(group, cand, n_clients, True)

        def round_():
            if mode == "per_draw":
                for core, cl in tenants:
                    bfarm.draw(core, cl, words_per_draw)
            else:                            # manual_flush: hand-coalesced
                for core, cl in tenants:
                    bfarm.request(core, cl, words_per_draw)
                bfarm.flush()

        t0 = time.perf_counter()
        round_()
        first_ms = (time.perf_counter() - t0) * 1e3
        round_()
        l0 = bfarm.launches
        bts = []
        for _ in range(n_rounds):
            t0 = time.perf_counter()
            round_()
            bts.append((time.perf_counter() - t0) * 1e3)
        bts.sort()
        return {"ms_first_round": first_ms,
                "ms_per_round": bts[len(bts) // 2],
                "words_per_s": words_per_round / (bts[len(bts) // 2] / 1e3),
                "launches_per_round": (bfarm.launches - l0) / n_rounds}

    stats["per_draw"] = _baseline("per_draw")
    stats["manual_flush"] = _baseline("manual_flush")

    speedup = (stats["async"]["words_per_s"]
               / stats["per_draw"]["words_per_s"])
    vs_manual = (stats["async"]["words_per_s"]
                 / stats["manual_flush"]["words_per_s"])
    result = {
        "group": group,
        "n_tenants": len(tenants),
        "rows_per_draw": ASYNC_ROWS,
        "deadline_ms": ASYNC_DEADLINE_MS,
        "auto_flush_rows": round_rows,
        "words_per_round": words_per_round,
        "bit_identical": True,
        **stats,
        "speedup_vs_per_draw": speedup,
        "ratio_vs_manual_flush": vs_manual,
    }
    emit("farm/async_coalesced", stats["async"]["ms_per_round"] * 1e3,
         f"tenants={len(tenants)};speedup_vs_per_draw={speedup:.2f}x;"
         f"vs_manual={vs_manual:.2f}x;"
         f"async_words_per_s={stats['async']['words_per_s']:.3e};"
         f"p99_miss_ms={stats['async']['p99_miss_ms']:.2f}")
    return result


SLOW_LAUNCH_S = 0.25              # injected launch duration (offload proof)


class _SlowFlush:
    """Wrap ``farm.flush`` so every launch pass (``deliver=False``) takes
    a known ``delay_s`` — the offload section needs a launch long enough
    that loop (un)responsiveness during it is unambiguous."""

    def __init__(self, farm, delay_s):
        self.farm = farm
        self.orig = farm.flush
        self.delay_s = delay_s

    def __call__(self, *a, **kw):
        if not kw.get("deliver", True):
            time.sleep(self.delay_s)
        return self.orig(*a, **kw)


def _offload_probe(offload, group, cand, n_clients, n_rounds, delay_s):
    """Ingress latency while a slow launch is in flight, one mode.

    A foreign thread (this one) submits a big draw, waits for its launch
    to be in flight, then measures round-trips of zero-word draws through
    the event loop — the loop-liveness probe behind every ingress path
    (submit scheduling, draw_sync wakeups, cancellation, deadlines).
    With ``offload=True`` the launch runs on the worker thread and probes
    return in microseconds; with ``offload=False`` (the PR 5 on-loop
    behavior) the first probe blocks for the whole launch.

    Returns (probe samples ms, delivered words per round).
    """
    from repro.serve.async_frontend import AsyncOscillatorFarm

    farm = _build_farm(group, cand, n_clients, True)
    slow = _SlowFlush(farm, delay_s)
    farm.flush = slow
    af = AsyncOscillatorFarm(farm, offload=offload).start_thread()
    probes, words = [], []
    core0 = group[0]
    try:
        for _ in range(n_rounds):
            dfut = asyncio.run_coroutine_threadsafe(
                af.draw(core0, "c0", ASYNC_ROWS * LANES_PER_CLIENT,
                        deadline_ms=0), af.loop)
            deadline = time.perf_counter() + 4 * delay_s + 5.0
            while not af.in_flight and not dfut.done():
                if time.perf_counter() > deadline:
                    raise RuntimeError("launch never became in-flight")
                time.sleep(1e-4)
            while af.in_flight and not dfut.done():
                t0 = time.perf_counter()
                asyncio.run_coroutine_threadsafe(
                    af.draw(core0, "c0", 0), af.loop).result(30.0)
                probes.append((time.perf_counter() - t0) * 1e3)
            words.append(np.asarray(dfut.result(30.0)))
    finally:
        farm.flush = slow.orig
        af.close()
    return probes, words


def _backpressure_point(group, cand, n_clients, delay_s):
    """Overload the front-end past a low queued-rows ceiling: over-limit
    submits must fail fast with ``Overloaded`` (typed, with a retry hint)
    while every admitted future still resolves with its exact words."""
    from repro.serve.admission import AdmissionController, Overloaded
    from repro.serve.async_frontend import AsyncOscillatorFarm

    farm = _build_farm(group, cand, n_clients, True)
    slow = _SlowFlush(farm, delay_s)
    farm.flush = slow
    ceiling = 2 * ASYNC_ROWS
    ac = AdmissionController(max_queued_rows=ceiling)
    af = AsyncOscillatorFarm(farm, admission=ac).start_thread()
    n_offered = 32
    words_per_draw = ASYNC_ROWS * LANES_PER_CLIENT
    served = rejected = failed = 0
    try:
        futs = [asyncio.run_coroutine_threadsafe(
                    af.draw(group[0], "c0", words_per_draw, deadline_ms=1.0),
                    af.loop)
                for _ in range(n_offered)]
        for f in futs:
            try:
                served += int(f.result(60.0).size == words_per_draw)
            except Overloaded as e:
                rejected += 1
                assert e.retry_after_ms >= 0.0 and e.scope == "farm"
            except Exception:            # noqa: BLE001 - tallied for the gate
                failed += 1
    finally:
        farm.flush = slow.orig
        af.close()
    stats = ac.stats()
    return {"offered": n_offered, "queued_rows_ceiling": ceiling,
            "served": served, "rejected": rejected,
            "failed_other": failed,
            "admitted": stats["admitted"],
            "rejected_farm": stats["rejected_farm"],
            "all_admitted_resolved": failed == 0
            and served + rejected == n_offered}


def _async_offload_section(n_streams, p, lm, cm, smoke):
    """The production-tier proof: executor offload keeps ingress live
    during slow launches, and admission control sheds overload.

    ``offload`` vs ``on_loop`` run identical traffic against a launch
    padded to ``SLOW_LAUNCH_S``; the p99 ingress probe (foreign-thread
    round-trip through the event loop while the launch is in flight) is
    the headline — the acceptance bar is p99 < 10% of the launch
    duration, where the on-loop baseline is pinned near 100%.  Delivered
    words are checked bit-identical across both modes and against the
    ``gang=False`` solo path before anything is reported.
    """
    from repro.serve.async_frontend import percentile

    group, cand = _compatible_group(p, lm, cm)
    n_clients = max(1, n_streams // LANES_PER_CLIENT)
    n_rounds = 2 if smoke else 4
    delay_s = SLOW_LAUNCH_S / (2 if smoke else 1)

    modes = {}
    delivered = {}
    for label, offload in (("offload", True), ("on_loop", False)):
        probes, words = _offload_probe(offload, group, cand, n_clients,
                                       n_rounds, delay_s)
        delivered[label] = words
        modes[label] = {
            "probe_samples": len(probes),
            "ingress_p50_ms": percentile(probes, 0.50),
            "ingress_p99_ms": percentile(probes, 0.99),
            "ingress_max_ms": max(probes, default=0.0),
        }

    # bit-identity: offload on == off == gang=False solo, round by round
    solo = _build_farm(group, cand, n_clients, False)
    bit_identical = True
    for a, b in zip(delivered["offload"], delivered["on_loop"]):
        ref = solo.draw(group[0], "c0", a.size)
        if not (np.array_equal(a, b) and np.array_equal(a, ref)):
            bit_identical = False
    back = _backpressure_point(group, cand, n_clients, delay_s / 4)

    launch_ms = delay_s * 1e3
    p99_frac = modes["offload"]["ingress_p99_ms"] / launch_ms
    result = {
        "group": group,
        "launch_ms_injected": launch_ms,
        "rounds": n_rounds,
        "bit_identical": bit_identical,
        "offload": modes["offload"],
        "on_loop": modes["on_loop"],
        "offload_p99_frac_of_launch": p99_frac,
        "backpressure": back,
    }
    emit("farm/async_offload", modes["offload"]["ingress_p99_ms"] * 1e3,
         f"p99_frac_of_launch={p99_frac:.4f};"
         f"on_loop_p99_ms={modes['on_loop']['ingress_p99_ms']:.1f};"
         f"bit_identical={bit_identical};"
         f"backpressure_rejects={back['rejected']}")
    return result


def _sharded_section(n_streams, p, lm, cm, smoke):
    """One logical gang launch across every forced host device.

    Runs the gang group's coalesced operating point at every available
    device count in {1, 2, 4, 8} (1 = the plain unsharded gang path; the
    CI sharded leg forces 4 via
    ``XLA_FLAGS=--xla_force_host_platform_device_count=4``).  Three
    invariants are recorded for the gate:

    * **bit-identity** — delivered words at every device count equal the
      1-device gang path, stream for stream (the sharded kernels' whole
      contract);
    * **launches/flush invariance** — sharding must not fragment the
      logical launch: the farm pays the same launches per flush at every
      device count;
    * **scaling** — words/s at 4 devices vs 1.  Forced host devices
      time-slice the physical cores, so the >= 2x bar arms only when the
      host actually has >= 4 CPUs (``speedup_gate_armed`` records the
      decision; the CI leg runs on such a host).

    The fitted cross-device launch overhead (``GangCostModel.fit`` with
    the largest mesh) is surfaced so planner decisions on a mesh are
    auditable.
    """
    import os

    import jax
    from jax.sharding import Mesh

    group, cand = _compatible_group(p, lm, cm)
    n_clients = max(1, n_streams // LANES_PER_CLIENT)
    avail = jax.device_count()
    counts = [n for n in (1, 2, 4, 8) if n <= avail]
    rows = 16                                  # the coalesced point
    words = {name: rows * LANES_PER_CLIENT for name in group}
    words_per_flush = len(group) * n_clients * rows * LANES_PER_CLIENT
    n_iters = 3 if smoke else 9
    host_cpus = os.cpu_count() or 1

    def build(n_dev):
        mesh = (None if n_dev == 1
                else Mesh(np.array(jax.devices()[:n_dev]), ("data",)))
        return _build_farm(group, cand, n_clients, True, mesh=mesh)

    # --- bit-identity gate: every device count vs the 1-device path -------
    outs = {}
    gate_farms = {n: build(n) for n in counts}
    for n, farm in gate_farms.items():
        outs[n] = _flush_once(farm, group, n_clients, words)
    bit_identical = True
    for n in counts[1:]:
        try:
            _assert_bit_identical(outs[n], outs[1])
        except AssertionError:
            bit_identical = False
    ganged = all(f.gang_launches > 0 for f in gate_farms.values())

    # --- timing: identical traffic, interleaved across device counts ------
    farms = {f"dev{n}": build(n) for n in counts}
    timings = _interleaved_flushes(farms, group, n_clients, words,
                                   n_iters, cold=False)
    per_count = {}
    for n in counts:
        t = timings[f"dev{n}"]
        per_count[str(n)] = dict(
            t, words_per_s=words_per_flush / (t["ms_per_flush"] / 1e3))
    launch_counts = {v["launches_per_flush"] for v in per_count.values()}

    speedup = (per_count["4"]["words_per_s"] / per_count["1"]["words_per_s"]
               if "4" in per_count else None)
    armed = 4 in counts and host_cpus >= 4
    result = {
        "group": group,
        "device_counts": counts,
        "host_cpus": host_cpus,
        "rows_per_client_flush": rows,
        "words_per_flush": words_per_flush,
        "bit_identical": bit_identical,
        "ganged_on_mesh": ganged,
        "per_device_count": per_count,
        "launches_per_flush_invariant": len(launch_counts) == 1,
        "speedup_4dev_vs_1dev": speedup,
        "speedup_gate_armed": armed,
    }
    if not armed and speedup is not None:
        result["speedup_gate_skip_reason"] = (
            f"host has {host_cpus} CPUs: forced devices time-slice, "
            f"words/s cannot scale")
    if counts[-1] > 1:
        mesh = Mesh(np.array(jax.devices()[:counts[-1]]), ("data",))
        model = GangCostModel.fit(cand, backend="pallas_interpret",
                                  mesh=mesh)
        result["fitted_cross_dev_overhead_cycles"] = (
            model.cross_dev_overhead_cycles)
    emit("farm/sharded",
         per_count[str(counts[-1])]["ms_per_flush"] * 1e3,
         f"devices={counts};bit_identical={bit_identical};"
         f"launches_invariant={result['launches_per_flush_invariant']};"
         f"speedup_4v1={'n/a' if speedup is None else f'{speedup:.2f}x'};"
         f"gate_armed={armed}")
    return result


def _planner_section(n_streams, p, lm, cm, smoke, profile=False):
    """Demand-shaped planner vs the PR 3 padded group-max gang policy.

    Measured on the f32 variant of the group's DSE solution: CPU interpret
    mode emulates bf16 by converting around every vector op, which makes
    per-op dispatch dominate and a C-tall stacked sweep cost the same as a
    single-core one — hiding exactly the overdraw compute the planner
    eliminates.  f32 keeps interpret costs proportional to array work, the
    regime a real TPU is in for either dtype (the gang section keeps the
    DSE-chosen bf16).
    """
    import dataclasses
    group, cand = _compatible_group(p, lm, cm)
    cand = dataclasses.replace(cand, dtype_bytes=4)
    n_clients = max(1, n_streams // LANES_PER_CLIENT)
    hot = group[0]
    skewed = {name: (HOT_ROWS if name == hot else COLD_ROWS)
              * LANES_PER_CLIENT for name in group}
    uniform = {name: UNIFORM_ROWS * LANES_PER_CLIENT for name in group}
    n_iters = 3 if smoke else 9

    # Launch-cost model fitted from real launches of this exact candidate,
    # so planner decisions reflect this machine (paper: estimate-then-
    # validate, applied to the launch model).
    model = GangCostModel.fit(cand, backend="pallas_interpret")
    result = {
        "group": group, "hot_core": hot,
        "dtype": cand.dtype_name,
        "rows": {"hot": HOT_ROWS, "cold": COLD_ROWS,
                 "uniform": UNIFORM_ROWS},
        "model": {"launch_overhead_cycles": model.launch_overhead_cycles,
                  "cell_overhead_cycles": model.cell_overhead_cycles,
                  "stacked_step_scale": model.stacked_step_scale,
                  "freeze_row_cycles": model.freeze_row_cycles,
                  "sec_per_cycle": model.sec_per_cycle},
    }

    # Bit-identity gate across two skewed flush rounds (the second round
    # exercises buffered state from the first) before any timing.
    check = {"planner": _build_farm(group, cand, n_clients, True,
                                    gang_cost_model=model),
             "solo": _build_farm(group, cand, n_clients, False)}
    for _ in range(2):
        outs = {k: _flush_once(f, group, n_clients, skewed)
                for k, f in check.items()}
        _assert_bit_identical(outs["planner"], outs["solo"])
    result["bit_identical"] = True

    for point, words in (("skewed", skewed), ("uniform", uniform)):
        words_per_flush = n_clients * sum(words.values())
        farms = {"planner": _build_farm(group, cand, n_clients, True,
                                        gang_cost_model=model),
                 "policy": _build_farm(group, cand, n_clients, True,
                                       planner=False)}
        timings = _interleaved_flushes(
            farms, group, n_clients, words,
            n_iters if point == "skewed" else max(n_iters, 7),
            cold=(point == "skewed"))
        stats = {}
        for label, farm in farms.items():
            stats[label] = dict(
                timings[label],
                words_per_s=words_per_flush
                / (timings[label]["ms_per_flush"] / 1e3),
                dispatch_misses=farm.dispatch_misses,
                decisions=farm.plan_decisions,
            )
        speedup = (stats["planner"]["words_per_s"]
                   / stats["policy"]["words_per_s"])
        result[point] = {
            "words_per_flush": words_per_flush,
            "timing": "cold_start" if point == "skewed" else "steady_state",
            "planner": stats["planner"], "policy": stats["policy"],
            "speedup": speedup,
        }
        emit(f"farm/planner_{point}", stats["planner"]["ms_per_flush"] * 1e3,
             f"speedup={speedup:.2f}x;"
             f"planner_words_per_s={stats['planner']['words_per_s']:.3e};"
             f"policy_words_per_s={stats['policy']['words_per_s']:.3e};"
             f"decisions={stats['planner']['decisions']}")

    if profile:
        farm = _build_farm(group, cand, n_clients, True,
                           gang_cost_model=model, profile=True)
        _interleaved_flushes({"profile": farm}, group, n_clients, skewed,
                             n_iters, cold=True)
        prof = farm.profile_stats
        n = max(prof["flushes"], 1.0)
        result["profile_ms_per_flush"] = {k: prof[k] / n * 1e3
                                          for k in TIMERS}
        emit("farm/planner_profile", 0.0,
             ";".join(f"{k}={v:.2f}ms"
                      for k, v in result["profile_ms_per_flush"].items()))
    return result


LATTICE_SPEC = "chen@ring32"      # 32-node ring of Chen cores: I=96, H=256
LATTICE_LANES = 128               # streams per lattice draw


def _lattice_section(p, lm, cm, smoke):
    """Block-coupled lattice: vpu-vs-mxu on model AND measurement.

    The scalar systems never let the MXU win (I, H too small: 128-padding
    swamps the useful MACs), so this section is where the mxu arm of the
    DSE earns its keep.  At 32 ring-coupled Chen nodes the contraction is
    genuinely MXU-shaped and ``select_config`` must pick mxu on the cycle
    model; the measured run re-draws the same traffic with each unit's
    selected solution (same s_block/t_block/f32 for both, so the timing
    isolates the compute-unit choice).

    Measured-number caveat (recorded in the section): CPU interpret mode
    executes the vpu path's ~I+H broadcast-FMA passes as that many XLA
    ops per step where the mxu path issues a handful of matmuls, so the
    measured mxu win is partly op-dispatch economics; on a real TPU the
    same ordering comes from the 128x128 systolic array instead.  The
    cycle model is the hardware-facing claim; the measured run checks the
    ordering end to end.  The mxu-vs-vpu measured gate arms only on hosts
    with >= 4 CPUs (same discipline as the sharded scaling gate); raw
    numbers are always recorded.
    """
    import dataclasses
    import os

    from repro.core.ann import lattice_meta_tuple
    from repro.core.dse import (VMEM_USABLE, select_config,
                                stacked_gang_vmem_bytes)
    from repro.kernels import ops

    params = {k: jnp.asarray(v)
              for k, v in default_params(system=LATTICE_SPEC).items()}
    i_dim, h_dim = params["w1"].shape
    n_nodes, base_dim, topo, strength = lattice_meta_tuple(
        np.asarray(params["lattice_meta"]))
    lanes = LATTICE_LANES
    host_cpus = os.cpu_count() or 1

    cands = {unit: select_config(i_dim, h_dim, s_total=lanes, unit=unit,
                                 n_nodes=n_nodes)
             for unit in ("vpu", "mxu")}
    selected = select_config(i_dim, h_dim, s_total=lanes, n_nodes=n_nodes)

    # Measured draw: identical blocking and f32 for both units (interpret
    # mode's emulated bf16 would bill per-op conversions to whichever unit
    # issues more ops); t_block clamped hard because interpret-mode trace
    # cost grows ~quadratically in the unrolled body (t_block * (I + H)
    # ops — at I=96, H=256 a t_block of 16 already costs minutes to trace).
    t_blk = 4 if smoke else 8
    n_steps = 4 * t_blk
    n_words = (n_steps // 2) * lanes
    units = {}
    for unit, cand in cands.items():
        run_cand = dataclasses.replace(cand, p=0, t_block=t_blk, unroll=2,
                                       dtype_bytes=4)   # s_block = lanes
        x0 = _splitmix_seeds(jnp.uint32(1), lanes, i_dim).astype(
            jnp.dtype(run_cand.dtype_name))

        def draw(c=run_cand, x=x0):
            words, _ = chaotic_bits(params, x, n_steps,
                                    backend="pallas_interpret", config=c)
            return np.asarray(words)

        us = time_fn(draw, n_iters=3, warmup=1)
        meas = measure_candidate(cand)
        units[unit] = {
            "s_block": cand.s_block, "t_block": cand.t_block,
            "unroll": cand.unroll, "dtype": cand.dtype_name,
            "modeled_cycles_per_step": meas["cycles_per_step"],
            "modeled_samples_per_s": meas["samples_per_sec"],
            "words_per_s": n_words / (us / 1e6),
        }

    mxu_wins_model = (units["mxu"]["modeled_samples_per_s"]
                      > units["vpu"]["modeled_samples_per_s"])
    measured_speedup = (units["mxu"]["words_per_s"]
                        / units["vpu"]["words_per_s"])
    armed = host_cpus >= 4

    # --- >= 24-member stacked-gang bit-identity vs solo lattice draws -----
    C = 24
    gc = dataclasses.replace(cands["vpu"], p=0, t_block=t_blk, unroll=2,
                             dtype_bytes=4)              # s_block = lanes
    dtype = jnp.dtype(gc.dtype_name)
    x0_all = _splitmix_seeds(jnp.uint32(7), C * lanes, i_dim).astype(
        dtype).reshape(C, lanes, i_dim)
    gang_params = {k: jnp.stack([params[k]] * C)
                   for k in ("w1", "b1", "w2", "b2")}
    gang_params["coupling"] = params["coupling"]
    gang_params["lattice_meta"] = params["lattice_meta"]
    gwords, gstate = ops.chaotic_bits_gang_stacked(
        gang_params, x0_all, n_steps, jnp.zeros((C, lanes), jnp.uint32),
        backend="pallas_interpret", config=gc)
    gwords, gstate = np.asarray(gwords), np.asarray(gstate)
    gang_ok = True
    for ci in range(C):
        swords, sstate = chaotic_bits(params, x0_all[ci], n_steps,
                                      backend="pallas_interpret", config=gc)
        gang_ok &= bool(np.array_equal(gwords[:, ci, :], np.asarray(swords))
                        and np.array_equal(gstate[ci], np.asarray(sstate)))

    # --- stacked-layout VMEM cliff (the planner's fallback threshold) -----
    cliff = 1
    while stacked_gang_vmem_bytes(cands["vpu"], cliff) <= VMEM_USABLE:
        cliff += 1
        if cliff > 1_000_000:       # unreachable guard: tiny candidate
            cliff = None
            break

    result = {
        "system": LATTICE_SPEC,
        "n_nodes": n_nodes, "base_dim": base_dim, "topology": topo,
        "coupling_strength": strength,
        "i_dim": i_dim, "h_dim": h_dim, "lanes": lanes,
        "n_steps_measured": n_steps,
        "units": units,
        "selected_compute_unit": selected.compute_unit,
        "mxu_wins_model": bool(mxu_wins_model),
        "measured_speedup_mxu_vs_vpu": measured_speedup,
        "mxu_wins_measured": bool(measured_speedup > 1.0),
        "speedup_gate_armed": bool(armed),
        "gang_members": C,
        "gang_bit_identical": gang_ok,
        "stacked_vmem_cliff_cores": cliff,
        "stacked_gang_vmem_at_cliff": (
            None if cliff is None
            else stacked_gang_vmem_bytes(cands["vpu"], cliff)),
        "vmem_usable_bytes": VMEM_USABLE,
        "measured_note": (
            "CPU interpret mode: the measured mxu win is partly per-op "
            "dispatch economics (vpu issues ~I+H elementwise passes per "
            "step, mxu a handful of matmuls); on TPU hardware the same "
            "ordering comes from the systolic array. The cycle model is "
            "the hardware-facing claim."),
    }
    if not armed:
        result["speedup_gate_skip_reason"] = (
            f"host has {host_cpus} CPUs: measured vpu-vs-mxu ordering is "
            f"not trustworthy under contention")
    emit("farm/lattice", units["mxu"]["words_per_s"],
         f"spec={LATTICE_SPEC};selected={selected.compute_unit};"
         f"mxu_model_speedup="
         f"{units['mxu']['modeled_samples_per_s'] / units['vpu']['modeled_samples_per_s']:.2f}x;"
         f"mxu_measured_speedup={measured_speedup:.2f}x;"
         f"gang24_bit_identical={gang_ok};vmem_cliff_cores={cliff}")
    return result


TRANSIENT_RATE = 0.10             # the resilience storm's launch-fault coin
FAULT_SEED = 2                    # chosen so the coin lands in a short run


def _resilience_section(n_streams, p, lm, cm, smoke):
    """Self-healing under a seeded fault storm: words/s + p99 round
    latency before / during / after a 10%-transient-launch-failure storm
    with one poisoned core.

    The storm phase arms a ``FaultPlan``: every launch flips a seeded 10%
    coin (a transient failure the supervision layer must retry with
    FakeClock-disciplined backoff — real time here, but the same code
    path the FakeClock suite drives), and the first group core's monitor
    samples are bit-masked so the online NIST gate condemns it.  The
    farm must quarantine the poisoned core and rotate its standby in
    within 3 flushes, keep degraded throughput at >= 0.5x the clean
    phase, and deliver every word bit-identical to fault-free solo runs
    (the poisoned core: original-core words up to the rotation flush,
    standby-from-row-0 words after).
    """
    from repro.serve.async_frontend import (AsyncOscillatorFarm,
                                            percentile)
    from repro.serve.faults import FaultPlan
    from repro.serve.health import HealthMonitor

    group, cand = _compatible_group(p, lm, cm)
    n_clients = max(1, n_streams // LANES_PER_CLIENT)
    tenants = [(name, f"c{j}") for name in group for j in range(n_clients)]
    words_per_draw = ASYNC_ROWS * LANES_PER_CLIENT
    words_per_round = len(tenants) * words_per_draw
    round_rows = len(group) * ASYNC_ROWS
    poisoned = group[0]
    # one round delivers n_clients * words_per_draw words per core: size
    # the quality window to fill (and be judged) every round
    window = max(256, n_clients * words_per_draw)
    rounds = {"before": 3, "during": 5, "after": 3} if smoke else \
             {"before": 5, "during": 8, "after": 5}

    faults = FaultPlan(seed=FAULT_SEED, transient_rate=TRANSIENT_RATE,
                       poison={poisoned})
    faults.disarm()                        # armed only for the storm phase
    health = HealthMonitor(window_words=window, breaker_threshold=5,
                           backoff_base_ms=1.0, backoff_cap_ms=20.0)
    farm = _build_farm(group, cand, n_clients, True, faults=faults)
    farm.add_standby(poisoned, default_params(system=poisoned),
                     config=cand, dtype=jnp.dtype(cand.dtype_name),
                     lanes_per_client=LANES_PER_CLIENT,
                     backend="pallas_interpret")

    delivered = {}
    phase_times = {}
    rotated_after = [None]                 # storm flushes until rotation

    async def _round(af):
        futs = [af.submit(core, cl, words_per_draw,
                          deadline_ms=ASYNC_DEADLINE_MS)
                for core, cl in tenants]
        out = list(await asyncio.gather(*futs))
        for (core, cl), w in zip(tenants, out):
            delivered.setdefault((core, cl), []).append(np.asarray(w))

    async def _bench():
        async with AsyncOscillatorFarm(farm, offload=False, health=health,
                                       auto_flush_rows=round_rows) as af:
            await _round(af)               # compile + warm (untimed)
            for phase in ("before", "during", "after"):
                if phase == "during":
                    faults.arm()
                elif phase == "after":
                    faults.disarm()
                times = []
                for i in range(rounds[phase]):
                    t0 = time.perf_counter()
                    await _round(af)
                    times.append((time.perf_counter() - t0) * 1e3)
                    if (phase == "during" and rotated_after[0] is None
                            and farm.rotations.get(poisoned) == 1):
                        rotated_after[0] = i + 1
                phase_times[phase] = times

    asyncio.run(_bench())

    # --- bit-identity: every tenant vs fault-free solo runs ---------------
    n_rounds_total = 1 + sum(rounds.values())          # incl. warm round
    solo = _build_farm(group, cand, n_clients, False)
    standby_solo = OscillatorFarm(gang=False)
    standby_solo.add_core(poisoned, default_params(system=poisoned),
                          config=cand, dtype=jnp.dtype(cand.dtype_name),
                          lanes_per_client=LANES_PER_CLIENT,
                          backend="pallas_interpret")
    for j in range(n_clients):
        standby_solo.register(poisoned, f"c{j}", seed=100 + j)
    bit_identical = True
    for (core, cl), chunks in delivered.items():
        if core == poisoned:
            continue
        mine = np.concatenate(chunks)
        bit_identical &= bool(
            np.array_equal(mine, solo.draw(core, cl, mine.size)))
    total = n_rounds_total * words_per_draw
    ref_orig = {f"c{j}": solo.draw(poisoned, f"c{j}", total)
                for j in range(n_clients)}
    ref_stand = {f"c{j}": standby_solo.draw(poisoned, f"c{j}", total)
                 for j in range(n_clients)}
    split_found = None
    for k in range(n_rounds_total + 1):    # k = rounds before the rotation
        cut = k * words_per_draw
        if all(np.array_equal(
                np.concatenate(delivered[(poisoned, cl)]),
                np.concatenate([ref_orig[cl][:cut],
                                ref_stand[cl][:total - cut]]))
               for _, cl in tenants if _ == poisoned):
            split_found = k
            break
    bit_identical &= split_found is not None

    stats = {}
    for phase, times in phase_times.items():
        ts = sorted(times)
        stats[phase] = {
            "ms_per_round": ts[len(ts) // 2],
            "p99_round_ms": percentile(times, 0.99),
            "words_per_s": words_per_round / (ts[len(ts) // 2] / 1e3),
        }
    frac = stats["during"]["words_per_s"] / stats["before"]["words_per_s"]
    result = {
        "group": group, "poisoned_core": poisoned,
        "n_tenants": len(tenants),
        "transient_rate": TRANSIENT_RATE, "fault_seed": FAULT_SEED,
        "window_words": window,
        "rounds": rounds,
        "phases": stats,
        "injected": dict(faults.injected),
        "retries": health.stats["retries"],
        "breaker_trips": health.stats["breaker_trips"],
        "quality_quarantines": health.stats["quality_quarantines"],
        "quarantined_within_flushes": rotated_after[0],
        "rotation_split_round": split_found,
        "rotations": dict(farm.rotations),
        "degraded_words_per_s_frac": frac,
        "bit_identical": bool(bit_identical),
    }
    emit("farm/resilience", stats["during"]["ms_per_round"] * 1e3,
         f"degraded_frac={frac:.2f};"
         f"rotated_within={rotated_after[0]};"
         f"transients={faults.injected['transient']};"
         f"retries={health.stats['retries']};"
         f"during_words_per_s={stats['during']['words_per_s']:.3e}")
    return result


def run_farm(n_streams: int = 256, n_steps: int = 1024, p: int = 1,
             out_json: str | None = "BENCH_farm.json",
             smoke: bool = False, nist_words: int = 20_000,
             profile: bool = False, lattice_only: bool = False) -> dict:
    lm, cm = LatencyModel.fit(), CostModel.fit()
    if smoke:
        n_steps = min(n_steps, 256)
        nist_words = 0
    lattice = _lattice_section(p, lm, cm, smoke)
    if lattice_only:
        res = {"config": {"n_streams": n_streams, "pareto_p": p,
                          "backend": "pallas_interpret", "smoke": smoke,
                          "lattice_only": True},
               "lattice": lattice}
        if out_json:
            pathlib.Path(out_json).write_text(json.dumps(res, indent=2))
        return res
    table = _system_rows(n_streams, n_steps, p, lm, cm, nist_words)
    gang = _gang_section(n_streams, p, lm, cm, smoke)
    async_ = _async_section(n_streams, p, lm, cm, smoke)
    async_offload = _async_offload_section(n_streams, p, lm, cm, smoke)
    planner = _planner_section(n_streams, p, lm, cm, smoke, profile=profile)
    sharded = _sharded_section(n_streams, p, lm, cm, smoke)
    resilience = _resilience_section(n_streams, p, lm, cm, smoke)
    res = {"config": {"n_streams": n_streams, "n_steps": n_steps,
                      "pareto_p": p, "backend": "pallas_interpret",
                      "smoke": smoke},
           "systems": table,
           "gang": gang,
           "async": async_,
           "async_offload": async_offload,
           "planner": planner,
           "sharded": sharded,
           "resilience": resilience,
           "lattice": lattice}
    if out_json:
        pathlib.Path(out_json).write_text(json.dumps(res, indent=2))
    return res


def async_gate(res: dict) -> list[str]:
    """CI perf-smoke acceptance for the async front-end: async-delivered
    words must be bit-identical to the ``gang=False`` solo path, the
    coalesced rounds must actually coalesce (one launch per round), and
    uncoordinated async tenants must beat one-launch-per-draw."""
    errors = []
    a = res["async"]
    if not a.get("bit_identical"):
        errors.append("async-delivered words NOT bit-identical to "
                      "gang=False")
    if a["async"]["launches_per_round"] > 1.0:
        errors.append(
            f"async rounds did not coalesce into one launch: "
            f"{a['async']['launches_per_round']:.2f} launches/round")
    if a["speedup_vs_per_draw"] < 1.0:
        errors.append(
            f"async front-end underperforms one-launch-per-draw: "
            f"{a['speedup_vs_per_draw']:.3f}x "
            f"({a['async']['words_per_s']:.3e} vs "
            f"{a['per_draw']['words_per_s']:.3e} words/s)")
    return errors


def async_offload_gate(res: dict) -> list[str]:
    """CI perf-smoke acceptance for the production tier: with a launch
    padded to a known duration, foreign-thread ingress p99 during the
    launch must stay under 10% of that duration under offload (the
    on-loop baseline pins near 100%), words must be bit-identical across
    offload on/off and the solo path, and backpressure must shed
    over-ceiling load with typed rejects while admitted futures all
    resolve."""
    errors = []
    o = res["async_offload"]
    if not o.get("bit_identical"):
        errors.append("offloaded words NOT bit-identical to the on-loop / "
                      "solo paths")
    if o["offload_p99_frac_of_launch"] >= 0.10:
        errors.append(
            f"ingress p99 during an offloaded launch is "
            f"{o['offload']['ingress_p99_ms']:.2f} ms = "
            f"{o['offload_p99_frac_of_launch']:.1%} of the "
            f"{o['launch_ms_injected']:.0f} ms launch (bar: <10%)")
    b = o["backpressure"]
    if b["rejected"] == 0:
        errors.append("overload shed no requests: the queued-rows ceiling "
                      "never rejected")
    if not b["all_admitted_resolved"]:
        errors.append(
            f"admitted futures did not all resolve under overload: "
            f"served={b['served']} rejected={b['rejected']} "
            f"failed_other={b['failed_other']} of {b['offered']}")
    return errors


def planner_gate(res: dict) -> list[str]:
    """CI perf-smoke acceptance: bit-identity must hold and the planner
    must not lose to the padded group-max policy on the skewed workload."""
    errors = []
    if not res["planner"].get("bit_identical"):
        errors.append("planner delivered words NOT bit-identical to "
                      "gang=False")
    sk = res["planner"]["skewed"]
    if sk["speedup"] < 1.0:
        errors.append(
            f"planner underperforms the group-max policy on the skewed "
            f"workload: {sk['speedup']:.3f}x "
            f"({sk['planner']['words_per_s']:.3e} vs "
            f"{sk['policy']['words_per_s']:.3e} words/s)")
    return errors


def sharded_gate(res: dict) -> list[str]:
    """CI perf-smoke acceptance for device-sharded gang launches: words
    at every device count must be bit-identical to the 1-device gang
    path, the farm must actually gang on the mesh, launches/flush must
    not fragment as devices scale, and (on hosts with the CPUs to show
    it) 4 forced devices must deliver >= 2x the 1-device words/s."""
    errors = []
    s = res["sharded"]
    if not s.get("bit_identical"):
        errors.append("sharded words NOT bit-identical to the 1-device "
                      "gang path")
    if not s.get("ganged_on_mesh"):
        errors.append("mesh-sharded farm fell back to solo launches "
                      "(gang_launches == 0 at some device count)")
    if not s.get("launches_per_flush_invariant"):
        errors.append(
            f"launches/flush varies with device count: "
            f"{ {n: v['launches_per_flush'] for n, v in s['per_device_count'].items()} }")
    if s.get("speedup_gate_armed"):
        if s["speedup_4dev_vs_1dev"] < 2.0:
            errors.append(
                f"sharded scaling below bar: 4-device words/s is "
                f"{s['speedup_4dev_vs_1dev']:.2f}x the 1-device path "
                f"(bar: >= 2x on a >= 4-CPU host)")
    return errors


def lattice_gate(res: dict) -> list[str]:
    """CI acceptance for the lattice/MXU arm: DSE must select mxu on the
    32-node lattice shape, the cycle model must actually rank mxu ahead
    of vpu there, the >= 24-member stacked gang must be bit-identical to
    solo lattice draws, and the stacked-layout VMEM cliff must be
    computed and recorded.  The measured mxu-vs-vpu ordering is enforced
    only on hosts with the CPUs to trust it (armed flag recorded)."""
    errors = []
    L = res["lattice"]
    if L["selected_compute_unit"] != "mxu":
        errors.append(
            f"select_config picked {L['selected_compute_unit']} for the "
            f"{L['n_nodes']}-node lattice (I={L['i_dim']}, "
            f"H={L['h_dim']}); the MXU arm never arms")
    if not L["mxu_wins_model"]:
        errors.append(
            f"cycle model ranks vpu ahead of mxu on the lattice shape: "
            f"{L['units']['mxu']['modeled_samples_per_s']:.3e} vs "
            f"{L['units']['vpu']['modeled_samples_per_s']:.3e} samples/s")
    if not L["gang_bit_identical"]:
        errors.append(
            f"{L['gang_members']}-member stacked lattice gang NOT "
            f"bit-identical to solo lattice draws")
    if L["stacked_vmem_cliff_cores"] is None:
        errors.append("stacked-layout VMEM cliff not computed")
    if L["speedup_gate_armed"] and not L["mxu_wins_measured"]:
        errors.append(
            f"measured lattice draw: mxu does not beat vpu "
            f"({L['units']['mxu']['words_per_s']:.3e} vs "
            f"{L['units']['vpu']['words_per_s']:.3e} words/s = "
            f"{L['measured_speedup_mxu_vs_vpu']:.2f}x)")
    return errors


def resilience_gate(res: dict) -> list[str]:
    """CI perf-smoke acceptance for the self-healing layer: under the
    seeded 10%-transient + one-poisoned-core storm, the poisoned core
    must quarantine and rotate within 3 flushes, the storm must actually
    have injected faults, degraded throughput must hold >= 0.5x the
    clean phase, and every delivered word (rotation included) must be
    bit-identical to fault-free solo runs."""
    errors = []
    r = res["resilience"]
    if not r.get("bit_identical"):
        errors.append("storm-delivered words NOT bit-identical to "
                      "fault-free solo runs (no rotation split matches)")
    if r["quarantined_within_flushes"] is None or \
            r["quarantined_within_flushes"] > 3:
        errors.append(
            f"poisoned core not quarantined+rotated within 3 flushes "
            f"(took {r['quarantined_within_flushes']})")
    if r["injected"]["transient"] < 1:
        errors.append("the seeded storm injected no transient launch "
                      "failures — the retry path went unexercised")
    if r["retries"] < 1:
        errors.append("transient failures were injected but never "
                      "retried")
    if r["degraded_words_per_s_frac"] < 0.5:
        errors.append(
            f"degraded throughput below bar: storm words/s is "
            f"{r['degraded_words_per_s_frac']:.2f}x the clean phase "
            f"(bar: >= 0.5x)")
    return errors


if __name__ == "__main__":
    import sys
    lattice_only = "--lattice" in sys.argv
    res = run_farm(smoke="--smoke" in sys.argv,
                   profile="--profile" in sys.argv,
                   lattice_only=lattice_only)
    errors = [f"LATTICE GATE FAIL: {e}" for e in lattice_gate(res)]
    if not lattice_only:
        errors += [f"PLANNER GATE FAIL: {e}" for e in planner_gate(res)]
        errors += [f"ASYNC GATE FAIL: {e}" for e in async_gate(res)]
        errors += [f"OFFLOAD GATE FAIL: {e}"
                   for e in async_offload_gate(res)]
        errors += [f"SHARDED GATE FAIL: {e}" for e in sharded_gate(res)]
        errors += [f"RESILIENCE GATE FAIL: {e}"
                   for e in resilience_gate(res)]
    if errors:
        for e in errors:
            print(e, file=sys.stderr)
        raise SystemExit(1)
    L = res["lattice"]
    print(f"lattice gate OK: {L['system']} selected="
          f"{L['selected_compute_unit']}, model mxu/vpu "
          f"{L['units']['mxu']['modeled_samples_per_s'] / L['units']['vpu']['modeled_samples_per_s']:.2f}x, "
          f"measured {L['measured_speedup_mxu_vs_vpu']:.2f}x "
          f"({'armed' if L['speedup_gate_armed'] else 'disarmed'}), "
          f"gang{L['gang_members']} bit-identical, VMEM cliff at "
          f"{L['stacked_vmem_cliff_cores']} cores")
    if lattice_only:
        raise SystemExit(0)
    print(f"planner gate OK: skewed speedup "
          f"{res['planner']['skewed']['speedup']:.2f}x, uniform ratio "
          f"{res['planner']['uniform']['speedup']:.2f}x")
    print(f"async gate OK: {res['async']['speedup_vs_per_draw']:.2f}x over "
          f"per-draw ({res['async']['ratio_vs_manual_flush']:.2f}x of the "
          f"manual-flush optimum), p99 deadline miss "
          f"{res['async']['async']['p99_miss_ms']:.2f} ms")
    o = res["async_offload"]
    print(f"offload gate OK: ingress p99 "
          f"{o['offload']['ingress_p99_ms']:.2f} ms during a "
          f"{o['launch_ms_injected']:.0f} ms launch "
          f"({o['offload_p99_frac_of_launch']:.1%}; on-loop baseline "
          f"{o['on_loop']['ingress_p99_ms']:.1f} ms), "
          f"{o['backpressure']['rejected']} typed rejects under overload")
    sh = res["sharded"]
    sp = sh["speedup_4dev_vs_1dev"]
    gate_state = ("armed" if sh["speedup_gate_armed"] else
                  "disarmed: " + sh.get("speedup_gate_skip_reason",
                                        "1 device"))
    print(f"sharded gate OK: devices={sh['device_counts']}, "
          f"bit-identical, launches/flush invariant, 4v1 speedup "
          f"{'n/a' if sp is None else f'{sp:.2f}x'} (gate {gate_state})")
    r = res["resilience"]
    print(f"resilience gate OK: poisoned core rotated within "
          f"{r['quarantined_within_flushes']} flush(es), "
          f"{r['injected']['transient']} transients / {r['retries']} "
          f"retries, degraded throughput "
          f"{r['degraded_words_per_s_frac']:.2f}x clean, bit-identical "
          f"through the storm")
