#!/usr/bin/env python3
"""Chip smoke test: serve the committed oscillator farm on a TPU, check it.

    python3 chip_smoke.py             # one chip: the served farm
    python3 chip_smoke.py --chips 4   # four chips: the mesh-sharded farm,
                                      # against the same farm on one chip

Run it from the root of a checkout, in one process that owns the chip.
It drives the entry points a user calls: ``OscillatorFarm.from_generated``
on the committed ``results/generated_cores/farm`` (four equal 3-8-3 bf16
cores that the planner stacks, plus the 4-16-4 hyperlorenz core alone),
plus ``chen@ring32`` at its DSE-selected solution, served through
``AsyncOscillatorFarm`` with offload, admission and health on: 64 tenants
of 128 lanes per core, a few rounds of draws of up to 4096 words (one TLS
record), some tenants skewed to 8-row draws.  Then it checks:

* every tenant's served words equal, bit for bit, a direct solo
  ``ops.chaotic_bits(backend="pallas")`` launch of its lanes from the
  same seeded state;
* a snapshot taken after the first round, restored into a fresh farm,
  continues every stream bit-exactly;
* a short trajectory of every core matches the ``ref`` oracle within the
  generated testbench's tolerances;
* the NIST gate quarantines no core on its served words (the verdict
  and the share of duplicate words are printed for every core).

It fails — exit code 1, no result line — when JAX finds no TPU, when a
served core resolves to anything but the compiled ``pallas`` kernel, when
a registry weight file is stale, when any flush fails, retries or
quarantines a core, or when a check disagrees.  The online quality gate
is held silent (see ``serve_async``).  The words/s it prints are a smoke
figure, not a benchmark.  The last line of standard output is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
"""
from __future__ import annotations

import argparse
import asyncio
import json
import pathlib
import sys
import time

import numpy as np

ROOT = pathlib.Path(__file__).resolve().parent
FARM_DIR = ROOT / "results" / "generated_cores" / "farm"
RING = "chen@ring32"
LANES = 128                 # lanes per tenant
TENANTS = 64                # per core
FULL = 32                   # rows of a 4096-word draw (one TLS record)
SKEW = 8                    # rows of a skewed draw
SERVED_BACKEND = "pallas"   # what every served core must resolve to


class SmokeFailure(RuntimeError):
    pass


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def say(*parts) -> None:
    print("chip_smoke:", *parts, flush=True)


# -- traffic ----------------------------------------------------------------

def round_rows(rnd: int, core_idx: int, tenant: int) -> int:
    """Word rows tenant ``tenant`` of the ``core_idx``-th core draws in
    round ``rnd``.  Round 0 is uniform (the planner pads); in later rounds
    some cores' tenants draw 8-row records, so the group's demand is
    skewed and the planner shapes a ragged or split launch; within each
    core every fourth tenant draws 8 rows and rides its core's launch."""
    if rnd == 0:
        return FULL
    if rnd == 1:
        return SKEW if core_idx % 2 else FULL
    return SKEW if (core_idx + tenant) % 4 == 0 else (
        FULL if core_idx % 3 else FULL // 2)


def build_farm(mesh=None):
    """The served farm: the committed generated cores, plus the ring32
    lattice at its DSE solution.  Weights come from committed files only;
    a stale registry entry fails instead of retraining here."""
    from repro.prng.stream import default_params, registry_weights
    from repro.serve.farm import OscillatorFarm
    check(registry_weights("chen") is not None,
          "results/weights/chen.npz is missing or stale for this jax; "
          "retrain it off the chip")
    farm = OscillatorFarm.from_generated(FARM_DIR, lanes_per_client=LANES,
                                         mesh=mesh)
    farm.add_core(RING, default_params(system=RING), lanes_per_client=LANES,
                  mesh=mesh)
    return farm


def register_all(farm, tenants: int) -> None:
    for ci, core in enumerate(farm.cores):
        for t in range(tenants):
            farm.register(core, f"t{t:02d}", seed=1000 * ci + t)


def _requests(farm, rnd: int, tenants: int):
    return [(core, f"t{t:02d}", LANES * round_rows(rnd, ci, t))
            for ci, core in enumerate(farm.cores) for t in range(tenants)]


# -- serving ----------------------------------------------------------------

async def serve_async(farm, n_rounds: int, tenants: int):
    """Serve ``n_rounds`` rounds through the async front-end.  Returns the
    words per (core, tenant) per round, the snapshot taken after round 0,
    and the wall seconds of each round."""
    from repro.serve.admission import AdmissionController
    from repro.serve.async_frontend import AsyncOscillatorFarm
    from repro.serve.health import HealthMonitor

    # Launch supervision on (retries, breaker).  The online quality
    # gate is held silent by a window that never fills: at 64 tenants
    # the committed bf16 cores fail it on the chip because their lanes
    # merge onto common bf16 orbits (duplicate words; PERF.md, PR 11).
    # Quality is judged below, by the NIST gate on the served words.
    health = HealthMonitor(window_words=1 << 40)
    admission = AdmissionController(max_queued_rows=1 << 20)
    served, secs, snap = {}, [], None
    async with AsyncOscillatorFarm(farm, offload=True, admission=admission,
                                   health=health) as af:
        for rnd in range(n_rounds):
            reqs = _requests(farm, rnd, tenants)
            t0 = time.perf_counter()
            futs = [af.submit(core, client, n, deadline_ms=0,
                              slo="latency" if rnd else None)
                    for core, client, n in reqs]
            words = await asyncio.gather(*futs)
            secs.append(time.perf_counter() - t0)
            await af.drain()                 # let the flush cycle finish
            for (core, client, n), w in zip(reqs, words):
                check(w.shape == (n,) and w.dtype.name == "uint32",
                      f"{core}/{client} round {rnd}: got {w.shape} "
                      f"{w.dtype}, want ({n},) uint32")
                served.setdefault((core, client), []).append(w)
            if rnd == 0:
                snap = await af.snapshot()
        check(not af.flush_errors,
              f"flush errors: {list(af.flush_errors)}")
    return served, snap, secs, health


def check_health(farm, health) -> None:
    """No launch failed or was retried, and no core was quarantined."""
    stats = health.stats
    say(f"health {stats}; quarantined {sorted(farm.quarantined)}")
    check(stats["launch_failures"] == 0 and stats["retries"] == 0,
          f"launch failures or retries: {stats}")
    check(not farm.quarantined and stats["quality_quarantines"] == 0,
          f"quarantined cores: {sorted(farm.quarantined)}; {stats}")


def check_solo(farm, pool0, served) -> None:
    """Each tenant's served words == one direct solo launch of its lanes
    from the same seeded, burned-in state, from word row 0."""
    from repro.kernels import ops
    for core in farm.cores:
        svc = farm.services[core]
        mine = {c: np.concatenate(w) for (k, c), w in served.items()
                if k == core}
        rows = max(-(-w.size // LANES) for w in mine.values())
        words, _ = ops.chaotic_bits(
            svc.params, pool0[core], 2 * rows, 0, activation=svc.activation,
            backend=SERVED_BACKEND, config=svc.config)
        words = np.asarray(words)
        differ = 0
        for client, got in mine.items():
            s = svc.clients[client].slot
            want = words[:, s * LANES:(s + 1) * LANES].reshape(-1)[:got.size]
            differ += int((got != want).sum())
        say(f"solo check {core}: {differ} of "
            f"{sum(w.size for w in mine.values())} served words differ")
        check(differ == 0, f"{core}: served words differ from a solo launch")


def check_restore(snap, served, n_rounds: int, tenants: int,
                  mesh=None) -> None:
    """A fresh farm restored from the round-0 snapshot serves rounds
    1.. with words bit-identical to the live front-end's."""
    farm2 = build_farm(mesh)
    farm2.restore(snap)
    for rnd in range(1, n_rounds):
        reqs = _requests(farm2, rnd, tenants)
        for core, client, n in reqs:
            farm2.request(core, client, n)
        out = farm2.flush()
        for core, client, n in reqs:
            check(np.array_equal(out[core][client],
                                 served[(core, client)][rnd]),
                  f"{core}/{client} round {rnd}: restored stream differs")


def check_ref(farm) -> None:
    """Short trajectories against the ``ref`` oracle, within the
    generated testbench's tolerances (``core/codegen.py``)."""
    import jax
    import jax.numpy as jnp
    from repro.kernels import ops
    for core in farm.cores:
        svc = farm.services[core]
        bf16 = svc.dtype == jnp.bfloat16
        n_steps, tol = (3, 1.5e-1) if bf16 else (8, 1e-4)
        x0 = (jax.random.uniform(jax.random.PRNGKey(0),
                                 (svc.config.s_block, svc.dim),
                                 dtype=jnp.float32) - 0.5).astype(svc.dtype)
        got, want = (ops.chaotic_trajectory(
            svc.params, x0, n_steps, activation=svc.activation,
            backend=backend, config=svc.config)
            for backend in (SERVED_BACKEND, "ref"))
        err = float(np.max(np.abs(np.asarray(got, np.float32)
                                  - np.asarray(want, np.float32))))
        say(f"ref check {core}: max |pallas - ref| over {n_steps} steps = "
            f"{err!r} (tolerance {tol})")
        check(err <= tol, f"{core}: trajectory off the ref oracle by {err}")


def check_nist(farm, served) -> None:
    """The offline NIST gate on the first ``GATE_WORDS`` of each core's
    served words, in tenant order: a core fails when the gate would
    quarantine it (more failed tests than chance explains, or any far
    outside chance).  The share of duplicate words, the mark of lanes
    that merged onto one orbit, is printed, not checked."""
    from repro.prng.quality import GATE_WORDS, nist_verdict
    bad = []
    for core in farm.cores:
        words = np.concatenate([np.concatenate(w) for (k, _), w
                                in sorted(served.items()) if k == core])
        v = nist_verdict(words[:GATE_WORDS])
        dup = 1.0 - np.unique(words).size / words.size
        say(f"NIST {core}: failed={v['failed_tests']} "
            f"hard_failed={v['hard_failed_tests']} p={v['p_values']}; "
            f"duplicate words {dup!r} of {words.size}")
        if v["quarantined"]:
            bad.append(core)
    check(not bad, f"the NIST gate quarantines {bad}")


class CompileClock:
    """XLA compiles and the seconds they took, from JAX's own monitoring
    events (a persistent-cache hit is not a compile)."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax
        self.secs, self.compiles = 0.0, 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event == self.EVENT:
            self.secs += duration
            self.compiles += 1

    def report(self) -> None:
        say(f"compile: {self.compiles} XLA compiles took {self.secs:.3f} s")


# -- phases -----------------------------------------------------------------

def smoke_one_chip(tenants: int = TENANTS, n_rounds: int = 3) -> None:
    clock = CompileClock()
    t0 = time.perf_counter()
    farm = build_farm()
    for core in farm.cores:
        svc = farm.services[core]
        say(f"core {core}: {svc.dim}-{int(svc.params['w1'].shape[1])} "
            f"{np.dtype(svc.dtype).name} {svc.config.compute_unit} "
            f"s_block={svc.config.s_block} t_block={svc.config.t_block} "
            f"unroll={svc.config.unroll} backend={svc.backend}")
        check(svc.backend == SERVED_BACKEND,
              f"{core} resolves to {svc.backend!r}, not {SERVED_BACKEND!r}")
    check(farm.services[RING].config.compute_unit == "mxu",
          f"{RING}: DSE picked {farm.services[RING].config}")
    register_all(farm, tenants)
    pool0 = {c: farm.services[c].pool_x for c in farm.cores}
    lanes = sum(int(p.shape[0]) for p in pool0.values())
    say(f"{len(farm.cores)} cores x {tenants} tenants x {LANES} lanes = "
        f"{lanes} lanes; set-up {time.perf_counter() - t0:.3f} s")

    served, snap, secs, health = asyncio.run(
        serve_async(farm, n_rounds, tenants))
    n_words = [sum(w[r].size for w in served.values())
               for r in range(n_rounds)]
    say(f"plans {farm.plan_decisions} layouts {farm.layout_launches} "
        f"launches {farm.launches} (gang {farm.gang_launches})")
    check(farm.plan_decisions["padded"] >= 1
          and farm.plan_decisions["ragged"] >= 1,
          f"planner never took both padded and ragged: "
          f"{farm.plan_decisions}")
    for r, (w, s) in enumerate(zip(n_words, secs)):
        say(f"round {r}: {w} words in {s:.6f} s = {w / s:.1f} words/s "
            f"(smoke figure, not a benchmark"
            f"{'; includes compiles' if r == 0 else ''})")

    failed = []
    for name, run in (
            ("health", lambda: check_health(farm, health)),
            ("solo", lambda: check_solo(farm, pool0, served)),
            ("restore", lambda: check_restore(snap, served, n_rounds,
                                              tenants)),
            ("ref", lambda: check_ref(farm)),
            ("nist", lambda: check_nist(farm, served))):
        try:
            run()
            say(f"{name} check passed")
        except SmokeFailure as e:    # report every check, then fail
            failed.append(name)
            print(f"chip_smoke: FAIL: {name}: {e}", file=sys.stderr)
    clock.report()
    check(not failed, f"checks failed: {failed}")


def smoke_four_chips(tenants: int = TENANTS, n_rounds: int = 2) -> None:
    """The mesh-sharded farm against the same farm on one device: one
    logical gang launch spans all four chips, bit-identically."""
    import jax
    from jax.sharding import Mesh
    clock = CompileClock()
    devs = jax.devices()
    check(len(devs) >= 4, f"--chips 4 needs 4 devices, have {len(devs)}")
    mesh = Mesh(np.asarray(devs[:4]), ("data",))
    sharded, single = build_farm(mesh), build_farm()
    for farm in (sharded, single):
        register_all(farm, tenants)
    got = {}
    for rnd in range(n_rounds):
        for name, farm in (("4 devices", sharded), ("1 device", single)):
            for core, client, n in _requests(farm, rnd, tenants):
                farm.request(core, client, n)
            t0 = time.perf_counter()
            out = farm.flush(slo_by_core={c: "latency" for c in farm.cores}
                             if rnd else None)
            dt = time.perf_counter() - t0
            n_words = sum(w.size for per in out.values()
                          for w in per.values())
            say(f"round {rnd} {name}: {n_words} words in {dt:.6f} s "
                f"(smoke figure, not a benchmark)")
            got[(rnd, name)] = out
        for core, per in got[(rnd, "1 device")].items():
            for client, w in per.items():
                check(np.array_equal(got[(rnd, "4 devices")][core][client],
                                     w),
                      f"round {rnd} {core}/{client}: 4-device words differ "
                      f"from 1-device words")
    say(f"plans {sharded.plan_decisions} layouts {sharded.layout_launches}")
    # the last pool of every gang plan, and of every core launched alone
    pools = [(f"gang {plan['mode']}", plan["last_x"],
              1 if plan["mode"] == "stacked" else 0)
             for plan in sharded._sched._plans.values()
             if plan["last_x"] is not None]
    pools += [(f"solo {core}", svc.pool_x, 0)
              for core, svc in sharded.services.items() if svc.launches]
    placed = set()
    for name, x, lane_ax in pools:
        shards = sorted((s.device.id, s.data.shape)
                        for s in x.addressable_shards)
        say(f"pool {x.shape} {name}: sharding {x.sharding}; "
            f"per device {shards}")
        check(len(shards) == 4 and all(
            shp[lane_ax] * 4 == x.shape[lane_ax] for _, shp in shards),
            f"pool {x.shape} is not split in quarters over 4 devices: "
            f"{shards}")
        placed.update(d for d, _ in shards)
    check(placed == {d.id for d in devs[:4]},
          f"gang pools placed on devices {sorted(placed)} only")
    say("4-device words equal 1-device words, bit for bit")
    clock.report()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)

    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"chip_smoke: FAIL: no TPU; JAX found {devs[0].platform}",
              file=sys.stderr)
        return 1
    sys.path.insert(0, str(ROOT / "src"))
    from repro.compile_cache import enable_compile_cache
    say(f"device {devs[0].device_kind} x {len(devs)}; jax {jax.__version__}; "
        f"compile cache {enable_compile_cache()}")
    try:
        if args.chips == 4:
            smoke_four_chips()
        else:
            smoke_one_chip()
    except SmokeFailure as e:
        print(f"chip_smoke: FAIL: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
