"""The farm's tracer (``repro.serve.tracer``): exact span totals under a
clock that ticks on every read, the lane counters of each launch shape,
queue wait at commit, the span names one flush cycle emits, the mesh
counters read from where a launch's arrays lie, the per-layer readers of
the benchmark that consume them, and the keys a tiny served window of
``farm5.bulk`` carries."""
import asyncio
import contextlib
import json
import math
import os
import pathlib
import subprocess
import sys

import jax
import numpy as np
import pytest

from repro.core.dse import Candidate
from repro.prng.stream import _round_rows
from repro.serve.async_frontend import AsyncOscillatorFarm
from repro.serve.clock import FakeClock
from repro.serve.farm import OscillatorFarm
from repro.serve.health import HealthMonitor
from repro.serve.tracer import COUNTERS, TIMERS, Tracer

from test_kernels import _mk

ROOT = pathlib.Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

CAND = Candidate(i_dim=3, h_dim=8, p=0, compute_unit="vpu",
                 dtype_bytes=4, unroll=2, t_block=32)
LANES = 128
MS = 1e-3
TABLE = {"frontend.cycle.commit", "frontend.cycle.resolve",
         "frontend.cycle.deliver", "frontend.cycle.quality", "farm.plan",
         "farm.stack", "farm.launch", "farm.launch.wait", "farm.launch.copy",
         "farm.absorb"}
HARNESS = {"frontend.commit", "frontend.resolve", "farm.flush",
           "service.absorb"}
NEW_METRICS = ("queue_wait_ms_mean.bulk", "commit_ms_per_mword.bulk",
               "resolve_ms_per_mword.bulk", "device_wait_ms_per_mword.bulk",
               "copy_ms_per_mword.bulk", "useful_lane_share.bulk",
               "absorb_copies_per_word.bulk", "overlapped_fetch_share.bulk")


class TickClock:
    """Every read advances the clock by 1 ms, so a span's seconds are the
    count of reads inside it, plus one."""

    def __init__(self):
        self.t = 0.0

    def now(self) -> float:
        self.t += MS
        return self.t

    def time(self) -> float:
        return self.now()


class Recorder:
    """Stands in for ``jax.profiler.TraceAnnotation``: records names."""

    def __init__(self):
        self.names = []

    def __call__(self, name, **_):
        self.names.append(name)
        return contextlib.nullcontext()


def _params(key):
    w1, b1, w2, b2, _ = _mk(3, 8, 1, key=key)
    return {"w1": w1, "b1": b1, "w2": w2, "b2": b2}


def _farm(clients_per_core, gang=True, **kw):
    farm = OscillatorFarm(gang=gang, profile=True, **kw)
    for i, n in enumerate(clients_per_core):
        farm.add_core(f"core{i}", _params(10 + i), config=CAND,
                      lanes_per_client=LANES, backend="pallas_interpret")
        for j in range(n):
            farm.register(f"core{i}", f"t{j}", seed=50 + j)
    return farm


def test_span_totals_are_exact_and_nest():
    tr = Tracer(TickClock())
    with tr.span("outer", "launch"):             # reads at 1 and 4 ms
        with tr.span("inner", "launch_wait"):    # reads at 2 and 3 ms
            pass
        with tr.span("note"):                    # no key: no read
            pass
    tr.count(queue_wait_s=0.5, draws_committed=2)
    st = tr.stats()
    assert set(st) == set(TIMERS + COUNTERS)
    assert st["launch"] == pytest.approx(3 * MS)
    assert st["launch_wait"] == pytest.approx(1 * MS)
    assert st["queue_wait_s"] == 0.5 and st["draws_committed"] == 2.0
    assert all(st[k] == 0.0 for k in TIMERS
               if k not in ("launch", "launch_wait"))


def test_off_tracer_reads_no_clock_and_annotates_nothing(monkeypatch):
    rec = Recorder()
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", rec)
    tr = Tracer()
    with tr.span("farm.launch", "launch"):
        tr.count(flushes=1)
    assert not tr.on and tr.stats() is None and rec.names == []


def test_one_gang_flush_is_timed_read_by_read():
    """A stacked gang flush under the ticking clock: plan (the planner's
    decision and the plan), stack, launch in two halves (the dispatch, 1
    ms; the fetch holding wait and copy, 5 ms), absorb."""
    farm = _farm([1, 1], clock=TickClock())
    for core in farm.cores:
        farm.request(core, "t0", 4 * LANES)
    farm.flush()
    st = farm.profile_stats
    assert st["plan"] == pytest.approx(2 * MS)
    assert st["stack"] == pytest.approx(1 * MS)
    assert st["launch"] == pytest.approx(6 * MS)
    assert st["launch_wait"] == pytest.approx(1 * MS)
    assert st["launch_copy"] == pytest.approx(1 * MS)
    assert st["launch_wait"] + st["launch_copy"] <= st["launch"]
    assert st["absorb"] == pytest.approx(1 * MS)
    assert st["flushes"] == 1.0


def test_a_solo_launch_is_timed_as_a_gang_launch():
    farm = _farm([1], clock=TickClock())
    farm.request("core0", "t0", 4 * LANES)
    farm.flush()
    st = farm.profile_stats
    assert st["launch"] == pytest.approx(6 * MS)
    assert st["launch_wait"] == pytest.approx(1 * MS)
    assert st["launch_copy"] == pytest.approx(1 * MS)
    assert st["absorb"] == pytest.approx(1 * MS)


@pytest.mark.parametrize("pools,layout", [((2,), None), ((1, 1), "stacked"),
                                          ((1, 2), "concat")])
def test_lanes_computed_are_rows_times_pool_lanes(pools, layout):
    farm = _farm(pools)
    for i, n in enumerate(pools):
        for j in range(n):
            farm.request(f"core{i}", f"t{j}", 5 * LANES)
    farm.flush()
    rows = _round_rows(5, CAND.t_block)
    st = farm.profile_stats
    assert st["lanes_computed"] == rows * LANES * sum(pools)
    assert st["lanes_used"] == st["lanes_computed"]
    assert farm.layout_launches == {
        "stacked": int(layout == "stacked"), "concat": int(layout == "concat")}


def test_an_idle_rider_is_computed_and_not_used():
    farm = _farm([2])
    farm.request("core0", "t1", 3 * LANES)
    farm.flush()
    rows = _round_rows(3, CAND.t_block)
    st = farm.profile_stats
    assert st["lanes_computed"] == rows * 2 * LANES
    assert st["lanes_used"] == rows * LANES


def test_queue_wait_is_commit_time_less_submit_time():
    async def go():
        fc = FakeClock()
        farm = _farm([1, 1], clock=fc)
        async with AsyncOscillatorFarm(farm) as af:
            futs = [af.submit(core, "t0", 100, deadline_ms=5)
                    for core in farm.cores]
            await af.drain()
            assert not any(f.done() for f in futs)
            fc.advance(0.005)
            await af.drain()
            assert all(f.done() for f in futs)
        st = farm.profile_stats
        assert st["draws_committed"] == 2.0
        assert st["queue_wait_s"] == pytest.approx(2 * 0.005)
        assert all(st[k] == 0.0 for k in TIMERS)   # a frozen clock
    asyncio.run(asyncio.wait_for(go(), 120.0))


def _one_cycle(farm):
    async def go():
        async with AsyncOscillatorFarm(farm, health=HealthMonitor()) as af:
            await asyncio.gather(*(af.draw(core, "t0", 200)
                                   for core in farm.cores))
    asyncio.run(asyncio.wait_for(go(), 120.0))


def test_one_cycle_emits_the_tables_spans_and_no_harness_name(monkeypatch):
    rec = Recorder()
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", rec)
    farm = _farm([1, 1])
    _one_cycle(farm)
    assert set(rec.names) == TABLE
    assert not set(rec.names) & HARNESS
    assert not any(n.startswith("bench.") for n in rec.names)
    st = farm.profile_stats
    assert st["commit"] > 0 and st["resolve"] > 0


def test_profile_off_annotates_nothing(monkeypatch):
    rec = Recorder()
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", rec)
    farm = OscillatorFarm(profile=False)
    farm.add_core("core0", _params(10), config=CAND, lanes_per_client=LANES,
                  backend="pallas_interpret")
    farm.register("core0", "t0", seed=50)
    _one_cycle(farm)
    assert rec.names == [] and farm.profile_stats is None


def test_from_generated_forwards_profile():
    farm_dir = ROOT / "results" / "generated_cores" / "farm"
    core = sorted(p.name for p in farm_dir.iterdir()
                  if (p / "solution.json").exists())[0]
    assert OscillatorFarm.from_generated(
        farm_dir, cores=[core], profile=True).profile_stats is not None
    assert OscillatorFarm.from_generated(
        farm_dir, cores=[core]).profile_stats is None


def _reader(name):
    from bench import spec
    return spec.reader(name)


def test_the_new_readers_by_hand():
    st = {"queue_wait_s": 0.3, "draws_committed": 100.0, "commit": 0.02,
          "resolve": 0.5, "launch_wait": 0.1, "launch_copy": 0.7,
          "lanes_used": 900.0, "lanes_computed": 1000.0,
          "absorb_words_copied": 4_020_480.0, "fetches": 4.0,
          "fetches_overlapped": 2.0}
    obs = {"stages": st, "words": 4_000_000}
    want = {"queue_wait_ms_mean.bulk": 3.0,
            "commit_ms_per_mword.bulk": 5.0,
            "resolve_ms_per_mword.bulk": 125.0,
            "device_wait_ms_per_mword.bulk": 25.0,
            "copy_ms_per_mword.bulk": 175.0,
            "useful_lane_share.bulk": 90.0,
            "absorb_copies_per_word.bulk": 1.00512,
            "overlapped_fetch_share.bulk": 50.0}
    for name, value in want.items():
        assert _reader(name)(obs) == pytest.approx(value), name


@pytest.mark.parametrize("name", NEW_METRICS)
def test_a_new_reader_reads_nothing_where_its_keys_are_absent(name):
    """The parent's program has none of these keys: nothing, not zero."""
    parent = {"plan": 1.0, "stack": 1.0, "launch": 2.0, "absorb": 1.0,
              "flushes": 10.0}
    assert _reader(name)({"stages": parent, "words": 1_000_000}) is None


def test_a_tiny_bulk_window_carries_every_new_key():
    """``farm5.bulk`` cut to the CPU interpreter's size, served with the
    farm's timers on (no profiler trace): the window's stages hold every
    new key, and the new readers read finite values from them."""
    from bench import run as bench_run, serve
    from bench.tests.tiny import tiny_cell
    cell = tiny_cell("farm5.bulk")
    seed, seconds = 2147484700, 2.0
    devs, _ = bench_run.device_info(cell.chips, require_tpu=False)
    sess = serve.Session(cell, seed, seconds)
    farm = serve.build_farm(cell, profile=True, devs=devs)
    for t, (core, client) in enumerate(sess.tenants):
        farm.register(core, client, seed=sess.tenant_seed[t])
    watched = {}
    for core, client in sess.audited:
        watched.setdefault(core, set()).add(client)
    audit = serve.Audit(farm, watched, cell.mix["audit_records"], seed, sess)
    sess.warm_shapes(farm)
    plan = cell.generator.make(cell.mix, len(sess.cores), seed, seconds)
    stats = asyncio.run(sess.serve(farm, plan, serve.CompileWatch(), audit))
    st = stats["stages"]
    assert set(TIMERS + COUNTERS) <= set(st)
    assert st["draws_committed"] > 0 and st["lanes_computed"] > 0
    assert st["launch_wait"] + st["launch_copy"] <= st["launch"]
    assert st["lanes_used"] <= st["lanes_computed"]
    done = np.asarray(sess.done)
    words = int(np.asarray(sess.words)[
        (done >= sess.t0) & (done < sess.t1)].sum())
    obs = {"stages": st, "words": words}
    for name in NEW_METRICS:
        v = _reader(name)(obs)
        assert v is not None and math.isfinite(v), name
    assert _reader("useful_lane_share.bulk")(obs) <= 100.0
    assert st["absorb_words_copied"] > 0


# Tier-1 runs on one CPU device: arrays that lie on several devices are
# made in a subprocess on four forced host devices.
MESH_SCRIPT = r"""
import json

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.serve.tracer import Tracer


class Clock:
    def now(self):
        return 0.0


devs = jax.devices()[:4]
MESH = Mesh(np.asarray(devs), ("data",))
ONE = Mesh(np.asarray(devs[:1]), ("data",))
x = np.arange(64 * 3, dtype=np.float32).reshape(64, 3)
arrays = {
    "one_device": jax.device_put(x, devs[0]),
    "replicated": jax.device_put(x, NamedSharding(MESH, P())),
    "sharded": jax.device_put(x, NamedSharding(MESH, P("data"))),
}
out = {}
for name, state in arrays.items():
    tr = Tracer(Clock())
    tr.launched(MESH, "data", state)
    tr.launched(ONE, "data", state)        # a mesh of one is no mesh launch
    tr.launched(None, "data", state)
    st = tr.stats()
    out[name] = {"mesh_launches": st["mesh_launches"],
                 "mesh_launches_split": st["mesh_launches_split"]}
for name, words in arrays.items():
    tr = Tracer(Clock())
    host = tr.fetch(words)
    out[name]["fetch_assembled"] = tr.stats()["fetch_assembled"]
    out[name]["fetched_equal"] = bool(np.array_equal(host, x))
print("RESULT " + json.dumps(out))
"""


@pytest.fixture(scope="module")
def on_a_mesh():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.pathsep.join([str(ROOT / "src"), str(ROOT)]))
    p = subprocess.run([sys.executable, "-c", MESH_SCRIPT], env=env,
                       cwd=ROOT, capture_output=True, text=True, timeout=300)
    line = [x for x in p.stdout.splitlines() if x.startswith("RESULT ")]
    assert p.returncode == 0 and line, p.stdout[-2000:] + p.stderr[-4000:]
    return json.loads(line[-1][len("RESULT "):])


@pytest.mark.parametrize("state,split", [("one_device", 0.0),
                                         ("replicated", 1.0),
                                         ("sharded", 1.0)])
def test_a_mesh_launch_is_split_where_its_state_lies(on_a_mesh, state,
                                                     split):
    """Only the launch on the four-device mesh counts; it counts as split
    when its state lies on every device.  The words are no longer read:
    a sharded launch gathers them onto every device, and a launch whose
    state stayed on one device ran there, wherever its words were put."""
    got = on_a_mesh[state]
    assert got["mesh_launches"] == 1.0
    assert got["mesh_launches_split"] == split


@pytest.mark.parametrize("words,assembled", [("one_device", 0.0),
                                             ("replicated", 0.0),
                                             ("sharded", 1.0)])
def test_a_fetch_is_counted_where_the_host_assembles_its_words(
        on_a_mesh, words, assembled):
    got = on_a_mesh[words]
    assert got["fetch_assembled"] == assembled
    assert got["fetched_equal"]


@pytest.mark.parametrize("overlapped", [False, True])
def test_a_fetch_is_counted_and_overlapped_under_a_later_dispatch(
        overlapped):
    tr = Tracer(TickClock())
    words = jax.numpy.arange(6, dtype=jax.numpy.uint32)
    words.copy_to_host_async()
    assert np.array_equal(tr.fetch(words, overlapped=overlapped),
                          np.arange(6))
    st = tr.stats()
    assert (st["fetches"], st["fetches_overlapped"]) == (1.0,
                                                         float(overlapped))
    assert st["launch_wait"] == pytest.approx(MS)
    assert st["launch_copy"] == pytest.approx(MS)


def test_an_untraced_fetch_counts_nothing():
    tr = Tracer()
    words = jax.numpy.arange(6, dtype=jax.numpy.uint32)
    assert np.array_equal(tr.fetch(words), np.arange(6))
    assert tr.stats() is None


def test_the_fetch_assembled_reader_by_hand():
    read = _reader("fetch_assembled_share.bulk_mesh4")
    st = {"fetch_assembled": 3.0, "mesh_launches": 12.0}
    assert read({"stages": st}) == pytest.approx(25.0)
    st["fetch_assembled"] = 0.0
    assert read({"stages": st}) == 0.0


@pytest.mark.parametrize("absent", ["fetch_assembled", "mesh_launches"])
def test_the_fetch_assembled_reader_reads_nothing_without_its_keys(absent):
    """The parent's program has no ``fetch_assembled``: nothing, not 0."""
    st = {"fetch_assembled": 0.0, "mesh_launches": 12.0,
          "mesh_launches_split": 12.0}
    del st[absent]
    assert _reader("fetch_assembled_share.bulk_mesh4")({"stages": st}) is None
