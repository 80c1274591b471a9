"""A core launched alone on a mesh: one build per launch shape, split over
every device, bit-identical to the unsharded launch.

A core that gangs with no other (the 4-16-4 core of a farm whose other
cores are 3-8-3) takes the solo path, ``PRNGService._dispatch`` ->
``ops.chaotic_bits(..., mesh=)``.  On a mesh of more than one device that
launch runs one jitted ``shard_map`` per step count, built once and
reused, as the gang launches are.  A pool that does not divide the device
count is padded with dead lanes, as the gang path pads its block axis.
The tracer counts every launch of a pool on a mesh (``mesh_launches``),
those whose state lies on every device (``mesh_launches_split``), the
sharded callables built (``launch_builds``) and the fetches whose words
the host had to assemble from several device buffers
(``fetch_assembled``).

Every sharded launch, solo, lane-concat gang or stacked gang, gathers its
words over the mesh inside its program: they come back whole on every
device, bit-identical to the unsharded launch, while the state keeps its
lane sharding for the next launch.

Tier-1 runs on one CPU device, so the cases run in one subprocess on
four forced host devices and the tests read its findings.

The plain reference that reproduces the vpu kernels bit for bit is the
op-by-op one the benchmark audits with (``bench/cores/ann.py``, sums in
index order).  ``kernels/ref.py`` contracts with ``jnp.matmul``, whose
summation order differs: from the same pool, the 4-16-4 core's state
after one 8-step launch is 0.039 away from it, so it is no bitwise
reference for a vpu core.
"""
import json
import os
import pathlib
import subprocess
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[1]

SCRIPT = r"""
import json

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh

from test_gang import _params as gang_params, _stacked
from test_kernels import _mk
from bench.cores import ann
from repro.kernels import ops
from repro.kernels.chaotic_ann import sharded_launch_builds
from repro.serve.farm import OscillatorFarm

compiles = []


def _event(event, duration, fun_name=None, **_):
    if event == "/jax/core/compile/backend_compile_duration":
        compiles.append(str(fun_name))


jax.monitoring.register_event_duration_secs_listener(_event)
MESH = Mesh(np.asarray(jax.devices()[:4]), ("data",))
CORES = {"small": (3, 8), "wide": (4, 16)}
PARAMS = {}
for core, (i, h) in CORES.items():
    w1, b1, w2, b2, _ = _mk(i, h, 1, key=i)
    PARAMS[core] = {"w1": w1, "b1": b1, "w2": w2, "b2": b2}


def farm(mesh, *, lanes=128, tenants=4, backend="pallas_interpret"):
    f = OscillatorFarm(profile=True)
    for core in CORES:
        f.add_core(core, PARAMS[core], lanes_per_client=lanes,
                   backend=backend, mesh=mesh, mesh_axis="data")
        for t in range(tenants):
            f.register(core, f"t{t}", seed=97 * t + len(core))
    return f


def flush(f, words):
    for core, svc in f.services.items():
        for name in svc.clients:
            f.request(core, name, words)
    return f.flush()


def same(a, b):
    return bool(np.array_equal(np.asarray(a, np.float32),
                               np.asarray(b, np.float32)))


out = {}
# several flushes of one shape, sharded and unsharded
sharded, plain = farm(MESH), farm(None)
svc = sharded.services["wide"]
words_ok = state_ok = solo_ok = ann_ok = True
for k in range(5):
    if k == 2:
        steady_from = len(compiles)
    x_pre = np.asarray(svc.pool_x)
    offsets = jnp.asarray(np.repeat(
        [c.row for c in svc.clients.values()], svc.lanes_per_client),
        jnp.uint32)
    got, want = flush(sharded, 512), flush(plain, 512)
    for core in CORES:
        for name in got[core]:
            words_ok &= bool(np.array_equal(got[core][name],
                                            want[core][name]))
        state_ok &= same(sharded.services[core].pool_x,
                         plain.services[core].pool_x)
    # the wide core's launch of 4 rows against the solo kernel and the
    # plain references, from the same pre-launch pool
    lanes = svc.lanes_per_client
    launched = np.concatenate([got["wide"][f"t{t}"].reshape(4, lanes)
                               for t in range(4)], axis=1)
    solo_w, solo_x = ops.chaotic_bits(
        svc.params, jnp.asarray(x_pre), 8, offsets, config=svc.config,
        backend="pallas_interpret")
    solo_ok &= bool(np.array_equal(launched, np.asarray(solo_w)))
    solo_ok &= same(svc.pool_x, solo_x)
    ref = {key: np.asarray(v, np.float32)
           for key, v in svc.params.items()}
    ann_w, ann_x = ann.launch(ref, x_pre, np.asarray(offsets), 4, "float32")
    ann_ok &= bool(np.array_equal(launched, ann_w)) and same(svc.pool_x,
                                                             ann_x)
    if k == 0:
        builds_first = sharded.profile_stats["launch_builds"]
st = sharded.profile_stats
out["words_equal_unsharded"] = words_ok
out["state_equal_unsharded"] = state_ok
out["equal_solo_kernel"] = solo_ok
out["equal_plain_reference"] = ann_ok
out["builds_first"] = builds_first
out["builds_all"] = st["launch_builds"]
out["steady_compiles"] = compiles[steady_from:]
out["mesh_launches"] = st["mesh_launches"]
out["mesh_launches_split"] = st["mesh_launches_split"]
out["fetch_assembled"] = st["fetch_assembled"]

# a pool of 3 x 6 = 18 lanes does not divide 4 devices: dead lanes pad it
ragged, ragged_plain = farm(MESH, lanes=6, tenants=3), farm(None, lanes=6,
                                                           tenants=3)
ok = True
for _ in range(2):
    got, want = flush(ragged, 48), flush(ragged_plain, 48)
    for core in CORES:
        for name in got[core]:
            ok &= bool(np.array_equal(got[core][name], want[core][name]))
        ok &= same(ragged.services[core].pool_x,
                   ragged_plain.services[core].pool_x)
st = ragged.profile_stats
out["ragged"] = {"equal": ok, "pool": int(ragged.services["wide"]
                                          .pool_x.shape[0]),
                 "mesh_launches": st["mesh_launches"],
                 "mesh_launches_split": st["mesh_launches_split"],
                 "fetch_assembled": st["fetch_assembled"]}

# the reference backend ignores the mesh: its launches are not split
oracle = farm(MESH, tenants=1, backend="ref")
flush(oracle, 128)
st = oracle.profile_stats
out["ref_backend"] = {"mesh_launches": st["mesh_launches"],
                      "mesh_launches_split": st["mesh_launches_split"]}

# each sharded builder on its own, from a pool that divides the mesh and,
# where the layout allows one, from a pool that does not
KW = dict(backend="pallas_interpret", s_block=128, t_block=32, unroll=2)
GANG = _stacked([gang_params(key=k) for k in range(3)])
rng = np.random.default_rng(5)


def pool(shape, key):
    x = _mk(3, 8, int(np.prod(shape[:-1])), key=key)[4]
    offs = rng.integers(0, 10_000, size=shape[:-1]).astype(np.uint32)
    return x.reshape(shape), jnp.asarray(offs)


def solo(lanes):
    x, offs = pool((lanes, 3), 21)
    return (lambda mesh: ops.chaotic_bits(PARAMS["small"], x, 16, offs,
                                          mesh=mesh, **KW)), 0


def gang(blocks):
    x, offs = pool((blocks * 128, 3), 22)
    cmap = np.arange(blocks, dtype=np.int32) % 3
    return (lambda mesh: ops.chaotic_bits_gang(
        GANG, x, 16, offs, core_map=cmap, mesh=mesh, **KW)), 0


def stacked(lanes):
    x, offs = pool((3, lanes, 3), 23)
    return (lambda mesh: ops.chaotic_bits_gang_stacked(
        GANG, x, 16, offs, mesh=mesh, **KW)), 1


CASES = {"solo": solo(512), "gang": gang(4), "stacked": stacked(512),
         "solo_padded": solo(18), "gang_padded": gang(6)}
out["kernels"] = {}
for case, (launch, lane_axis) in CASES.items():
    want_w, want_x = launch(None)
    got_w, got_x = launch(MESH)
    builds = sharded_launch_builds()
    again_w, again_x = launch(MESH)
    spec = tuple(got_x.sharding.spec) + (None,) * got_x.ndim
    out["kernels"][case] = {
        "replicated": bool(got_w.sharding.is_fully_replicated),
        "on_every_device": got_w.sharding.device_set == set(MESH.devices.flat),
        "words_equal": bool(np.array_equal(got_w, want_w)),
        "shapes": [list(got_w.shape), list(want_w.shape),
                   list(got_x.shape), list(want_x.shape)],
        "state_equal": same(got_x, want_x),
        "state_lane_sharded": (not got_x.sharding.is_fully_replicated
                               and spec[lane_axis] == "data"),
        "builds_again": sharded_launch_builds() - builds,
        "again_equal": bool(np.array_equal(again_w, got_w))
                       and same(again_x, got_x)}
print("RESULT " + json.dumps(out))
"""


@pytest.fixture(scope="module")
def found():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.pathsep.join(
                   [str(ROOT / "src"), str(ROOT / "tests"), str(ROOT)]))
    p = subprocess.run([sys.executable, "-c", SCRIPT], env=env, cwd=ROOT,
                       capture_output=True, text=True, timeout=600)
    line = [x for x in p.stdout.splitlines() if x.startswith("RESULT ")]
    assert p.returncode == 0 and line, p.stdout[-2000:] + p.stderr[-4000:]
    return json.loads(line[-1][len("RESULT "):])


def test_words_and_state_match_the_unsharded_launch(found):
    assert found["words_equal_unsharded"]
    assert found["state_equal_unsharded"]


def test_wide_core_matches_the_solo_kernel_and_the_plain_reference(found):
    assert found["equal_solo_kernel"]
    assert found["equal_plain_reference"]


def test_one_build_per_launch_shape_and_no_steady_compiles(found):
    # both cores launch 8 steps: one sharded callable serves them
    assert found["builds_first"] == 1
    assert found["builds_all"] == 1
    assert found["steady_compiles"] == []


def test_every_launch_on_the_mesh_runs_split(found):
    assert found["mesh_launches"] == 10            # 2 cores x 5 flushes
    assert found["mesh_launches_split"] == found["mesh_launches"]


def test_a_pool_that_does_not_divide_the_mesh_is_padded_and_counted(found):
    ragged = found["ragged"]
    assert ragged["pool"] == 18
    assert ragged["equal"]
    assert ragged["mesh_launches"] == 4
    assert ragged["mesh_launches_split"] == 4


def test_a_launch_that_ignores_the_mesh_counts_as_unsplit(found):
    assert found["ref_backend"] == {"mesh_launches": 2.0,
                                    "mesh_launches_split": 0.0}


def test_no_fetch_on_the_mesh_assembles_its_words(found):
    assert found["fetch_assembled"] == 0
    assert found["ragged"]["fetch_assembled"] == 0


KERNEL_CASES = ("solo", "gang", "stacked", "solo_padded", "gang_padded")


@pytest.mark.parametrize("case", KERNEL_CASES)
def test_sharded_words_come_back_whole_on_every_device(found, case):
    got = found["kernels"][case]
    assert got["replicated"]
    assert got["on_every_device"]


@pytest.mark.parametrize("case", KERNEL_CASES)
def test_gathered_words_and_state_match_the_unsharded_launch(found, case):
    got = found["kernels"][case]
    assert got["words_equal"]
    assert got["state_equal"]


@pytest.mark.parametrize("case", ("solo", "gang", "stacked"))
def test_the_state_keeps_its_lane_sharding(found, case):
    assert found["kernels"][case]["state_lane_sharded"]


@pytest.mark.parametrize("case", KERNEL_CASES)
def test_a_second_launch_of_a_shape_builds_nothing(found, case):
    got = found["kernels"][case]
    assert got["builds_again"] == 0
    assert got["again_equal"]


@pytest.mark.parametrize("case", ("solo_padded", "gang_padded"))
def test_a_padded_pool_slices_its_dead_lanes_off_the_gathered_words(
        found, case):
    words, want_words, state, want_state = found["kernels"][case]["shapes"]
    lanes = {"solo_padded": 18, "gang_padded": 6 * 128}[case]
    assert words == want_words == [8, lanes]
    assert state == want_state == [lanes, 3]
