"""A core launched alone on a mesh: one build per launch shape, split over
every device, bit-identical to the unsharded launch.

A core that gangs with no other (the 4-16-4 core of a farm whose other
cores are 3-8-3) takes the solo path, ``PRNGService._launch`` ->
``ops.chaotic_bits(..., mesh=)``.  On a mesh of more than one device that
launch runs one jitted ``shard_map`` per step count, built once and
reused, as the gang launches are.  A pool that does not divide the device
count is padded with dead lanes, as the gang path pads its block axis.
The tracer counts every launch of a pool on a mesh (``mesh_launches``),
those whose words came back from every device (``mesh_launches_split``)
and the sharded callables built (``launch_builds``).

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

from test_kernels import _mk
from bench.cores import ann
from repro.kernels import ops
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
                 "mesh_launches_split": st["mesh_launches_split"]}

# the reference backend ignores the mesh: its launches are not split
oracle = farm(MESH, tenants=1, backend="ref")
flush(oracle, 128)
st = oracle.profile_stats
out["ref_backend"] = {"mesh_launches": st["mesh_launches"],
                      "mesh_launches_split": st["mesh_launches_split"]}
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
