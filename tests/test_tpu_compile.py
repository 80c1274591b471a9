"""Rehearsal compiles: the served kernels, compiled for a described v5e.

Nothing runs here.  Each test lowers one kernel of the farm's served path
at the committed solutions and real lane counts, and compiles it with the
TPU compiler for a v5e that is described, not attached — which is where
Mosaic refuses a lowering gap, a shape it cannot lay out, or a kernel
over the scoped-VMEM limit, things interpret mode cannot see.  A compile
that passes is not a chip run.

The topology is described inside a module fixture (never at import):
only the worker that runs this file loads the TPU library.
"""
import json
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from repro.core.dse import Candidate, select_config
from repro.kernels import chaotic_ann as ca

FARM_DIR = (pathlib.Path(__file__).resolve().parents[1]
            / "results" / "generated_cores" / "farm")
LANES_PER_CLIENT = 128
TENANTS = 64                          # per core: 8192 lanes
S_CORE = TENANTS * LANES_PER_CLIENT
N_STEPS = 512                         # one whole 256-step time block


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        t = topologies.get_topology_desc(platform="tpu",
                                         topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "no TPU compiler"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the cache but cannot be
    # read back without one: keep the cache out of these compiles
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield t
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _served(name: str) -> Candidate:
    """A committed core's solution as the farm serves it: the stream
    block clamped to one client's lanes (``OscillatorFarm.from_generated``)."""
    sol = json.loads((FARM_DIR / name / "solution.json").read_text())
    return Candidate(**dict(sol["candidate"], p=0))


def _ring32(unit: str) -> Candidate:
    return select_config(96, 256, s_total=LANES_PER_CLIENT, unit=unit,
                         n_nodes=32)


def _kw(c: Candidate):
    return dict(n_steps=N_STEPS, s_block=c.s_block, t_block=c.t_block,
                unroll=c.unroll, compute_unit=c.compute_unit)


def _compile(fn, *args):
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text      # the Pallas kernel, not a fallback
    return text


def _sds(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("core", ["chen", "hyperlorenz"])
def test_solo_bf16_vpu_compiles(one_chip, core):
    c = _served(core)
    assert c.dtype_bytes == 2 and c.compute_unit == "vpu"
    i, h, dt = c.i_dim, c.h_dim, jnp.bfloat16
    _compile(lambda *a: ca.chaotic_ann_bits_pallas(*a, **_kw(c)),
             _sds(one_chip, (i, h), dt), _sds(one_chip, (h,), dt),
             _sds(one_chip, (h, i), dt), _sds(one_chip, (i,), dt),
             _sds(one_chip, (S_CORE, i), dt),
             _sds(one_chip, (S_CORE,), jnp.uint32))


def test_stacked_bf16_gang_ragged_compiles(one_chip):
    """The four equal 3-8 bf16 cores as the planner stacks them, with a
    per-core row map (the ragged freeze)."""
    c = _served("chen")
    n, i, h, dt = 4, c.i_dim, c.h_dim, jnp.bfloat16
    _compile(lambda *a: ca.chaotic_ann_gang_stacked_pallas(*a, **_kw(c)),
             _sds(one_chip, (n, i, h), dt), _sds(one_chip, (n, h), dt),
             _sds(one_chip, (n, h, i), dt), _sds(one_chip, (n, i), dt),
             _sds(one_chip, (n, S_CORE, i), dt),
             _sds(one_chip, (n, S_CORE), jnp.uint32),
             _sds(one_chip, (n,), jnp.int32))


def test_stacked_gang_at_model_cliff_compiles(one_chip):
    """The planner stacks the served bf16 group up to the member count
    where ``stacked_gang_vmem_bytes`` reaches the budget: the compiler
    must accept that largest stack under the same limit."""
    from repro.core.dse import VMEM_USABLE, stacked_gang_vmem_bytes
    c = _served("chen")
    n = 1
    while stacked_gang_vmem_bytes(c, n + 1) <= VMEM_USABLE:
        n += 1
    i, h, dt = c.i_dim, c.h_dim, jnp.bfloat16
    _compile(lambda *a: ca.chaotic_ann_gang_stacked_pallas(*a, **_kw(c)),
             _sds(one_chip, (n, i, h), dt), _sds(one_chip, (n, h), dt),
             _sds(one_chip, (n, h, i), dt), _sds(one_chip, (n, i), dt),
             _sds(one_chip, (n, c.s_block, i), dt),
             _sds(one_chip, (n, c.s_block), jnp.uint32),
             _sds(one_chip, (n,), jnp.int32))


def _concat_args(sharding, c: Candidate, n_cores: int, n_blocks: int,
                 dt, maps_sharding=None):
    i, h = c.i_dim, c.h_dim
    ms = maps_sharding or sharding
    return (_sds(sharding, (n_cores, i, h), dt),
            _sds(sharding, (n_cores, h), dt),
            _sds(sharding, (n_cores, h, i), dt),
            _sds(sharding, (n_cores, i), dt),
            _sds(ms[0] if isinstance(ms, tuple) else ms,
                 (n_blocks * c.s_block, i), dt),
            _sds(ms[1] if isinstance(ms, tuple) else ms,
                 (n_blocks * c.s_block,), jnp.uint32),
            _sds(ms[1] if isinstance(ms, tuple) else ms,
                 (n_blocks,), jnp.int32),
            _sds(ms[1] if isinstance(ms, tuple) else ms,
                 (n_blocks,), jnp.int32))


def test_lane_concat_ragged_gang_compiles(one_chip):
    """Four cores' pools concatenated on lanes, each lane block with its
    own core id and row demand (scalar-prefetched maps)."""
    c = _served("chen")
    n_blocks = 4 * S_CORE // c.s_block

    def launch(w1, b1, w2, b2, x0, off, cmap, rmap):
        return ca.chaotic_ann_gang_bits_pallas(
            w1, b1, w2, b2, x0, cmap, off, rmap, **_kw(c))

    _compile(launch, *_concat_args(one_chip, c, 4, n_blocks, jnp.bfloat16))


@pytest.mark.parametrize("unit", ["vpu", "mxu"])
def test_ring32_lattice_compiles(one_chip, unit):
    """``chen@ring32`` at its DSE solution for each unit.  The vpu kernel
    needs more scoped VMEM than Mosaic's 16 MiB default: it compiles only
    because every kernel is given the DSE budget."""
    c = _ring32(unit)
    assert c.compute_unit == unit
    from repro.prng.stream import default_params
    params = default_params(system="chen@ring32")
    from repro.core.ann import lattice_meta_tuple
    lattice = lattice_meta_tuple(np.asarray(params["lattice_meta"]))
    i, h, dt = c.i_dim, c.h_dim, jnp.float32
    args = [_sds(one_chip, (i, h), dt), _sds(one_chip, (h,), dt),
            _sds(one_chip, (h, i), dt), _sds(one_chip, (i,), dt),
            _sds(one_chip, (S_CORE, i), dt),
            _sds(one_chip, (S_CORE,), jnp.uint32)]
    if unit == "mxu":
        args.append(_sds(one_chip, (i, i), dt))
    _compile(lambda *a: ca.chaotic_ann_bits_pallas(*a, lattice=lattice,
                                                   **_kw(c)), *args)


def test_sharded_lane_concat_gang_compiles_on_four_chips(topo):
    """The lane-concat gang inside ``shard_map`` over four described
    chips: each device holds a quarter of the lane blocks and its own
    slice of both scalar-prefetch maps; the weights are replicated."""
    c = _served("chen")
    mesh = Mesh(np.asarray(topo.devices[:4]), ("data",))
    rep = NamedSharding(mesh, P())
    lanes = (NamedSharding(mesh, P("data", None)),
             NamedSharding(mesh, P("data")))
    n_blocks = 4 * S_CORE // c.s_block
    fn = ca._sharded_gang_bits_fn(
        mesh, "data", True, N_STEPS, c.s_block, c.t_block, c.unroll,
        "relu", c.compute_unit, None, False, False)
    args = _concat_args(rep, c, 4, n_blocks, jnp.bfloat16,
                        maps_sharding=lanes)
    text = fn.lower(*args).compile().as_text()
    assert "tpu_custom_call" in text
