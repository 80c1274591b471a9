# lint-as: src/repro/serve/custom_launcher.py
"""BAD: a serve-layer module wrapping its own shard_map around a launch.

Sharding belongs to the launch stack (``ops.chaotic_bits_gang(...,
mesh=)`` / ``ops.chaotic_bits(..., mesh=)``): a direct ``shard_map`` here bypasses
the gang scheduler, the cost model, and the topology-keyed plan caches,
and its words sit outside every bit-identity suite.
"""
import jax
from jax.sharding import PartitionSpec as P

from repro.kernels import ops


def launch_sharded(params, x0, n_steps, mesh):
    def local(x_l):
        return ops.chaotic_bits(params, x_l, n_steps, 0)

    fn = jax.shard_map(local, mesh=mesh, in_specs=(P("data", None),),
                       out_specs=(P(None, "data"), P("data", None)))
    return fn(x0)
