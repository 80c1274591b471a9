"""Auto-generated by repro.core.codegen — do not edit.

HENNC chaotic oscillator core: chen 3-8-3 ANN,
solution P=5 (vpu, bfloat16), s_block=4096,
t_block=256, unroll=8.
"""
import pathlib

import jax.numpy as jnp
import numpy as np

from repro.kernels import ops

_HERE = pathlib.Path(__file__).parent
_W = dict(np.load(_HERE / "weights.npz"))

SYSTEM = "chen"
I_DIM, H_DIM = 3, 8
N_NODES = 1
S_BLOCK, T_BLOCK, UNROLL = 4096, 256, 8
COMPUTE_UNIT, DTYPE = "vpu", jnp.bfloat16
ACTIVATION = "relu"
SCALE = np.asarray([np.float64(24.578670501708984), np.float64(28.151321411132812), np.float64(20.312288284301758)], dtype=np.float32)
OFFSET = np.asarray([np.float64(0.2676715850830078), np.float64(0.3592557907104492), np.float64(27.184282302856445)], dtype=np.float32)


def params():
    return {k: jnp.asarray(v) for k, v in _W.items()}


def generate(x0, n_steps, *, interpret=None):
    """Run the frozen oscillator core. x0: (S, I) normalized seeds.

    Routed through ``ops.chaotic_trajectory`` so a lattice core (weights
    carrying ``lattice_meta``/``coupling``) dispatches its block-coupled
    kernel path with no change to the generated surface.
    """
    if interpret is None:
        interpret = ops.resolve_backend() == "pallas_interpret"
    backend = "pallas_interpret" if interpret else "pallas"
    return ops.chaotic_trajectory(
        params(), jnp.asarray(x0, DTYPE), n_steps, activation=ACTIVATION,
        backend=backend, s_block=S_BLOCK, t_block=T_BLOCK, unroll=UNROLL,
        compute_unit=COMPUTE_UNIT)


def generate_bits(x0, n_steps, word_offset=0, *, backend="auto"):
    """Fused, resumable PRNG draw: ((n_steps // 2, S) uint32 words,
    (S, I) final state).

    Draws through the same fused ``ops.chaotic_bits`` launch that
    ``repro.serve`` batches clients onto (the float trajectory never
    leaves VMEM); ``word_offset`` + the returned state resume the exact
    word sequence of one long draw, chunk-size invariantly.
    """
    return ops.chaotic_bits(
        params(), jnp.asarray(x0, DTYPE), n_steps, word_offset,
        activation=ACTIVATION, backend=backend, s_block=S_BLOCK,
        t_block=T_BLOCK, unroll=UNROLL, compute_unit=COMPUTE_UNIT)
