"""Auto-generated by repro.core.codegen — do not edit.

HENNC chaotic oscillator core: lorenz 3-8-3 ANN,
solution P=1 (vpu, bfloat16), s_block=256,
t_block=256, unroll=8.
"""
import pathlib

import jax.numpy as jnp
import numpy as np

from repro.kernels import ops
from repro.kernels.chaotic_ann import chaotic_ann_pallas

_HERE = pathlib.Path(__file__).parent
_W = dict(np.load(_HERE / "weights.npz"))

SYSTEM = "lorenz"
I_DIM, H_DIM = 3, 8
S_BLOCK, T_BLOCK, UNROLL = 256, 256, 8
COMPUTE_UNIT, DTYPE = "vpu", jnp.bfloat16
ACTIVATION = "relu"
SCALE = np.asarray([np.float64(17.938642501831055), np.float64(24.061004638671875), np.float64(19.61319923400879)], dtype=np.float32)
OFFSET = np.asarray([np.float64(-0.10909461975097656), np.float64(-0.20266056060791016), np.float64(25.184228897094727)], dtype=np.float32)


def params():
    return {k: jnp.asarray(v) for k, v in _W.items()}


def generate(x0, n_steps, *, interpret=None):
    """Run the frozen oscillator core. x0: (S, I) normalized seeds."""
    if interpret is None:
        interpret = ops.resolve_backend() == "pallas_interpret"
    p = params()
    return chaotic_ann_pallas(
        p["w1"], p["b1"], p["w2"], p["b2"], jnp.asarray(x0, DTYPE),
        n_steps=n_steps, s_block=S_BLOCK, t_block=T_BLOCK, unroll=UNROLL,
        activation=ACTIVATION, compute_unit=COMPUTE_UNIT, interpret=interpret)


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
