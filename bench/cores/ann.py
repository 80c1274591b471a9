"""Core kind ``ann``: one I-H-I oscillator network read from its committed
weights, with its plain reference, independent of the program.

A core kind (``bench/cores/<kind>.py``, named by a configuration's core
entry) gives the harness four functions: ``program_params`` (what the
program's ``add_core`` is handed), ``load`` and ``launch`` (the
reference's own weights, and one launch of it), and ``work_class`` (the
work per word, ``bench/work.py``).

The oscillator of the paper (Eqs. 2-4): ``x' = W2^T relu(W1^T x + b1) + b2``
for one I-H-I network.  Two samples make one word: the low mantissa bits
of every state dimension are XOR-folded with odd shifts, the pair is
packed into 32 bits, whitened with a golden-ratio Weyl counter of the
absolute word row, and avalanched (Murmur3 finalizer).

Arithmetic is spelled out op by op, sums in index order, with every
product and every sum rounded to the precision the configuration states
(round to nearest, ties to even, done on the integer bits so that no
compiler can keep excess precision): ``bfloat16`` keeps 7 mantissa bits,
``float32`` 23.  The control precision below ``bfloat16`` is ``float8``,
the 3 mantissa bits of fp8 e4m3.

Nothing here imports the program: the weights are read from the
configuration's committed files by ``load``.
"""
from __future__ import annotations

import functools
import pathlib
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

from bench import work

GOLDEN = 0x9E3779B9
MANTISSA_BITS = {"float32": 23, "bfloat16": 7, "float8": 3}


def round_mantissa(v, bits: int):
    """f32 -> f32 rounded to ``bits`` mantissa bits, ties to even."""
    drop = 23 - bits
    if drop == 0:
        return v
    u = jax.lax.bitcast_convert_type(v, jnp.uint32)
    lsb = (u >> jnp.uint32(drop)) & jnp.uint32(1)
    r = (u + jnp.uint32((1 << (drop - 1)) - 1) + lsb) & jnp.uint32(
        (0xFFFFFFFF << drop) & 0xFFFFFFFF)
    return jax.lax.bitcast_convert_type(r, jnp.float32)


def make_step(w1, b1, w2, b2, *, precision: str):
    """One oscillator step on x of shape (S, I), in ``precision``."""
    bits = MANTISSA_BITS[precision]

    def rnd(v):
        return round_mantissa(v, bits)

    w1, b1, w2, b2 = (rnd(jnp.asarray(a, jnp.float32))
                      for a in (w1, b1, w2, b2))
    i_dim, h_dim = w1.shape

    def step(x):
        h = jnp.zeros(x.shape[:1] + (h_dim,), jnp.float32)
        for i in range(i_dim):
            h = rnd(h + rnd(x[:, i:i + 1] * w1[i][None, :]))
        h = jax.nn.relu(rnd(h + b1[None, :]))
        y = jnp.zeros_like(x)
        for j in range(h_dim):
            y = rnd(y + rnd(h[:, j:j + 1] * w2[j][None, :]))
        return rnd(y + b2[None, :])

    return step


@functools.partial(jax.jit, static_argnames=("n_steps", "precision"))
def trajectory(w1, b1, w2, b2, x0, *, n_steps: int, precision: str):
    """(n_steps, S, I) states after x0 (x0 excluded), as float32."""
    step = make_step(w1, b1, w2, b2, precision=precision)

    def body(x, _):
        y = step(x)
        return y, y

    x0 = round_mantissa(x0.astype(jnp.float32), MANTISSA_BITS[precision])
    return jax.lax.scan(body, x0, None, length=n_steps)[1]


def _finalize(w: np.ndarray) -> np.ndarray:
    w = w ^ (w >> np.uint32(16))
    w = w * np.uint32(0x85EBCA6B)
    w = w ^ (w >> np.uint32(13))
    w = w * np.uint32(0xC2B2AE35)
    return w ^ (w >> np.uint32(16))


def pack_words(traj: np.ndarray, row0: np.ndarray, half: bool) -> np.ndarray:
    """Words of a (2R, S, I) float32 trajectory: (R, S) uint32.

    ``row0`` (S,) is each lane's absolute word row at the first sample;
    ``half``: the state is a 16-bit float held in float32, whose low
    mantissa bits are the 7 below the top half of the bit pattern; else
    the low 16 bits of float32.
    """
    bits = np.ascontiguousarray(traj, np.float32).view(np.uint32)
    lo = (bits >> np.uint32(16)) & np.uint32(0x7F) if half else (
        bits & np.uint32(0xFFFF))
    folded = lo[..., 0].copy()
    for i in range(1, lo.shape[-1]):
        folded ^= lo[..., i] << np.uint32(5 * i % 16)
    words = (folded[0::2] << np.uint32(16)) | folded[1::2]
    rows = np.arange(words.shape[0], dtype=np.uint32)[:, None] + np.asarray(
        row0, np.uint32)[None, :]
    with np.errstate(over="ignore"):
        return _finalize(words ^ (rows * np.uint32(GOLDEN)))


def program_params(root: pathlib.Path, core: Dict) -> Dict:
    """What the program's ``add_core`` is handed: the committed weights."""
    with np.load(root / core["weights"]) as z:
        return {k: z[k] for k in z.files}


def load(root: pathlib.Path, core: Dict) -> Dict:
    """The reference's own copy of one core's weights, read from its
    committed file."""
    with np.load(root / core["weights"]) as z:
        return {k: np.asarray(z[k], np.float32)
                for k in ("w1", "b1", "w2", "b2")}


def launch(ref: Dict, x0: np.ndarray, row0: np.ndarray, n_rows: int,
           precision: str):
    """Words (n_rows, S) and final state (S, I) of one launch from the
    program's pre-launch state ``x0`` (S, I)."""
    traj = np.asarray(trajectory(
        ref["w1"], ref["b1"], ref["w2"], ref["b2"],
        jnp.asarray(x0, jnp.float32), n_steps=2 * n_rows,
        precision=precision))
    half = MANTISSA_BITS[precision] < 23
    return pack_words(traj, row0, half), traj[-1]


def work_class(ref: Dict, core: Dict) -> Dict:
    """The core's work per word (``bench/work.py``)."""
    return work.core_class(ref["w1"], ref["w2"], dtype=core["dtype"])
