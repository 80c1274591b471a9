"""Pure-jnp oracles for the Pallas kernels (the co-simulation references)."""
from __future__ import annotations

import jax
import jax.numpy as jnp

Array = jax.Array


def chaotic_ann_ref(w1: Array, b1: Array, w2: Array, b2: Array,
                    x0: Array, n_steps: int, activation: str = "relu") -> Array:
    """Iterate the I-H-I oscillator ``n_steps`` times for a batch of streams.

    Args:
      w1: (I, H); b1: (H,); w2: (H, I); b2: (I,)
      x0: (S, I) initial states, one independent oscillator per row.
    Returns:
      (n_steps, S, I) trajectory (excluding x0), in x0's dtype.
    """
    phi = {"relu": jax.nn.relu, "tanh": jnp.tanh, "sigmoid": jax.nn.sigmoid}[activation]
    dtype = x0.dtype
    # exact f32 products on a TPU too, whose default f32 dot is one bf16 pass
    prec = jax.lax.Precision.HIGHEST if dtype == jnp.float32 else None

    def step(x, _):
        h = phi(jnp.matmul(x, w1.astype(dtype), precision=prec)
                + b1.astype(dtype))
        y = jnp.matmul(h, w2.astype(dtype), precision=prec) + b2.astype(dtype)
        return y, y

    _, traj = jax.lax.scan(step, x0, None, length=n_steps)
    return traj


def chaotic_ann_lattice_ref(w1: Array, b1: Array, w2: Array, b2: Array,
                            x0: Array, n_steps: int,
                            activation: str = "relu", *, lattice,
                            coupling: Array | None = None,
                            compute_unit: str = "vpu") -> Array:
    """Block-coupled lattice oracle, bitwise identical to the Pallas kernels.

    Unlike ``chaotic_ann_ref`` (an independent ``x @ w`` formulation that
    matches the mxu kernel bitwise but the vpu kernel only to fp-order
    ulps), the lattice oracle scans the kernels' own ``_make_step`` closure
    on the kernels' own (I, S) layout — same expression tree, same
    accumulation order — so ref-vs-Pallas equality is exact for BOTH
    compute units, which is what pins down the coupled dynamics.

    Args:
      w1 (I, H), b1 (H,), w2 (H, I), b2 (I,), x0 (S, I) — lattice-expanded.
      lattice: static ``(n_nodes, base_dim, topology, strength)``.
      coupling: dense (I, I) operator; required when compute_unit="mxu".
      compute_unit: which kernel expression tree to mirror — the two units
        produce legitimately different (both deterministic) streams.
    Returns:
      (n_steps, S, I) trajectory (excluding x0), in x0's dtype.
    """
    from repro.kernels.chaotic_ann import (_check_lattice, _make_step,
                                           _pad_params)
    dtype = x0.dtype
    i_dim, h_dim = w1.shape
    _check_lattice(lattice, i_dim, i_dim)
    cpl = None
    if compute_unit == "mxu":
        if coupling is None:
            raise ValueError("mxu lattice oracle needs the coupling operand")
        cpl = coupling.astype(dtype)
    step = _make_step(
        *_pad_params(w1, b1, w2, b2, i_pad=i_dim, h_pad=h_dim, dtype=dtype,
                     compute_unit=compute_unit),
        activation=activation, compute_unit=compute_unit,
        i_dim=i_dim, h_dim=h_dim, lattice=lattice, cpl=cpl)

    def body(x, _):
        y = step(x)
        return y, y

    _, traj = jax.lax.scan(body, x0.T, None, length=n_steps)
    return traj.transpose(0, 2, 1)
