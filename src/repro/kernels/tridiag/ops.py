"""Public jit'd wrapper for the tridiag Pallas kernel.

Accepts the solver's native (..., N) layout, flattens the batch onto the
lane axis, pads to 128 and dispatches to the kernel. On non-TPU backends
it runs in interpret mode.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core.backends import on_tpu
from repro.kernels.tridiag.kernel import LANES, tridiag_nb


@functools.partial(jax.jit, static_argnames=("interpret",))
def tridiag(
    dl: jax.Array,
    d: jax.Array,
    du: jax.Array,
    b: jax.Array,
    *,
    interpret: "bool | None" = None,
) -> jax.Array:
    """Batched tridiagonal solve along the last axis, Pallas-accelerated.

    Drop-in replacement for solver.tridiag_scan (same semantics:
    dl[..., 0] / du[..., -1] ignored).
    """
    if interpret is None:
        interpret = not on_tpu()
    shape = d.shape
    n = shape[-1]
    batch = 1
    for s in shape[:-1]:
        batch *= s

    def flat(a):
        return jnp.broadcast_to(a, shape).reshape(batch, n).T  # (N, B)

    pad = (-batch) % LANES
    args = []
    for a in (dl, d, du, b):
        a = flat(a)
        if pad:
            # Padding systems solve d*x = 0 with d=1 — harmless.
            fill = jnp.ones((n, pad), a.dtype) if a is not None else None
            a = jnp.concatenate([a, fill], axis=1)
        args.append(a)
    # Ensure padded diagonal is nonsingular: replace d-pad with ones, the
    # off-diagonals/rhs with zeros.
    if pad:
        dl_p, d_p, du_p, b_p = args
        zeros = jnp.zeros((n, pad), d_p.dtype)
        args = [
            jnp.concatenate([dl_p[:, :batch], zeros], axis=1),
            d_p,
            jnp.concatenate([du_p[:, :batch], zeros], axis=1),
            jnp.concatenate([b_p[:, :batch], zeros], axis=1),
        ]
    x = tridiag_nb(*args, interpret=interpret)
    return x[:, :batch].T.reshape(shape)
