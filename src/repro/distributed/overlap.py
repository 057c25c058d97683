"""Ring collective matmul: all-gather overlapped with partial matmuls.

For an FSDP-sharded weight W = concat_k(W_k) along the contraction dim,
y = x @ W can hide the gather latency: each of the N ring steps multiplies
the locally-resident shard while the next shard is in flight
(collective_permute), instead of waiting for a full all-gather. This is
the TPU analogue of overlapped FSDP unsharding, expressed in shard_map
so XLA schedules the permute concurrently with the dot.

On real hardware the win is the gather latency (bounded by ICI link
time); the dry-run's HLO shows N collective-permutes of 1/N size instead
of one all-gather, which the §Perf log uses to reason about overlap.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


def ring_allgather_matmul(
    x: jax.Array,
    w: jax.Array,
    mesh: Mesh,
    axis_name: str = "data",
):
    """y = x @ W with W row-sharded over `axis_name` and x row-local.

    Args:
      x: (..., K) activations, replicated over `axis_name`.
      w: (K, N) weight, sharded (K/axis,) on dim 0 across `axis_name`.

    Returns:
      y: (..., N) replicated over `axis_name`.
    """
    n = mesh.shape[axis_name]
    k = w.shape[0]
    assert k % n == 0, (k, n)
    shard_k = k // n

    def body(x_l, w_l):
        # x_l: full x (replicated); w_l: (shard_k, N) local shard.
        idx = jax.lax.axis_index(axis_name)

        def step(i, carry):
            acc, w_cur = carry
            # Which global shard does w_cur correspond to at step i?
            src = (idx + i) % n
            x_piece = jax.lax.dynamic_slice_in_dim(
                x_l, src * shard_k, shard_k, axis=-1
            )
            acc = acc + jnp.einsum("...k,kn->...n", x_piece, w_cur)
            # Rotate shards around the ring (overlaps with next matmul).
            w_nxt = jax.lax.ppermute(
                w_cur, axis_name,
                perm=[(j, (j - 1) % n) for j in range(n)],
            )
            return acc, w_nxt

        acc0 = jnp.zeros(x_l.shape[:-1] + (w_l.shape[-1],), x_l.dtype)
        acc, _ = jax.lax.fori_loop(0, n, step, (acc0, w_l))
        return acc

    fn = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(P(), P(axis_name, None)),
        out_specs=P(),
        check_vma=False,
    )
    return fn(x, w)
