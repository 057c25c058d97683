"""Horizontal / vertical partitioning of IMAC layers (H_P, V_P).

IMAC-Sim's mapLayer splits each layer's crossbar into hp_j x vp_j
subarrays: horizontal partitions divide the *input* (row) dimension,
vertical partitions divide the *output* (column) dimension. Partitioning
shortens the resistive row/column lines, trading interface-circuit power
for IR-drop accuracy (paper Table III).

`auto_partition` reproduces the paper's Table III arithmetic exactly:
hp = ceil((fan_in + 1) / rows), vp = ceil(fan_out / cols) — the +1 is the
bias row, which IMAC-Sim folds into the first layer rows (the published
H_P=[13,4,3] etc. for the 400x120x84x10 MLP on 32x32 arrays follow).
"""
from __future__ import annotations

import dataclasses
import math
from typing import Sequence

import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class PartitionPlan:
    """Tiling of one (fan_in+1, fan_out) conductance matrix.

    Attributes:
      hp: number of horizontal partitions (row splits).
      vp: number of vertical partitions (column splits).
      rows: padded rows per tile.
      cols: padded cols per tile.
      total_rows: fan_in + 1 (bias row included).
      total_cols: fan_out.
    """

    hp: int
    vp: int
    rows: int
    cols: int
    total_rows: int
    total_cols: int

    @property
    def n_tiles(self) -> int:
        return self.hp * self.vp

    @property
    def row_pad(self) -> int:
        return self.hp * self.rows - self.total_rows

    @property
    def col_pad(self) -> int:
        return self.vp * self.cols - self.total_cols


def auto_partition(fan_in: int, fan_out: int, array_rows: int, array_cols: int) -> "tuple[int, int]":
    """Paper Table III: minimum partitions for a given subarray size."""
    hp = math.ceil((fan_in + 1) / array_rows)
    vp = math.ceil(fan_out / array_cols)
    return hp, vp


def plan_partition(
    fan_in: int,
    fan_out: int,
    hp: int,
    vp: int,
) -> PartitionPlan:
    """Plan tiling with user-specified (hp, vp), as IMAC-Sim allows any."""
    total_rows = fan_in + 1
    if hp < 1 or vp < 1:
        raise ValueError(f"partitions must be >= 1, got hp={hp} vp={vp}")
    if hp > total_rows or vp > fan_out:
        raise ValueError(
            f"more partitions than rows/cols: hp={hp} rows={total_rows}, "
            f"vp={vp} cols={fan_out}"
        )
    rows = math.ceil(total_rows / hp)
    cols = math.ceil(fan_out / vp)
    return PartitionPlan(
        hp=hp, vp=vp, rows=rows, cols=cols,
        total_rows=total_rows, total_cols=fan_out,
    )


def tile_matrix(g: jnp.ndarray, plan: PartitionPlan, fill: float = 0.0) -> jnp.ndarray:
    """Split (..., total_rows, total_cols) into (..., hp*vp, rows, cols)
    padded tiles; leading axes (e.g. a stacked-config axis) pass through.

    Padding cells get conductance `fill` (an absent/unprogrammed device;
    0 S = no device bridging the wires, the wire grid itself remains).
    """
    if g.shape[-2:] != (plan.total_rows, plan.total_cols):
        raise ValueError(f"matrix {g.shape} != plan {(plan.total_rows, plan.total_cols)}")
    lead = g.shape[:-2]
    pad = [(0, 0)] * len(lead) + [(0, plan.row_pad), (0, plan.col_pad)]
    padded = jnp.pad(g, pad, constant_values=fill)
    tiles = padded.reshape(*lead, plan.hp, plan.rows, plan.vp, plan.cols)
    order = tuple(range(len(lead)))
    tiles = tiles.transpose(
        *order, len(lead), len(lead) + 2, len(lead) + 1, len(lead) + 3
    )
    return tiles.reshape(*lead, plan.n_tiles, plan.rows, plan.cols)


def untile_matrix(tiles: jnp.ndarray, plan: PartitionPlan) -> jnp.ndarray:
    """Inverse of tile_matrix (drops padding)."""
    t = tiles.reshape(plan.hp, plan.vp, plan.rows, plan.cols)
    t = t.transpose(0, 2, 1, 3).reshape(plan.hp * plan.rows, plan.vp * plan.cols)
    return t[: plan.total_rows, : plan.total_cols]


def tile_inputs(v: jnp.ndarray, plan: PartitionPlan) -> jnp.ndarray:
    """Split (..., total_rows) input voltages into (..., hp, rows)."""
    if v.shape[-1] != plan.total_rows:
        raise ValueError(f"inputs {v.shape} != total_rows {plan.total_rows}")
    pad = plan.hp * plan.rows - plan.total_rows
    v = jnp.pad(v, [(0, 0)] * (v.ndim - 1) + [(0, pad)])
    return v.reshape(*v.shape[:-1], plan.hp, plan.rows)


def combine_outputs(per_tile: jnp.ndarray, plan: PartitionPlan) -> jnp.ndarray:
    """Sum partial output currents over horizontal partitions.

    Args:
      per_tile: (..., hp*vp, cols) per-tile column currents.

    Returns:
      (..., total_cols) combined currents (padding columns dropped).
    """
    t = per_tile.reshape(*per_tile.shape[:-2], plan.hp, plan.vp, plan.cols)
    summed = ordered_sum(t, axis=-3)  # partial sums over horizontal partitions
    out = summed.reshape(*summed.shape[:-2], plan.vp * plan.cols)
    return out[..., : plan.total_cols]


def ordered_sum(x: jnp.ndarray, axis: int = -1) -> jnp.ndarray:
    """Sum over one axis in a fixed pairwise order.

    A reduce op adds in whatever order the memory layout the compiler
    picks for it gives, and on TPU that layout follows the shapes around
    the op: the same configuration's power summed inside a stack of four
    configurations and inside a stack of one differed in the last bit.
    Halving with elementwise adds fixes the order, so per-configuration
    results do not depend on how many configurations share a program
    (sharded vs unsharded sweeps). Odd lengths pad with an exact zero.
    """
    axis = axis % x.ndim
    while x.shape[axis] > 1:
        if x.shape[axis] % 2:
            pad = [(0, 0)] * x.ndim
            pad[axis] = (0, 1)
            x = jnp.pad(x, pad)
        lo, hi = jnp.split(x, 2, axis=axis)
        x = lo + hi
    return jnp.squeeze(x, axis=axis)


def plan_topology(
    topology: Sequence[int],
    array_rows: int,
    array_cols: int,
    hp: "Sequence[int] | None" = None,
    vp: "Sequence[int] | None" = None,
) -> "list[PartitionPlan]":
    """Plans for every layer of T_N = [n_0, n_1, ..., n_L].

    If hp/vp are None they are derived from the array size (Table III).
    """
    n_layers = len(topology) - 1
    if hp is None or vp is None:
        auto = [
            auto_partition(topology[i], topology[i + 1], array_rows, array_cols)
            for i in range(n_layers)
        ]
        hp = [a[0] for a in auto]
        vp = [a[1] for a in auto]
    if len(hp) != n_layers or len(vp) != n_layers:
        raise ValueError(
            f"H_P/V_P length mismatch: {len(hp)}/{len(vp)} vs {n_layers} layers"
        )
    return [
        plan_partition(topology[i], topology[i + 1], hp[i], vp[i])
        for i in range(n_layers)
    ]
