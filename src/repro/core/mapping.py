"""mapWB — Module 2 of IMAC-Sim (Algorithm 1, line 2).

Maps trained weights/biases to differential conductance pairs
(W+, W-, B+, B-) for a given synaptic technology [R_low, R_high].

Scheme (standard differential mapping used by the IMAC papers):
  w >= 0:  G+ = G_off + (w / w_scale) * (G_on - G_off),   G- = G_off
  w <  0:  G- = G_off + (|w|/ w_scale) * (G_on - G_off),  G+ = G_off
so that (G+ - G-) = w * (G_on - G_off) / w_scale, and the differential
column current is
  I+ - I- = sum_i (G+_ij - G-_ij) V_i = k * (W^T v)   with
  k = (G_on - G_off) / w_scale.

`w_scale` is the per-layer max |w| (biases included), so every layer uses
the full conductance range. The inverse sense factor needed to recover the
digital pre-activation z = W^T a + b from the differential current is
returned as `sense_r` (ohms): z = (I+ - I-) * sense_r / v_unit with inputs
encoded as V = a * v_unit.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp

from repro.core.devices import DeviceTech


@dataclasses.dataclass
class MappedLayer:
    """Differential conductances for one layer (bias folded as extra row).

    g_pos/g_neg have shape (fan_in + 1, fan_out); row -1 is the bias row,
    driven at `v_unit` volts.
    """

    g_pos: jax.Array
    g_neg: jax.Array
    w_scale: float        # max |w| used for normalisation
    k: float              # (G_on - G_off) / w_scale  [S per unit weight]
    sense_r: float        # ohms; z = I_diff * sense_r / v_unit
    v_unit: float         # volts encoding one unit of activation

    @property
    def fan_in(self) -> int:
        return self.g_pos.shape[0] - 1

    @property
    def fan_out(self) -> int:
        return self.g_pos.shape[1]

    @property
    def g_diff(self) -> jax.Array:
        return self.g_pos - self.g_neg

    def effective_weights(self) -> jax.Array:
        """Recover the (quantised) weight matrix implied by conductances."""
        return self.g_diff / self.k


def map_wb(
    weights: jax.Array,
    biases: jax.Array,
    tech: DeviceTech,
    v_unit: float,
    *,
    quantize: bool = True,
    variation_key: Optional[jax.Array] = None,
    w_scale: Optional[float] = None,
) -> MappedLayer:
    """Map one layer's (fan_in, fan_out) weights + (fan_out,) biases.

    Args:
      weights: (fan_in, fan_out) float weights.
      biases: (fan_out,) float biases.
      tech: synaptic technology defining [G_off, G_on].
      v_unit: input-voltage encoding of one unit of activation (volts).
      quantize: snap to the device's programmable levels.
      variation_key: if given, apply lognormal programming variation.
      w_scale: optional fixed normalisation (default: per-layer max |w|).

    Returns:
      MappedLayer with bias folded in as the last row.
    """
    weights = jnp.asarray(weights)
    biases = jnp.asarray(biases)
    if weights.ndim != 2 or biases.ndim != 1 or biases.shape[0] != weights.shape[1]:
        raise ValueError(
            f"bad shapes: weights {weights.shape}, biases {biases.shape}"
        )
    wb = jnp.concatenate([weights, biases[None, :]], axis=0)
    if w_scale is None:
        w_scale = float(jnp.max(jnp.abs(wb)))
    if w_scale <= 0.0:
        w_scale = 1.0
    wn = wb / w_scale  # in [-1, 1]
    g_pos, g_neg = _conductance_pair(wn, tech, quantize, variation_key)

    k = tech.g_range / w_scale
    sense_r = 1.0 / (k * 1.0)  # z = I_diff / (k * v_unit) * 1.0; see below
    # With V_i = a_i * v_unit:  I_diff = k * v_unit * z  =>  z = I_diff/(k v_unit)
    return MappedLayer(
        g_pos=g_pos,
        g_neg=g_neg,
        w_scale=w_scale,
        k=k,
        sense_r=sense_r,
        v_unit=v_unit,
    )


def _conductance_pair(wn, tech, quantize, variation_key=None):
    """(G+, G-) of weights normalised to [-1, 1]."""
    g_pos = tech.g_off + jnp.maximum(wn, 0.0) * tech.g_range
    g_neg = tech.g_off + jnp.maximum(-wn, 0.0) * tech.g_range
    if quantize:
        g_pos = tech.quantize(g_pos)
        g_neg = tech.quantize(g_neg)
    if variation_key is not None:
        kp, kn = jax.random.split(variation_key)
        g_pos = tech.perturb(kp, g_pos)
        g_neg = tech.perturb(kn, g_neg)
    return g_pos, g_neg


def map_matrix(
    weights: jax.Array,
    tech: DeviceTech,
    *,
    quantize: bool = True,
) -> "tuple[jax.Array, jax.Array, float]":
    """Map a bias-free (fan_in, fan_out) weight matrix.

    The matrix is scaled by its largest magnitude onto [G_off, G_on], as
    `map_wb` does, with no bias row. Returns (G+, G-, k) with
    (G+ - G-) = k * W.
    """
    weights = jnp.asarray(weights)
    if weights.ndim != 2:
        raise ValueError(f"bad shape: weights {weights.shape}")
    w_scale = float(jnp.max(jnp.abs(weights)))
    if w_scale <= 0.0:
        w_scale = 1.0
    g_pos, g_neg = _conductance_pair(weights / w_scale, tech, quantize)
    return g_pos, g_neg, tech.g_range / w_scale


def map_network(
    params: "list[tuple[jax.Array, jax.Array]]",
    tech: DeviceTech,
    v_unit: float,
    *,
    quantize: bool = True,
    variation_key: Optional[jax.Array] = None,
) -> "list[MappedLayer]":
    """mapWB over a whole T_N = [L_1 .. L_n] topology."""
    keys = (
        jax.random.split(variation_key, len(params))
        if variation_key is not None
        else [None] * len(params)
    )
    return [
        map_wb(w, b, tech, v_unit, quantize=quantize, variation_key=k)
        for (w, b), k in zip(params, keys)
    ]
