"""Public wrapper for the imac_mvm kernel: pads to MXU tiles, picks
interpret mode off-TPU, and exposes device-physics semantics (technology
-> levels, read noise, energy estimate) for AnalogLinear.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.core.backends import on_tpu
from repro.core.devices import DeviceTech, get_tech
from repro.kernels.imac_mvm.kernel import imac_mvm_padded
from repro.kernels.imac_mvm.ref import imac_mvm_ref


def _pad_to(a: jax.Array, mult: int, axis: int) -> jax.Array:
    pad = (-a.shape[axis]) % mult
    if not pad:
        return a
    widths = [(0, 0)] * a.ndim
    widths[axis] = (0, pad)
    return jnp.pad(a, widths)


@functools.partial(
    jax.jit, static_argnames=("dac_bits", "levels", "interpret", "bm", "bn", "bk")
)
def imac_mvm(
    x: jax.Array,
    w: jax.Array,
    *,
    dac_bits: int = 8,
    levels: int = 16,
    bm: int = 128,
    bn: int = 128,
    bk: int = 128,
    interpret: "bool | None" = None,
) -> jax.Array:
    """Quantised differential analog MVM. x: (..., K) in [0,1] digital
    units; w: (K, N) in [-1,1] normalised weights. Returns (..., N)."""
    if interpret is None:
        interpret = not on_tpu()
    lead = x.shape[:-1]
    k = x.shape[-1]
    xf = x.reshape(-1, k)
    m = xf.shape[0]
    xp = _pad_to(_pad_to(xf, bm, 0), bk, 1)
    wp = _pad_to(_pad_to(w, bk, 0), bn, 1)
    y = imac_mvm_padded(
        xp, wp, dac_bits=dac_bits, levels=levels,
        bm=bm, bn=bn, bk=bk, interpret=interpret,
    )
    return y[:m, : w.shape[1]].reshape(*lead, w.shape[1])


def analog_linear(
    x: jax.Array,
    w: jax.Array,
    b: Optional[jax.Array],
    tech: "DeviceTech | str" = "PCM",
    *,
    dac_bits: int = 8,
    levels: Optional[int] = None,
    noise_key: Optional[jax.Array] = None,
    w_scale: Optional[jax.Array] = None,
    interpret: "bool | None" = None,
) -> jax.Array:
    """Drop-in y = x @ w + b simulated on ideal analog crossbars.

    Activations are normalised per-call into [0,1] (dynamic-range DAC),
    weights into [-1,1]; device physics sets the conductance level count
    and read noise. Used by the substrate's AnalogLinear serving mode.
    """
    tech = get_tech(tech)
    levels = levels or tech.levels or 16
    if w_scale is None:
        w_scale = jnp.max(jnp.abs(w)) + 1e-12
    x_lo = jnp.min(x)
    x_hi = jnp.max(x)
    x_rng = jnp.maximum(x_hi - x_lo, 1e-12)
    xn = (x - x_lo) / x_rng
    wn = w / w_scale
    y = imac_mvm(xn, wn, dac_bits=dac_bits, levels=levels, interpret=interpret)
    # Undo normalisation: x = xn*rng + lo -> x@w = (xn@wn)*rng*scale + lo*colsum.
    colsum = jnp.sum(w, axis=0)
    y = y * (x_rng * w_scale) + x_lo * colsum
    if noise_key is not None and tech.read_noise_rel > 0:
        y = y + tech.read_noise_rel * jnp.abs(y) * jax.random.normal(
            noise_key, y.shape, y.dtype
        )
    if b is not None:
        y = y + b
    return y.astype(x.dtype)
