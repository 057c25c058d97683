"""Monte-Carlo trial draws, rebuilt from a study's seed for the reference.

The benchmark's own copy of the simulator's sampling rules
(`variability.engine`, `core.devices`, and the read noise of
`core.imac.linear_forward` as `core.evaluate` keys it), so that the
reference can solve any trial of a call with the very devices and noise
the program drew, and cannot move with the program. Draws use
`jax.random` (the library, not the program):

  trial keys   split(PRNGKey(seed), T); trial t takes row t;
  variation    per layer l: (k+, k-) = split(split(trial key, L)[l]);
               G = clip(G0 * exp(sigma * normal(k, G0.shape)), G_off, G_on)
               with G0 the layer's mapped conductances (float32 draws);
  stuck faults per layer l: (f+, f-) = split(split(fold_in(trial key,
               0x0FA17), L)[l]); u = uniform(f, G.shape) in float32;
               stuck at G_on where u < p_on, at G_off where
               p_on <= u < p_on + p_off (each probability rounded to
               float32 as the comparison does);
  read noise   key = fold_in(PRNGKey(seed), 0x0A01E); one key per chunk of
               samples, split(key, chunks); per chunk and layer,
               split(chunk key, L)[l], a (T, chunk, fan_out) standard
               normal in float32 over all the study's trials at once.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

FAULT_SALT = 0x0FA17
NOISE_SALT = 0x0A01E


def trial_keys(seed: int, trials: int) -> jax.Array:
    """The (T, 2) per-trial keys of a study seed."""
    return jax.random.split(jax.random.PRNGKey(seed), trials)


def conductances(base, key, g_on: float, g_off: float, sigma_rel: float,
                 p_stuck_on: float, p_stuck_off: float):
    """Per layer (G+, G-, k) of one trial, float64.

    `base`: per layer (G+, G-, k) of the unperturbed mapping
    (`reference.map_layer`); `key`: the trial's key.
    """
    n_layers = len(base)
    layer_keys = jax.random.split(key, n_layers)
    fault_keys = jax.random.split(jax.random.fold_in(key, FAULT_SALT), n_layers)
    faults = p_stuck_on > 0.0 or p_stuck_off > 0.0
    out = []
    for layer, (g_pos, g_neg, k) in enumerate(base):
        drawn = []
        for g, vkey, fkey in zip((g_pos, g_neg),
                                 jax.random.split(layer_keys[layer]),
                                 jax.random.split(fault_keys[layer])):
            if sigma_rel > 0.0:
                noise = np.asarray(jax.random.normal(vkey, g.shape, jnp.float32),
                                   np.float64)
                g = np.clip(g * np.exp(sigma_rel * noise), g_off, g_on)
            if faults:
                u = np.asarray(jax.random.uniform(fkey, g.shape, jnp.float32))
                on = u < np.float32(p_stuck_on)
                off = (u >= np.float32(p_stuck_on)) & (
                    u < np.float32(p_stuck_on + p_stuck_off))
                g = np.where(on, g_on, np.where(off, g_off, g))
            drawn.append(g)
        out.append((drawn[0], drawn[1], k))
    return out


def read_noise(seed: int, trials: int, n_samples: int, chunk: int,
               fan_outs) -> "list[np.ndarray]":
    """Per layer, the (T, n_samples, fan_out) read-noise draws of a study."""
    n_chunks = math.ceil(n_samples / chunk)
    key = jax.random.fold_in(jax.random.PRNGKey(seed), NOISE_SALT)
    chunk_keys = jax.random.split(key, n_chunks)
    per_layer = [[] for _ in fan_outs]
    for ci in range(n_chunks):
        size = min(chunk, n_samples - ci * chunk)
        layer_keys = jax.random.split(chunk_keys[ci], len(fan_outs))
        for layer, fan_out in enumerate(fan_outs):
            per_layer[layer].append(np.asarray(jax.random.normal(
                layer_keys[layer], (trials, size, fan_out), jnp.float32)))
    return [np.concatenate(d, axis=1).astype(np.float64) for d in per_layer]
