"""Kind `loop`: closed loop, one client, one design point per `run_sweep`
call (a request), in rounds that each hold every point once in an order
shuffled from the seed (`benchlib.traffic`). Inputs, weights and the check
are the `sweep` kind's.

Controls, for the ideal (no wire) crossbar the loop's mixes use: the
reference in JAX on the chip in the program's place, its two dots at
`Precision.HIGH` (three bfloat16 passes, `control_high`), one below the
`HIGHEST` the ideal path states, and at `DEFAULT` (one bfloat16 pass,
`control_default`) for scale. The CPU computes every precision in full
float32; the chip does not.
"""
from __future__ import annotations

import types

from benchlib import check
from benchlib.traffic import Traffic, failed, on_digits  # noqa: F401  (failed: the kind's)

NUMBERS = check.NUMBERS
CONTROLS = ("control_high", "control_default")
compare = check.compare


def ideal_results(cfg, traffic, call, precision):
    """IMACResult-like results of one call, computed by a plain JAX ideal
    crossbar in float32 with its two dots at `precision`."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchlib import reference

    xs, ys = traffic.inputs(call)
    z_lim = cfg["vdd"] / cfg["neuron"]["z_volt"]
    out = []
    for point in call.points:
        tech = cfg["technologies"][point.tech]
        g_on, g_off = 1.0 / tech["r_low"], 1.0 / tech["r_high"]
        plans = reference.plan_layers(cfg["topology"], point.partitioning)
        a = jnp.asarray(xs, jnp.float32)
        powers = []
        for layer, ((w, b), plan) in enumerate(zip(traffic.params, plans)):
            wb = jnp.concatenate([w, b[None, :]])
            scale = jnp.max(jnp.abs(wb))
            gp = jnp.clip(g_off + jnp.maximum(wb / scale, 0) * (g_on - g_off), g_off, g_on)
            gn = jnp.clip(g_off + jnp.maximum(-wb / scale, 0) * (g_on - g_off), g_off, g_on)
            v = jnp.concatenate([a, jnp.ones((a.shape[0], 1))], 1) * cfg["vdd"]
            i = jnp.matmul(v, gp - gn, precision=precision)
            p = jnp.matmul(v ** 2, gp + gn, precision=precision).sum(1)
            z = jnp.clip(i / ((g_on - g_off) / scale * cfg["vdd"]), -z_lim, z_lim)
            a = z if layer == len(plans) - 1 else jax.nn.sigmoid(z)
            powers.append(float(jnp.mean(p)) + reference.interface_power(plan, cfg["neuron"]))
        errors = int(np.sum(np.asarray(jnp.argmax(a, -1)) != ys))
        out.append(types.SimpleNamespace(per_layer_power=tuple(powers),
                                         error_rate=errors / len(ys),
                                         avg_power=sum(powers),
                                         accuracy=1 - errors / len(ys)))
    return out


class IdealControl(Traffic):
    """The loop's calls with the reference at `precision` in the program's
    place: the same points and inputs, the control's results."""

    def __init__(self, *args, precision, **kw):
        super().__init__(*args, **kw)
        self.precision = precision

    def call(self, points, label):
        c = super().call(points, label)
        c.results = ideal_results(self.cfg, self, c, self.precision)
        return c


def build(cfg: dict, mix: dict, seed: int, control=None):
    """(traffic, notes): the digit pool and trained weights of the seed."""
    if control is None:
        return on_digits(cfg, mix, seed)
    if control not in CONTROLS:
        raise ValueError(f"unknown control {control!r}; known: {CONTROLS}")
    import jax

    precision = {"control_high": jax.lax.Precision.HIGH,
                 "control_default": jax.lax.Precision.DEFAULT}[control]
    return on_digits(cfg, mix, seed, IdealControl, precision=precision)
