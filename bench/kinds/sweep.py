"""Kind `sweep`: every design point of the configuration in one `run_sweep`
call (a pass) over the next `n_samples` inputs of the seeded digit pool,
passes back to back (`benchlib.traffic`). The weights are the 400-120-84-10
MLP trained from the seed (`benchlib.data`); the check reruns a seeded
sample of passes through the float64 reference (`benchlib.check`).

Control `control_bf16`: the program's own bfloat16 path
(`IMACConfig.dtype`), one precision below the float32 the configuration
states.
"""
from __future__ import annotations

from benchlib import check
from benchlib.traffic import failed, on_digits  # noqa: F401  (failed: the kind's)

NUMBERS = check.NUMBERS
CONTROLS = ("control_bf16",)
compare = check.compare


def build(cfg: dict, mix: dict, seed: int, control=None):
    """(traffic, notes): the digit pool and trained weights of the seed."""
    dtype = None
    if control == "control_bf16":
        import jax.numpy as jnp

        dtype = jnp.bfloat16
    elif control is not None:
        raise ValueError(f"unknown control {control!r}; known: {CONTROLS}")
    return on_digits(cfg, mix, seed, dtype=dtype)
