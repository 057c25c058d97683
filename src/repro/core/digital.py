"""Digital reference MLP — the "trained weights and biases" input to
IMAC-Sim (Algorithm 1). Self-contained (tiny Adam) so the core library
does not depend on the large-model substrate.

The paper's workload is a 400x120x84x10 sigmoid MLP on MNIST; the
container is offline, so `repro.data.digits` provides a deterministic
synthetic 20x20 digit-prototype dataset (see DESIGN.md §9).
"""
from __future__ import annotations

from typing import List, Sequence, Tuple

import jax
import jax.numpy as jnp

Params = List[Tuple[jax.Array, jax.Array]]


def init_mlp(key: jax.Array, topology: Sequence[int]) -> Params:
    """Glorot-initialised MLP params [(W, b), ...]; W: (fan_in, fan_out)."""
    params = []
    for i in range(len(topology) - 1):
        key, sub = jax.random.split(key)
        fan_in, fan_out = topology[i], topology[i + 1]
        scale = jnp.sqrt(2.0 / (fan_in + fan_out))
        w = scale * jax.random.normal(sub, (fan_in, fan_out), jnp.float32)
        params.append((w, jnp.zeros((fan_out,), jnp.float32)))
    return params


def mlp_forward(params: Params, x: jax.Array, activation: str = "sigmoid") -> jax.Array:
    """Digital forward; sigmoid hidden layers, linear readout (matches the
    analog circuit's diff-amp readout)."""
    act = {"sigmoid": jax.nn.sigmoid, "tanh": jnp.tanh,
           "relu": jax.nn.relu}[activation]
    a = x
    for i, (w, b) in enumerate(params):
        z = jnp.matmul(a, w, precision=jax.lax.Precision.HIGHEST) + b
        a = z if i == len(params) - 1 else act(z)
    return a


def train_mlp(
    key: jax.Array,
    topology: Sequence[int],
    x: jax.Array,
    y: jax.Array,
    *,
    steps: int = 300,
    batch_size: int = 128,
    lr: float = 3e-3,
    activation: str = "sigmoid",
) -> Params:
    """Train with Adam + softmax-CE. Returns trained params."""
    params = init_mlp(key, topology)
    flat, tree = jax.tree_util.tree_flatten(params)
    m = [jnp.zeros_like(p) for p in flat]
    v = [jnp.zeros_like(p) for p in flat]

    def loss_fn(params, xb, yb):
        logits = mlp_forward(params, xb, activation)
        logp = jax.nn.log_softmax(logits)
        return -jnp.mean(jnp.take_along_axis(logp, yb[:, None], axis=1))

    @jax.jit
    def step(carry, idx):
        flat, m, v, t = carry
        params = jax.tree_util.tree_unflatten(tree, flat)
        xb, yb = x[idx], y[idx]
        grads = jax.grad(loss_fn)(params, xb, yb)
        gflat = jax.tree_util.tree_flatten(grads)[0]
        t = t + 1
        b1, b2, eps = 0.9, 0.999, 1e-8
        new_flat, new_m, new_v = [], [], []
        for p, g, mi, vi in zip(flat, gflat, m, v):
            mi = b1 * mi + (1 - b1) * g
            vi = b2 * vi + (1 - b2) * g * g
            mhat = mi / (1 - b1**t)
            vhat = vi / (1 - b2**t)
            new_flat.append(p - lr * mhat / (jnp.sqrt(vhat) + eps))
            new_m.append(mi)
            new_v.append(vi)
        return (new_flat, new_m, new_v, t), None

    n = x.shape[0]
    key_idx = jax.random.PRNGKey(17)
    idxs = jax.random.randint(key_idx, (steps, batch_size), 0, n)
    carry = (flat, m, v, jnp.zeros((), jnp.int32))
    carry, _ = jax.lax.scan(step, carry, idxs)
    return jax.tree_util.tree_unflatten(tree, carry[0])


def accuracy(params: Params, x: jax.Array, y: jax.Array, activation: str = "sigmoid") -> float:
    pred = jnp.argmax(mlp_forward(params, x, activation), axis=-1)
    return float(jnp.mean((pred == y).astype(jnp.float32)))
