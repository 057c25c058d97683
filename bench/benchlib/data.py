"""Inputs and weights of a run, made on the device from the seed.

The digit generator and the training loop are the benchmark's own copies
(of the simulator's `data.digits` and `core.digital`), so the data and
the weights a cell sees cannot move with the program. Each is one jitted
call.

Digits: ten smooth 20x20 prototypes (a Gaussian field blurred three
times by a 5-tap box filter, scaled to [0, 1]); each sample is a
prototype shifted by up to two pixels in each direction, plus Gaussian
pixel noise, clipped to [0, 1]. The 400-120-84-10 sigmoid MLP is
trained on them by Adam on softmax cross-entropy.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

IMG = 20
N_CLASSES = 10
HIGHEST = jax.lax.Precision.HIGHEST


def derive(seed: int, *words: int) -> int:
    """A 31-bit integer from the run's seed and a purpose, for PRNG keys."""
    state = np.random.SeedSequence([int(seed) % (1 << 128), *words])
    return int(state.generate_state(1, np.uint32)[0] >> 1)


def _blur(img):
    k = jnp.ones((5,)) / 5.0
    img = jax.vmap(jax.vmap(lambda r: jnp.convolve(r, k, mode="same")))(img)
    img = jnp.swapaxes(img, 1, 2)
    img = jax.vmap(jax.vmap(lambda r: jnp.convolve(r, k, mode="same")))(img)
    return jnp.swapaxes(img, 1, 2)


@functools.partial(jax.jit, static_argnames=("n", "noise", "max_shift"))
def make_digits(key, n: int, noise: float, max_shift: int = 2):
    """(x (n, 400) in [0, 1], y (n,) int32) from one key."""
    kp, ky, kn, ks = jax.random.split(key, 4)
    s = jax.random.normal(kp, (N_CLASSES, IMG, IMG))
    for _ in range(3):
        s = _blur(s)
    lo = s.min(axis=(1, 2), keepdims=True)
    hi = s.max(axis=(1, 2), keepdims=True)
    protos = (s - lo) / (hi - lo)
    y = jax.random.randint(ky, (n,), 0, N_CLASSES)
    shifts = jax.random.randint(ks, (n, 2), -max_shift, max_shift + 1)
    imgs = jax.vmap(lambda img, sh: jnp.roll(img, (sh[0], sh[1]), axis=(0, 1)))(
        protos[y], shifts)
    imgs = jnp.clip(imgs + noise * jax.random.normal(kn, imgs.shape), 0.0, 1.0)
    return imgs.reshape(n, IMG * IMG), y.astype(jnp.int32)


def mlp_logits(params, x):
    a = x
    for i, (w, b) in enumerate(params):
        z = jnp.matmul(a, w, precision=HIGHEST) + b
        a = z if i == len(params) - 1 else jax.nn.sigmoid(z)
    return a


@functools.partial(jax.jit, static_argnames=("topology", "steps", "batch", "lr"))
def train_mlp(key, x, y, topology: tuple, steps: int, batch: int = 128,
              lr: float = 3e-3):
    """Glorot init and `steps` Adam steps on random batches; returns params."""
    k_init, k_idx = jax.random.split(key)
    params = []
    for fan_in, fan_out, k in zip(topology[:-1], topology[1:],
                                  jax.random.split(k_init, len(topology) - 1)):
        scale = jnp.sqrt(2.0 / (fan_in + fan_out))
        params.append((scale * jax.random.normal(k, (fan_in, fan_out)),
                       jnp.zeros((fan_out,))))

    def loss(p, xb, yb):
        logp = jax.nn.log_softmax(mlp_logits(p, xb))
        return -jnp.mean(jnp.take_along_axis(logp, yb[:, None], axis=1))

    def step(carry, idx):
        p, m, v, t = carry
        g = jax.grad(loss)(p, x[idx], y[idx])
        t = t + 1
        m = jax.tree.map(lambda a, b: 0.9 * a + 0.1 * b, m, g)
        v = jax.tree.map(lambda a, b: 0.999 * a + 0.001 * b * b, v, g)
        p = jax.tree.map(
            lambda q, a, b: q - lr * (a / (1 - 0.9 ** t))
            / (jnp.sqrt(b / (1 - 0.999 ** t)) + 1e-8), p, m, v)
        return (p, m, v, t), None

    zeros = jax.tree.map(jnp.zeros_like, params)
    idxs = jax.random.randint(k_idx, (steps, batch), 0, x.shape[0])
    (params, _, _, _), _ = jax.lax.scan(
        step, (params, zeros, zeros, jnp.zeros((), jnp.float32)), idxs)
    return params


def make_workload(seed: int, cfg: dict):
    """Training set, test pool and trained weights of one run.

    Returns (params as a list of (W, b) device arrays, x_pool, y_pool,
    digital test accuracy).
    """
    data = cfg["data"]
    n_train, n_pool = data["train_samples"], cfg["test_set_size"]
    x, y = make_digits(jax.random.PRNGKey(derive(seed, 1)), n_train + n_pool,
                       float(data["pixel_noise"]))
    params = train_mlp(jax.random.PRNGKey(derive(seed, 2)), x[:n_train],
                       y[:n_train], tuple(cfg["topology"]),
                       int(data["train_steps"]))
    x_pool, y_pool = x[n_train:], y[n_train:]
    acc = jnp.mean(jnp.argmax(mlp_logits(params, x_pool), -1) == y_pool)
    return [tuple(p) for p in params], x_pool, y_pool, float(acc)
