"""HLO analysis parser: validated against unrolled-scan ground truth."""
import jax
import jax.numpy as jnp
import pytest

from repro.launch.hlo_analysis import collective_stats, compute_stats


def _body(x, w):
    return jnp.tanh(x @ w), None


def _scanned(x, ws):
    return jax.lax.scan(_body, x, ws)[0]


def _unrolled(x, ws):
    for i in range(10):
        x, _ = _body(x, ws[i])
    return x


X = jax.ShapeDtypeStruct((128, 256), jnp.float32)
WS = jax.ShapeDtypeStruct((10, 256, 256), jnp.float32)
EXPECTED_FLOPS = 2 * 128 * 256 * 256 * 10


def test_scan_flops_loop_multiplicity():
    c = jax.jit(_scanned).lower(X, WS).compile()
    stats = compute_stats(c.as_text())
    assert stats["flops"] == pytest.approx(EXPECTED_FLOPS, rel=1e-6)


def test_scan_matches_unrolled():
    cs = compute_stats(jax.jit(_scanned).lower(X, WS).compile().as_text())
    cu = compute_stats(jax.jit(_unrolled).lower(X, WS).compile().as_text())
    assert cs["flops"] == pytest.approx(cu["flops"], rel=1e-6)


def test_grad_flops_ratio():
    g = jax.jit(
        jax.grad(lambda x, ws: _scanned(x, ws).sum())
    ).lower(X, WS).compile()
    stats = compute_stats(g.as_text())
    # backward of a matmul chain costs 2x the forward.
    assert stats["flops"] == pytest.approx(2 * EXPECTED_FLOPS, rel=1e-6)


def test_plain_matmul_bytes_reasonable():
    a = jax.ShapeDtypeStruct((512, 512), jnp.float32)
    c = jax.jit(lambda a, b: a @ b).lower(a, a).compile()
    stats = compute_stats(c.as_text())
    minimal = 3 * 512 * 512 * 4  # two reads + one write
    assert minimal <= stats["bytes"] <= 4 * minimal


def test_collective_counting_with_psum():
    mesh = jax.make_mesh((1,), ("data",))
    from jax.sharding import PartitionSpec as P

    def f(x):
        return jax.lax.psum(x, "data")

    fn = jax.shard_map(
        f, mesh=mesh, in_specs=P(), out_specs=P(), check_vma=False
    )
    c = jax.jit(fn).lower(jax.ShapeDtypeStruct((1024,), jnp.float32)).compile()
    stats = collective_stats(c.as_text())
    # single-device psum may be optimized away; stats must not crash and
    # must report a numeric total either way.
    assert isinstance(stats.total_bytes, (int, float))


def test_scan_in_scan_multiplicity():
    def inner(x, w):
        return jax.lax.scan(_body, x, w)[0]

    def outer(x, ws):
        def step(c, w3):
            return inner(c, w3), None
        return jax.lax.scan(step, x, ws)[0]

    ws = jax.ShapeDtypeStruct((4, 10, 256, 256), jnp.float32)
    c = jax.jit(outer).lower(X, ws).compile()
    stats = compute_stats(c.as_text())
    assert stats["flops"] == pytest.approx(4 * EXPECTED_FLOPS, rel=1e-6)
