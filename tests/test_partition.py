import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytest.importorskip("hypothesis")
from hypothesis import given, settings, strategies as st

from repro.core.partition import (
    auto_partition,
    combine_outputs,
    ordered_sum,
    plan_partition,
    plan_topology,
    tile_inputs,
    tile_matrix,
    untile_matrix,
)

TOPOLOGY = [400, 120, 84, 10]  # the paper's MNIST MLP

# Paper Table III: array size -> (H_P, V_P).
TABLE_III = {
    32: ([13, 4, 3], [4, 3, 1]),
    64: ([7, 2, 2], [2, 2, 1]),
    128: ([4, 1, 1], [1, 1, 1]),
    256: ([2, 1, 1], [1, 1, 1]),
    512: ([1, 1, 1], [1, 1, 1]),
}


@pytest.mark.parametrize("size", sorted(TABLE_III))
def test_table_iii_auto_partitioning(size):
    """auto_partition reproduces the paper's Table III exactly."""
    want_hp, want_vp = TABLE_III[size]
    got = [
        auto_partition(TOPOLOGY[i], TOPOLOGY[i + 1], size, size)
        for i in range(3)
    ]
    assert [g[0] for g in got] == want_hp
    assert [g[1] for g in got] == want_vp


def test_plan_topology_defaults():
    plans = plan_topology(TOPOLOGY, 32, 32)
    assert [p.hp for p in plans] == [13, 4, 3]
    assert [p.vp for p in plans] == [4, 3, 1]
    assert plans[0].total_rows == 401  # bias row folded in


def test_plan_topology_custom():
    plans = plan_topology(TOPOLOGY, 32, 32, hp=[16, 8, 8], vp=[8, 8, 1])
    assert [p.hp for p in plans] == [16, 8, 8]


@settings(max_examples=25, deadline=None)
@given(
    st.integers(min_value=1, max_value=40),
    st.integers(min_value=1, max_value=30),
    st.integers(min_value=1, max_value=8),
    st.integers(min_value=1, max_value=8),
)
def test_tile_untile_roundtrip(fan_in, fan_out, hp, vp):
    hp = min(hp, fan_in + 1)
    vp = min(vp, fan_out)
    plan = plan_partition(fan_in, fan_out, hp, vp)
    key = jax.random.PRNGKey(fan_in * 1000 + fan_out * 10 + hp)
    g = jax.random.normal(key, (plan.total_rows, plan.total_cols))
    tiles = tile_matrix(g, plan)
    assert tiles.shape == (plan.n_tiles, plan.rows, plan.cols)
    np.testing.assert_allclose(np.asarray(untile_matrix(tiles, plan)), np.asarray(g))


@settings(max_examples=25, deadline=None)
@given(
    st.integers(min_value=1, max_value=30),
    st.integers(min_value=1, max_value=20),
    st.integers(min_value=1, max_value=6),
    st.integers(min_value=1, max_value=6),
)
def test_partitioned_ideal_mvm_equals_full(fan_in, fan_out, hp, vp):
    """Property: tiled ideal crossbar MVM == full-matrix MVM.

    This is the core invariant of partitioning: splitting a layer across
    subarrays and summing partial currents is exact in the ideal case.
    """
    hp = min(hp, fan_in + 1)
    vp = min(vp, fan_out)
    plan = plan_partition(fan_in, fan_out, hp, vp)
    key = jax.random.PRNGKey(fan_in + 97 * fan_out + 31 * hp + vp)
    kg, kv = jax.random.split(key)
    g = jax.random.uniform(kg, (plan.total_rows, plan.total_cols))
    v = jax.random.uniform(kv, (3, plan.total_rows))

    tiles = tile_matrix(g, plan)                      # (T, M, N)
    v_t = tile_inputs(v, plan)                        # (3, hp, M)
    v_per_tile = jnp.repeat(v_t, plan.vp, axis=1)     # (3, T, M)
    i_tiles = jnp.einsum("tmn,btm->btn", tiles, v_per_tile)
    out = combine_outputs(i_tiles, plan)              # (3, fan_out)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(v @ g), rtol=2e-4, atol=1e-5
    )


@pytest.mark.parametrize("length", [1, 2, 7, 13, 64])
@pytest.mark.parametrize("axis", [0, 1, -1])
def test_ordered_sum_matches_sum_and_ignores_batch(length, axis):
    """ordered_sum is a sum, and a slice of a stacked batch reduces to
    the same bits as that slice reduced alone (sharded-sweep identity)."""
    shape = [3, 5, 4]
    shape[axis] = length
    x = jax.random.uniform(jax.random.PRNGKey(length), (4, *shape))
    row_axis = axis % 3
    got = jax.jit(lambda x: ordered_sum(x, axis=row_axis + 1))(x)
    want = np.sum(np.asarray(x, np.float64), axis=row_axis + 1)
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-6)
    for c in range(x.shape[0]):
        alone = jax.jit(lambda x: ordered_sum(x, axis=axis))(x[c])
        np.testing.assert_array_equal(np.asarray(got[c]), np.asarray(alone))


def test_validation_errors():
    with pytest.raises(ValueError):
        plan_partition(10, 5, 0, 1)
    with pytest.raises(ValueError):
        plan_partition(10, 5, 12, 1)  # more partitions than rows
    with pytest.raises(ValueError):
        tile_matrix(jnp.zeros((5, 5)), plan_partition(10, 5, 2, 1))
