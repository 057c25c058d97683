"""The control comes out not correct: the program's bfloat16 path, one
precision below the float32 the configuration states, fails the
comparison's limits (run here at a test size; `bench/control.py` reads
it on the chip at the cell's size)."""
import jax
import jax.numpy as jnp
import pytest

from benchlib import check, data
from benchlib.traffic import Traffic


@pytest.mark.parametrize("workload,groups", [
    ("tableIV-sweep", None), ("grid-sweep", ["32x32", "128x128"])])
def test_bfloat16_control_fails(small, workload, groups):
    spec = small(workload, 4, groups)
    seed = 2**31 + 21
    params, x_pool, y_pool, _ = data.make_workload(seed, spec["config"])
    worst = {}
    for dtype in (None, jnp.bfloat16):
        traffic = Traffic(spec["config"], spec["mix"], seed, params, x_pool,
                        y_pool, dtype=dtype)
        calls = [traffic.call(traffic.next_points(), traffic.label)]
        worst[dtype] = check.compare(traffic, calls, seed)
    assert check.verdict(worst[None], spec["limits"])[0] is True
    assert check.verdict(worst[jnp.bfloat16], spec["limits"])[0] is False


@pytest.mark.skipif(jax.devices()[0].platform != "tpu",
                    reason="the CPU computes Precision.HIGH in full float32")
def test_high_precision_control_fails_on_the_ideal_path(small):
    """Run on the chip: JAX_PLATFORMS=tpu python -m pytest bench/tests/test_control.py"""
    import control

    spec = small("grid-screen-loop", 256)
    spec["config"]["test_set_size"] = 2048
    seed = 2**31 + 22
    params, x_pool, y_pool, _ = data.make_workload(seed, spec["config"])
    traffic = Traffic(spec["config"], spec["mix"], seed, params, x_pool, y_pool)
    calls = [traffic.call(traffic.next_points(), traffic.label) for _ in range(8)]
    sound = check.compare(traffic, calls, seed)
    high = check.compare(traffic, calls, seed, results_of=lambda c: control.ideal_results(
        spec["config"], traffic, c, jax.lax.Precision.HIGH))
    assert check.verdict(sound, spec["limits"])[0] is True
    assert check.verdict(high, spec["limits"])[0] is False
