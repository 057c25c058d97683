"""The controls come out not correct while the program comes out correct
(run here at a test size; `bench/control.py` reads them on the chip at the
cell's size): the program's bfloat16 path, one precision below the float32
the configuration states; in the Monte-Carlo cell also a checked trial
drawn with another key."""
import jax
import pytest

import run
from conftest import small_study


def readings(kind, spec, seed, control, n_calls=1):
    traffic, _ = kind.build(spec["config"], spec["mix"], seed, control=control)
    calls = [c for _ in range(n_calls) for c in traffic.window(0, traffic.label)[0]]
    worst = kind.compare(traffic, calls, seed)
    return run.verdict(worst, spec["limits"], kind.NUMBERS)[0]


@pytest.mark.parametrize("workload,groups", [
    ("tableIV-sweep", None), ("grid-sweep", ["32x32", "128x128"])])
def test_bfloat16_control_fails(small, workload, groups):
    spec = small(workload, 4, groups)
    kind = run.load_kind(spec["mix"]["kind"])
    seed = 2**31 + 21
    assert readings(kind, spec, seed, None) is True
    assert readings(kind, spec, seed, "control_bf16") is False


@pytest.mark.parametrize("control", [None, "control_bf16", "control_wrong_key"])
def test_study_controls_fail(control):
    spec = small_study()
    kind = run.load_kind("variability")
    assert readings(kind, spec, 2**31 + 23, control) is (control is None)


@pytest.mark.skipif(jax.devices()[0].platform != "tpu",
                    reason="the CPU computes Precision.HIGH in full float32")
def test_high_precision_control_fails_on_the_ideal_path(small):
    """Run on the chip: JAX_PLATFORMS=tpu python -m pytest bench/tests/test_control.py"""
    spec = small("grid-screen-loop", 256)
    spec["config"]["test_set_size"] = 2048
    kind = run.load_kind("loop")
    seed = 2**31 + 22
    assert readings(kind, spec, seed, None, n_calls=8) is True
    assert readings(kind, spec, seed, "control_high", n_calls=8) is False
