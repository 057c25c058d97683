"""Traffic streams repeat exactly for a seed, and seeds share one workload."""
import numpy as np

from benchlib import data
from benchlib.traffic import Traffic


class Pool:
    """Stands in for the device arrays: the stream never reads them."""

    shape = (64, 400)


def traffic(spec, seed):
    return Traffic(spec["config"], spec["mix"], seed, None, Pool(), Pool())


def test_loop_stream_repeats_per_seed(small):
    spec = small("grid-screen-loop", n_samples=16)
    seed = 2**31 + 1234
    d1, d2 = traffic(spec, seed), traffic(spec, seed)
    a = [d1.next_points()[0].name for _ in range(60)]
    b = [d2.next_points()[0].name for _ in range(60)]
    assert a == b


def test_every_seed_draws_every_point_once_per_round(small):
    spec = small("grid-screen-loop", n_samples=16)
    rounds = []
    for seed in (1, 2**31 + 7, 9_000_000_000):
        d = traffic(spec, seed)
        rounds.append([d.next_points()[0].name for _ in range(24)])
    for r in rounds:
        assert sorted(r) == sorted(p.name for p in d.points)
    assert rounds[0] != rounds[1]


def test_sweep_passes_take_the_next_slice(small):
    spec = small("tableIV-sweep", n_samples=16)
    d = traffic(spec, 3)
    offsets = []
    for _ in range(6):
        offsets.append((d._next_slice % d.slices) * d.n_samples)
        d._next_slice += 1
    assert offsets == [0, 16, 32, 48, 0, 16]
    assert [p.name for p in d.next_points()] == [
        "hp13-4-3/MRAM", "hp13-4-3/RRAM", "hp13-4-3/CBRAM", "hp13-4-3/PCM"]


def test_inputs_and_weights_repeat_per_seed(small):
    spec = small("tableIV-sweep")
    cfg = spec["config"]
    p1, x1, y1, _ = data.make_workload(2**31 + 99, cfg)
    p2, x2, y2, _ = data.make_workload(2**31 + 99, cfg)
    _, x3, _, _ = data.make_workload(2**31 + 100, cfg)
    np.testing.assert_array_equal(np.asarray(x1), np.asarray(x2))
    np.testing.assert_array_equal(np.asarray(y1), np.asarray(y2))
    for (w1, b1), (w2, b2) in zip(p1, p2):
        np.testing.assert_array_equal(np.asarray(w1), np.asarray(w2))
    assert not np.array_equal(np.asarray(x1), np.asarray(x3))
    assert data.derive(2**33, 1) != data.derive(2**33 + 1, 1)
