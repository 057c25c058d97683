"""A traffic kind is one file, found by name.

The `sweep` and `loop` kinds, looked up through `run.load_kind`, draw the
same calls and give the same results and check numbers, to every digit, as
the harness did before kinds were files: the values below were recorded
on the CPU from that harness (`benchlib.data.make_workload` and
`benchlib.traffic.Traffic` called directly, and `run.execute`) at these
sizes and this seed. A kind file in another directory runs with no other
edit, and an unknown kind names the known ones.
"""
import argparse

import jax
import pytest

import run
from conftest import run_small

SEED = 2**31 + 5
CASES = {"tableIV-sweep": (4, 2), "grid-screen-loop": (16, 5)}  # n_samples, calls

RECORDED = {'tableIV-sweep': {'calls': [(0,
                              ['hp13-4-3/MRAM',
                               'hp13-4-3/RRAM',
                               'hp13-4-3/CBRAM',
                               'hp13-4-3/PCM']),
                             (4,
                              ['hp13-4-3/MRAM',
                               'hp13-4-3/RRAM',
                               'hp13-4-3/CBRAM',
                               'hp13-4-3/PCM'])],
                   'results': [[[0.915794312953949,
                                 0.1591208577156067,
                                 0.014359194785356522,
                                 0.25],
                                [0.9108599424362183,
                                 0.1625082641839981,
                                 0.014709574170410633,
                                 0.25],
                                [0.5520625114440918,
                                 0.10770662128925323,
                                 0.009147414937615395,
                                 0.25],
                                [0.22527754306793213,
                                 0.04845917224884033,
                                 0.004228963516652584,
                                 0.25]],
                               [[0.8374309539794922,
                                 0.16150057315826416,
                                 0.014961468987166882,
                                 0.25],
                                [0.8387458920478821,
                                 0.16453240811824799,
                                 0.015223048627376556,
                                 0.25],
                                [0.5149524211883545,
                                 0.10948701202869415,
                                 0.00960476603358984,
                                 0.0],
                                [0.21871282160282135,
                                 0.04888582602143288,
                                 0.004336963407695293,
                                 0.0]]],
                   'worst': {'dev_power_rel_err': 8.033467440245442e-06,
                             'error_count_diff': 0.0,
                             'checked_points': 4.0},
                   'execute': {'dev_power_rel_err': 5.571226557659758e-06,
                               'error_count_diff': 0.0}},
 'grid-screen-loop': {'calls': [(0, ['512x512/MRAM']),
                                (16, ['256x256/CBRAM']),
                                (32, ['128x128/MRAM']),
                                (48, ['32x32/MRAM']),
                                (0, ['32x32/RRAM'])],
                      'results': [[[1.1117503643035889,
                                    0.2171054184436798,
                                    0.01700931042432785,
                                    0.1875]],
                                  [[0.5192169547080994,
                                    0.10101097822189331,
                                    0.008382363244891167,
                                    0.1875]],
                                  [[1.1403217315673828,
                                    0.21130476891994476,
                                    0.01643301174044609,
                                    0.125]],
                                  [[1.186450481414795,
                                    0.23811650276184082,
                                    0.019125616177916527,
                                    0.125]],
                                  [[1.297713279724121,
                                    0.2594852149486542,
                                    0.02033184841275215,
                                    0.1875]]],
                      'worst': {'dev_power_rel_err': 1.2186323264122867e-07,
                                'error_count_diff': 0.0,
                                'checked_points': 5.0},
                      'execute': {'dev_power_rel_err': 1.0846156526283917e-07,
                                  'error_count_diff': 0.0}}}


@pytest.mark.parametrize("workload", sorted(CASES))
def test_mlp_kinds_draw_what_they_drew_before(small, workload):
    n_samples, n_calls = CASES[workload]
    spec = small(workload, n_samples)
    kind = run.load_kind(spec["mix"]["kind"])
    traffic, _ = kind.build(spec["config"], spec["mix"], SEED)
    calls = [c for _ in range(n_calls) for c in traffic.window(0, traffic.label)[0]]
    worst = kind.compare(traffic, calls, SEED)
    want = RECORDED[workload]
    assert [(c.offset, [p.name for p in c.points]) for c in calls] == want["calls"]
    assert [[list(r.per_layer_power) + [r.error_rate] for r in c.results]
            for c in calls] == want["results"]
    assert {k: worst[k] for k in want["worst"]} == want["worst"]


@pytest.mark.parametrize("workload", sorted(CASES))
def test_mlp_kinds_check_what_they_checked_before(small, capsys, workload):
    result = run_small(small(workload, CASES[workload][0]), capsys, seed=SEED)
    assert {k: v["value"] for k, v in result["checks"].items()} == (
        RECORDED[workload]["execute"])


NEW_KIND = '''
"""A kind the harness has never seen: one call evaluates the first design
point of the configuration alone."""
from benchlib import check, data
from benchlib.traffic import Traffic

NUMBERS = check.NUMBERS
CONTROLS = ()
compare = check.compare


class FirstPoint(Traffic):
    def next_points(self):
        return self.points[:1]

    def warmup_points(self):
        return [self.points[:1]]


def build(cfg, mix, seed, control=None):
    params, x_pool, y_pool, _ = data.make_workload(seed, cfg)
    return FirstPoint(cfg, dict(mix, kind="sweep"), seed, params, x_pool, y_pool), {}


def failed(calls):
    return 0
'''


def test_a_kind_file_elsewhere_runs_with_no_other_edit(small, capsys, monkeypatch,
                                                      tmp_path):
    (tmp_path / "first_point.py").write_text(NEW_KIND)
    monkeypatch.setattr(run, "KINDS", tmp_path)
    spec = small("tableIV-sweep", 4)
    spec["mix"]["kind"] = "first_point"
    result = run_small(spec, capsys, seed=SEED)
    assert result["correct"] is True
    assert result["attempted"] == 1


def test_an_unknown_kind_names_the_known_ones(small):
    spec = small("tableIV-sweep", 4)
    spec["mix"]["kind"] = "no_such_kind"
    args = argparse.Namespace(workload="tableIV-sweep", seed=SEED, seconds=0.01,
                              trace=0)
    with pytest.raises(SystemExit) as exc:
        run.execute(args, spec, jax.devices())
    assert "no_such_kind" in str(exc.value)
    for known in ("loop", "sweep", "variability"):
        assert known in str(exc.value)
