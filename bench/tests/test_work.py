"""The solve's operation and byte count against a count by hand."""
from benchlib import work


def test_two_tile_plan_by_hand():
    # A 3-input, 2-output layer on arrays of 2 rows x 2 columns: 4 rows with
    # the bias row, so H_P = 2, V_P = 1, tiles of 2 x 2 cells; a G+ and a G-
    # array of each: 4 tile systems per input.
    topology = [3, 2]
    part = {"array_rows": 2, "array_cols": 2}
    w = work.solve_work(topology, part, configs=3, samples=5)
    systems = 3 * 5 * 4
    cells = 2 * 2
    sweeps = 48                      # max(48, 0.75 * 2)
    assert w["ops"] == systems * cells * sweeps * 26
    g_bytes = 4 * 3 * 4 * cells      # per configuration and tile
    drive_bytes = 4 * systems * 2    # 2 rows
    current_bytes = 4 * systems * 2  # 2 columns
    assert w["bytes"] == g_bytes + drive_bytes + current_bytes


def test_sweep_budget_is_the_solver_rule():
    assert work.sweep_budget(31, 30) == 48
    assert work.sweep_budget(101, 120) == 90
    assert work.sweep_budget(401, 120) == 300


def test_roofline_share_names_its_bound():
    peaks = {"flops": 100.0, "hbm_bytes_per_s": 10.0}
    share, bound = work.roofline_share(ops=50, nbytes=1, busy_s=1.0, peaks=peaks)
    assert bound == "compute" and share == 50.0
    share, bound = work.roofline_share(ops=1, nbytes=5, busy_s=1.0, peaks=peaks, chips=2)
    assert bound == "memory" and share == 25.0
    assert work.roofline_share(0, 0, 0.0, peaks) is None
