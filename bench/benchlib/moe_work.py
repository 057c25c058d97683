"""Work of an MoE FFN layer's circuit solves, counted from the problem.

One (token, expert) pair drives three matrices: gate and up
(d_model x d_expert each) and down (d_expert x d_model), each on tiles of
rows x cols cells, with a G+ and a G- array per tile. Each tile is one
system of the block Gauss-Seidel solve, counted as `benchlib.work` counts
it: rows x cols cells x the sweep budget S_ref x 26 operations
(`work.OPS_PER_CELL_SWEEP`), whatever the solver ran. At DeepSeek-V3's
widths on 64 x 64 tiles a pair is 3 x 3584 x 2 = 21,504 systems.

Bytes: each expert's conductances once per call that uses it, and each
system's drive voltages and column currents, in float32.
"""
from __future__ import annotations

import math

from benchlib import work


def matrix_tiles(fan_in: int, fan_out: int, rows: int, cols: int) -> int:
    return math.ceil(fan_in / rows) * math.ceil(fan_out / cols)


def pair_systems(cfg: dict) -> int:
    """Tile systems (G+ and G- arrays) of one (token, expert) pair."""
    d, f = cfg["hidden_size"], cfg["moe_intermediate_size"]
    rows, cols = cfg["imac"]["array_rows"], cfg["imac"]["array_cols"]
    return 2 * (2 * matrix_tiles(d, f, rows, cols) + matrix_tiles(f, d, rows, cols))


def call_work(cfg: dict, pairs: int, experts: int) -> dict:
    """Operations and bytes of one call that solved `pairs` pairs over
    `experts` distinct experts."""
    rows, cols = cfg["imac"]["array_rows"], cfg["imac"]["array_cols"]
    systems = pair_systems(cfg)
    per_system = rows * cols * work.sweep_budget(rows, cols) * work.OPS_PER_CELL_SWEEP
    nbytes = work.BYTES_PER_VALUE * (experts * systems * rows * cols
                                     + pairs * systems * (rows + cols))
    return {"ops": pairs * systems * per_system, "bytes": nbytes}
