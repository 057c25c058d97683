"""Work of the circuit solve, counted from the problem, not from the code that ran.

A tile of M x N cells holds a row-wire node and a column-wire node per
cell. One block Gauss-Seidel sweep solves every row as a tridiagonal
system (Thomas algorithm) with the column voltages held, then every
column with the row voltages held, then over-relaxes the column
voltages. Operations per cell and sweep:

  row system      d = chain + g, b = g * vc                        2
  row forward     den = d - dl * c', c' = du / den,
                  d' = (b - dl * d'_prev) / den                    6
  row backward    x = d' - c' * x_next                             2
  column system   d = chain + g, b = g * vr                        2
  column forward  as the row                                       6
  column backward as the row                                       2
  SOR and stop    vc + w * (vc_gs - vc), |vc_new - vc|, max         6
                                                                  --
                                                         OPS_PER_CELL_SWEEP = 26

A division counts as one operation. The sweep count is this benchmark's
copy of the solver's budget, S_ref = max(48, floor(0.75 * max(M, N))),
whatever the solver actually ran: a change that converges in fewer
sweeps, or runs another backend, changes the device time and not this
count.

Bytes: each tile's conductances (once per configuration), each system's
drive voltages and column currents, read or written once, in float32.
"""
from __future__ import annotations

from benchlib.reference import plan_layers

OPS_PER_CELL_SWEEP = 26
BYTES_PER_VALUE = 4


def sweep_budget(rows: int, cols: int) -> int:
    return max(48, int(0.75 * max(rows, cols)))


def solve_work(topology, partitioning, configs: int, samples: int) -> dict:
    """Operations and bytes of the circuit solves of `configs` design points
    that share one partitioning, each over `samples` inputs."""
    ops = 0
    nbytes = 0
    for plan in plan_layers(topology, partitioning):
        tiles = 2 * plan.hp * plan.vp  # G+ and G- arrays
        m, n = plan.rows, plan.cols
        systems = configs * samples * tiles
        ops += systems * m * n * sweep_budget(m, n) * OPS_PER_CELL_SWEEP
        nbytes += BYTES_PER_VALUE * (configs * tiles * m * n
                                     + systems * m + systems * n)
    return {"ops": ops, "bytes": nbytes}


def roofline_share(ops: float, nbytes: float, busy_s: float, peaks: dict,
                   chips: int = 1) -> "tuple[float, str] | None":
    """(percent of the roofline, bound) for work done in `busy_s` seconds of
    device time on each of `chips` chips; None when nothing ran."""
    if busy_s <= 0 or ops <= 0:
        return None
    t_ops = ops / (peaks["flops"] * chips)
    t_bytes = nbytes / (peaks["hbm_bytes_per_s"] * chips)
    bound = "compute" if t_ops >= t_bytes else "memory"
    return 100.0 * max(t_ops, t_bytes) / busy_s, bound
