"""Mean Gauss-Seidel sweeps per group layer solve, from the program's solver_sweeps counter."""
from benchlib import readers


def read(ctx):
    return readers.sweeps_per_solve(ctx)
