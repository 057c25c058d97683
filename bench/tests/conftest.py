"""Helpers for the benchmark's own tests (run them explicitly:
`python -m pytest bench/tests`). They run on the CPU at small sizes."""
from __future__ import annotations

import argparse
import json
import os
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))
os.environ.setdefault("JAX_PLATFORMS", "cpu")


def small_spec(workload: str, n_samples: int = 4, groups=None) -> dict:
    """The cell's spec, cut to a size the CPU runs in seconds."""
    import run

    spec = run.load_cell(workload)
    spec["mix"].update(n_samples=n_samples, chunk=n_samples)
    spec["config"]["data"].update(train_samples=500, train_steps=50)
    spec["config"]["test_set_size"] = 64
    if groups is not None:
        spec["config"]["partitionings"] = [
            p for p in spec["config"]["partitionings"] if p["name"] in groups]
    return spec


def small_study(n_samples: int = 8, trials: int = 4, **mix) -> dict:
    """The Monte-Carlo cell at a CPU size: two solve chunks, every trial
    checked."""
    spec = small_spec("tableIV-mram-mc64", n_samples)
    spec["mix"].update(trials=trials, chunk=n_samples // 2, check_trials=trials,
                       **mix)
    return spec


def run_small(spec: dict, capsys, seed: int = 2**31 + 5, seconds: float = 0.01,
              trace: int = 0) -> dict:
    """Drive bench/run.py's `execute` on the CPU; return its result line."""
    import jax
    import run

    args = argparse.Namespace(workload=spec["cell"]["name"], seed=seed,
                              seconds=seconds, trace=trace)
    assert run.execute(args, spec, jax.devices()) == 0
    lines = capsys.readouterr().out.strip().splitlines()
    return json.loads(lines[-1])


@pytest.fixture
def small():
    return small_spec


@pytest.fixture(autouse=True, scope="module")
def program_on_path():
    import run

    run.import_program()
