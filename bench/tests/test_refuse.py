"""Without a TPU the harness prints no result and exits non-zero."""
import os
import subprocess
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]


def test_refuses_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, str(BENCH / "run.py"), "--workload", "tableIV-sweep",
         "--seed", str(2**31 + 3), "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, env=env, timeout=300)
    assert proc.returncode != 0
    assert "correct" not in proc.stdout
    assert "TPU" in proc.stderr
