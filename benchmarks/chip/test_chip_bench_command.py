"""The benchmark's command refuses to run where JAX finds no TPU: it
exits non-zero and prints no result. (Whole runs at tiny sizes on the
CPU are in rehearsal_check.py, sound, and faults_check.py, with planted
faults and the controls.)"""

from __future__ import annotations

import os
import pathlib
import subprocess
import sys

BENCH_DIR = pathlib.Path(__file__).resolve().parent
ROOT = BENCH_DIR.parents[1]


def test_command_refuses_to_run_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, str(BENCH_DIR / "run.py"), "--workload",
         "serve.reconnect", "--seed", "1", "--seconds", "1", "--trace", "0"],
        env=env, capture_output=True, text=True, timeout=300, cwd=ROOT)
    assert out.returncode != 0
    assert out.stdout.strip() == ""
    assert "no TPU" in out.stderr
