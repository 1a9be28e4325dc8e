"""The measurement path refuses a backend that is not a TPU: non-zero exit
and no result line."""
import os
import subprocess
import sys

import _paths


def test_run_refuses_the_cpu_backend():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, str(_paths.BENCH / "run.py"), "--workload",
         "zeta_logreg.solve", "--seed", "3", "--seconds", "1",
         "--trace", "0"], cwd=_paths.ROOT, env=env, capture_output=True,
        text=True, timeout=240)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "not 'tpu'" in p.stderr


def test_devices_refuses_cpu_in_process():
    import pytest
    import harness
    with pytest.raises(harness.NoChip):
        harness.devices(1)
