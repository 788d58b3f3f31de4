"""Multi-device behaviour (COM collectives, grad compression, sharded train
step, elastic restore) — executed in a subprocess with 8 host devices so the
main pytest process keeps the real single-device view."""
import os
import subprocess
import sys

import pytest


@pytest.mark.timeout(560)
def test_mesh_checks_subprocess():
    script = os.path.join(os.path.dirname(__file__), "_mesh_checks.py")
    proc = subprocess.run(
        [sys.executable, script], capture_output=True, text=True, timeout=540
    )
    sys.stdout.write(proc.stdout[-3000:])
    if proc.returncode != 0:
        pytest.fail(
            f"mesh checks subprocess exited {proc.returncode}\n"
            f"--- stdout (tail) ---\n{proc.stdout[-3000:]}\n"
            f"--- stderr (tail) ---\n{proc.stderr[-6000:]}"
        )
    assert "ALL MESH CHECKS PASSED" in proc.stdout
