import os
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[1]
SRC = REPO / "src"


def run_subprocess(code: str, devices: int = 8, timeout: int = 600) -> str:
    """Run python code in a fresh process with N fake XLA host devices.

    Multi-device tests must not pollute this process's jax (which smoke
    tests expect to see exactly ONE device), hence the subprocess.
    """
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = f"{SRC}:{env.get('PYTHONPATH','')}"
    proc = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(code)],
        capture_output=True, text=True, timeout=timeout, env=env,
    )
    if proc.returncode != 0:
        raise AssertionError(
            f"subprocess failed (rc={proc.returncode})\n--- stdout\n"
            f"{proc.stdout}\n--- stderr\n{proc.stderr}"
        )
    return proc.stdout


@pytest.fixture(scope="session")
def subproc():
    return run_subprocess
