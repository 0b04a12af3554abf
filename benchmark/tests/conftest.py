"""The benchmark's own tests: run with ``python -m pytest benchmark/tests -q``
from the root of the repo (they are not part of the program's tier-1 suite).
They need no chip: the cells run in ``--rehearsal`` on the CPU."""

import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)

RESULT_KEYS = {"correct", "attempted", "failed", "metrics", "device"}


def run_cell(root: str, *args: str, timeout: float = 600.0):
    """One run of ``benchmark/run.py`` under ``root`` with JAX held to the
    CPU -> the finished process."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, os.path.join(root, "benchmark", "run.py"), *args],
        cwd=root, env=env, capture_output=True, text=True, timeout=timeout)


def result_line(stdout: str):
    """The last line of stdout as the result object, or None."""
    import json

    lines = stdout.strip().splitlines()
    try:
        obj = json.loads(lines[-1]) if lines else None
    except ValueError:
        return None
    return obj if isinstance(obj, dict) and RESULT_KEYS <= set(obj) else None


@pytest.fixture(scope="session")
def root() -> str:
    return ROOT
