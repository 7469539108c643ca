"""Blocks on the devices their locations name, and the mesh backend reading
them in place, on four host devices.

The checks run in one child process (``tests/_mesh_child.py``) under four
forced CPU devices, so this process keeps seeing one; each check is a test
case here.
"""

import json
import os
import subprocess
import sys

import pytest

CHILD = os.path.join(os.path.dirname(__file__), "_mesh_child.py")
SRC = os.path.join(os.path.dirname(__file__), os.pardir, "src")
CHECKS = [
    "placement",
    "in_place",
    "agrees_in_place",
    "fallback_blocks_elsewhere",
    "fallback_ranks_stack_their_tasks",
    "scan_lowering_in_place",
]


@pytest.fixture(scope="module")
def results() -> dict:
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    out = subprocess.run(
        [sys.executable, CHILD], env=env, capture_output=True, text=True, timeout=600
    )
    assert out.returncode == 0, f"child failed:\n{out.stdout}\n{out.stderr}"
    return json.loads(out.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("check", CHECKS)
def test_mesh_placement(results, check):
    assert results.get(check) == "ok", results.get(check)
