"""Multi-device coverage via the subprocess battery.

pytest itself sees ONE device (dry-run hygiene); everything needing a
mesh runs in a child process with 8 fake host devices. One subprocess
executes all checks; each gets its own pytest for reporting.
"""
import json
import os
import subprocess
import sys

import pytest

from repro.testing import distributed_checks as dc

# each check spawns its own 8-device subprocess: minutes of wall clock —
# the fast CI tier (-m "not slow") skips the whole battery
pytestmark = pytest.mark.slow

CHECK_NAMES = [f.__name__ for f in dc.ALL_CHECKS]


@pytest.fixture(scope="session")
def battery_results():
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    proc = subprocess.run(
        [sys.executable, "-m", "repro.testing.run_checks"],
        env=env, capture_output=True, text=True, timeout=1800)
    lines = [ln for ln in proc.stdout.splitlines() if ln.startswith("{")]
    assert lines, f"battery produced no JSON.\nstdout: {proc.stdout[-2000:]}\n" \
                  f"stderr: {proc.stderr[-2000:]}"
    return json.loads(lines[-1])


@pytest.mark.parametrize("name", CHECK_NAMES)
def test_check(battery_results, name):
    res = battery_results[name]
    assert res["ok"], f"{name} failed:\n{res.get('error', '')}"
