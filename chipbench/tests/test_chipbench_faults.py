"""Whole runs at a CPU size: a sound program is correct; the bfloat16
control and each planted fault of the timed path come out not correct.

The runs share one child process (four virtual CPU devices, so the
four-domain cell runs too); see ``faults.py``.
"""

import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from chipbench.tests.faults import SCENARIOS  # noqa: E402

SOUND = {"sound", "sound_d4", "sound_collide"}


@pytest.fixture(scope="module")
def outcomes():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run([sys.executable, "-m", "chipbench.tests.faults"],
                       cwd=ROOT, env=env, capture_output=True, text=True,
                       timeout=900)
    assert p.returncode == 0, p.stderr[-4000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("scenario", list(SCENARIOS))
def test_scenario(outcomes, scenario):
    got = outcomes[scenario]
    assert got["correct"] == (scenario in SOUND), got["checks"]
