"""A four-chip simulation cell (skewed Ant, hierarchical schedule),
added from data alone, on four virtual CPU devices: a sound run is
correct; the control and a run with the exchange between chips left out
are not."""

import json
import os
import subprocess
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent


def test_four_chip_cell_sound_control_and_no_exchange():
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    p = subprocess.run([sys.executable, str(HERE / "bench_mesh_check.py")],
                       env=env, capture_output=True, text=True, timeout=900)
    assert p.returncode == 0, p.stderr[-3000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    assert out["sound"] is True, out["sound_checks"]
    assert out["control"] is False
    assert out["no_exchange"] is False, out["no_exchange_checks"]
