"""The tp=4 path on four host CPU devices: a sound run is correct, and a
run with the exchange between chips left out is not."""
import json
import subprocess
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent


def test_exchange_between_chips_left_out_is_caught():
    p = subprocess.run([sys.executable, str(HERE / "tp4_runs.py")], capture_output=True,
                       text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    assert out == {"sound": True, "no_exchange": False}, p.stderr[-3000:]
