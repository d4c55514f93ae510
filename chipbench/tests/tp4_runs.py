"""Runs the tiny tp=4 cell on four host CPU devices, sound and with the
exchange between chips left out, and prints whether each came out
correct. Started by ``test_chipbench_tp4.py`` in a fresh process, since
the device count is fixed when JAX starts."""
import json
import os
import sys
import time
from pathlib import Path

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
os.environ["JAX_PLATFORMS"] = "cpu"
BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent / "src"))
DATA = BENCH / "tests" / "data"


def run():
    from harness.cell import run_cell
    from harness.registry import Cell

    conf = json.loads((DATA / "configs" / "tiny-qwen15-tp4.json").read_text())
    mix = json.loads((DATA / "traffic" / "tiny-backlog.json").read_text())
    cell = Cell("tiny-tp4", conf, mix, 4, [{"name": "tokens_per_s", "unit": "tokens/s"}], [])
    return run_cell(cell, 2**31 + 78, 1.5, False, t_process=time.perf_counter(),
                    require_tpu=False, device_kind="TPU v5 lite")["correct"]


def main():
    import jax.numpy as jnp

    from repro.models import transformer

    out = {"sound": run()}
    # the exchange left out: each chip carries on with its own slice,
    # tiled to the gathered width, instead of all chips' slices
    transformer._tp_gather = lambda axis_name, y: jnp.concatenate([y] * 4, axis=-1)
    out["no_exchange"] = run()
    print(json.dumps(out))


if __name__ == "__main__":
    main()
