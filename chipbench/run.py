#!/usr/bin/env python3
"""Run one cell of the chip benchmark once.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is looked up in ``BENCHMARK.json`` at the checkout root. With
``--trace 0`` the result carries the cell's end-to-end metrics; with
``--trace 1`` its per-layer metrics, the device's busy and traced
seconds and a breakdown of the trace. The last line of standard output is
the result as one JSON object; the last lines of standard error are the
numbers the correctness check compared, each beside its limit.

It runs only on the chips it is given: where JAX finds no TPU, or fewer
chips than the cell asks for, it exits with code 2 and prints no result.
JAX's persistent compilation cache is kept in ``.jax_cache`` at the
checkout root, so only a checkout's first run of a cell compiles.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(ROOT / "src"))


def use_compile_cache():
    import jax

    jax.config.update("jax_compilation_cache_dir", str(ROOT / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--trace-dir", default=None,
                    help="where the profiler writes (default: a directory under TMPDIR)")
    args = ap.parse_args(argv)
    if args.seed < 0:
        ap.error("--seed must be non-negative")

    from harness.cell import NoChip, log, run_cell
    from harness.registry import load_cell

    cell = load_cell(args.workload)
    use_compile_cache()
    trace_dir = args.trace_dir
    if args.trace and trace_dir is None:
        import tempfile

        trace_dir = tempfile.mkdtemp(prefix="chipbench-trace-")
    try:
        result = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                          t_process=T_PROCESS, trace_dir=trace_dir)
    except NoChip as e:
        log(f"chipbench: {e}")
        return 2
    finally:
        if args.trace and args.trace_dir is None and trace_dir:
            import shutil

            shutil.rmtree(trace_dir, ignore_errors=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
