#!/usr/bin/env python3
"""The correctness check's control, run on the chip.

    python3 chipbench/control.py --workload <cell> --seeds 1,2,3 --seconds 20 [--control int8,fp8]

For each seed, in one process: the cell's served window at its own load
(long enough to finish the mix's longest requests), then at the same
sampled positions the program's readings and the readings of the
reference computed in a lower precision (the configuration's
``check.control`` unless ``--control`` names others), each judged by
the same verdict and limits as the program. One JSON line per seed on
standard output: every reading, and ``correct`` for the program and for
each control (a control has to come out false). The program's readings over a dozen seeds set a
limit's lower end, the control's its upper end (PERF.md). The
benchmark's own runs never run this.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE.parent / "src"))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--control", default=None)
    args = ap.parse_args(argv)

    from harness import check
    from harness.cell import NoChip, log, run_cell
    from harness.registry import load_cell
    from run import use_compile_cache

    cell = load_cell(args.workload)
    quants = (args.control or cell.config["check"]["control"]).split(",")
    use_compile_cache()
    for seed in (int(s) for s in args.seeds.split(",")):
        gc.collect()  # the previous seed's engine, runner and weights sit in cycles
        t0 = time.perf_counter()
        try:
            res = run_cell(cell, seed, args.seconds, False, t_process=t0, control=quants)
        except NoChip as e:
            log(f"chipbench: {e}")
            return 2
        limits = cell.config["check"]["limits"]
        print(json.dumps({
            "seed": seed, "correct": res["correct"], "program": res["readings"],
            "control": {q: {"correct": check.verdict(r, limits), **r}
                        for q, r in res["control"].items()},
            "limits": limits}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
