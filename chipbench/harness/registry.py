"""Find a cell's parts by name.

``BENCHMARK.json`` at the checkout root lists the cells. A cell names a
configuration (``configs/<config>.json``) and a traffic mix
(``traffic/<traffic>.json``); a configuration names its architecture
(``arch/<architecture>.py`` and ``reference/<architecture>.py``); each
per-layer metric is a reader in ``metrics/<metric>.py``. Adding a configuration, a mix or a metric is
adding a file: nothing here lists them.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
from pathlib import Path
from typing import Callable, Dict, List, Optional

BENCH_DIR = Path(__file__).resolve().parents[1]
ROOT = BENCH_DIR.parent


@dataclasses.dataclass
class Cell:
    name: str
    config: dict  # the configuration file, parsed
    traffic: dict  # the traffic file, parsed
    chips: int
    end_to_end: List[dict]  # BENCHMARK.json entries this cell reports
    per_layer: List[dict]


def load_benchmark(root: Path = ROOT) -> dict:
    path = Path(root) / "BENCHMARK.json"
    if not path.is_file():
        raise FileNotFoundError(f"no BENCHMARK.json at {root}")
    return json.loads(path.read_text())


def _reports(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, bench_dir: Path = BENCH_DIR, root: Path = ROOT) -> Cell:
    bench = load_benchmark(root)
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r}; known: {sorted(cells)}")
    w = cells[name]
    confs = {c["name"]: c for c in bench["configs"]}
    conf_file = Path(root) / confs[w["config"]]["file"]
    return Cell(
        name=name,
        config=json.loads(conf_file.read_text()),
        traffic=load_traffic(w["traffic"], bench_dir),
        chips=int(w["chips"]),
        end_to_end=[m for m in bench["end_to_end"] if _reports(m, name)],
        per_layer=[m for m in bench["per_layer"] if _reports(m, name)],
    )


def load_traffic(name: str, bench_dir: Path = BENCH_DIR) -> dict:
    return json.loads((Path(bench_dir) / "traffic" / f"{name}.json").read_text())


def _load_module(path: Path, prefix: str):
    spec = importlib.util.spec_from_file_location(f"chipbench_{prefix}_{path.stem}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_metric(name: str, bench_dir: Path = BENCH_DIR) -> Callable:
    """The ``read`` function of ``metrics/<name>.py``: it takes the run's
    record and returns a number, or None where it finds nothing to read."""
    return _load_module(Path(bench_dir) / "metrics" / f"{name}.py", "metric").read


def load_arch(name: str, bench_dir: Path = BENCH_DIR):
    """The module ``arch/<name>.py`` a configuration names under
    ``"architecture"``: its sizes, parameter layout, weight draw and
    operation count."""
    return _load_module(Path(bench_dir) / "arch" / f"{name}.py", "arch")


def load_reference(name: str, bench_dir: Path = BENCH_DIR):
    """The plain reference ``reference/<name>.py`` of the architecture a
    configuration names under ``"architecture"``."""
    return _load_module(Path(bench_dir) / "reference" / f"{name}.py", "reference")


def read_metrics(names: List[str], record, bench_dir: Path = BENCH_DIR) -> Dict[str, Optional[float]]:
    return {n: load_metric(n, bench_dir)(record) for n in names}
