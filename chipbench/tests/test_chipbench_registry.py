"""Discovery: a configuration, its architecture, a traffic mix and a
metric dropped in as files are found by name, with no file of the
harness edited."""
import json
import shutil
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

from harness import registry  # noqa: E402


@pytest.fixture
def tree(tmp_path):
    root = tmp_path / "checkout"
    shutil.copytree(BENCH, root / "chipbench", ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(BENCH.parent / "BENCHMARK.json", root / "BENCHMARK.json")
    return root


def test_new_config_mix_and_metric_are_found(tree):
    b = tree / "chipbench"
    conf = json.loads((b / "configs" / "qwen2-1.5b.json").read_text())
    conf["name"], conf["architecture"] = "new-model", "new-arch"
    for part in ("arch", "reference"):
        text = (b / part / "qwen2.py").read_text()
        (b / part / "new-arch.py").write_text(text.replace('"""', '"""New architecture. ', 1))
    (b / "configs" / "new-model.json").write_text(json.dumps(conf))
    mix = json.loads((b / "traffic" / "saturated-exits.json").read_text())
    mix["prompt_lengths"], mix["prompt_counts"] = [256], [mix["deck"]]
    (b / "traffic" / "short-only.json").write_text(json.dumps(mix))
    (b / "metrics" / "new.metric.py").write_text(
        "def read(r):\n    return None if not r['host']['steps'] else 2.0 * r['host']['steps']\n")
    bench = json.loads((tree / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "new-model", "source": "https://example.org",
                             "file": "chipbench/configs/new-model.json", "reduced": [],
                             "why": "a test"})
    bench["workloads"].append({"name": "new-model.short-only", "config": "new-model",
                               "traffic": "short-only", "chips": 1, "why": "a test"})
    bench["per_layer"].append({"name": "new.metric", "unit": "x", "better": "higher",
                               "source": "program_span", "layer": "engine",
                               "moves": "tokens_per_s", "workloads": ["new-model.short-only"]})
    (tree / "BENCHMARK.json").write_text(json.dumps(bench))

    cell = registry.load_cell("new-model.short-only", bench_dir=b, root=tree)
    assert cell.config["name"] == "new-model"
    arch = registry.load_arch(cell.config["architecture"], b)
    assert arch.__doc__.startswith("New architecture.")
    assert arch.dims(cell.config)["L"] == conf["model"]["num_hidden_layers"]
    ref = registry.load_reference(cell.config["architecture"], b)
    assert ref.__doc__.startswith("New architecture.")
    assert cell.traffic["prompt_lengths"] == [256]
    assert "new.metric" in [m["name"] for m in cell.per_layer]
    got = registry.read_metrics(["new.metric", "engine.occupancy"],
                                {"host": {"steps": 4, "row_steps": 32, "slots": 8}}, b)
    assert got == {"new.metric": 8.0, "engine.occupancy": 100.0}
    # the existing cell does not report the new cell's metric
    old = registry.load_cell("qwen2-1.5b.saturated-exits", bench_dir=b, root=tree)
    assert "new.metric" not in [m["name"] for m in old.per_layer]


def test_every_listed_part_exists():
    bench = registry.load_benchmark()
    for w in bench["workloads"]:
        cell = registry.load_cell(w["name"])
        registry.load_reference(cell.config["architecture"])
        registry.load_arch(cell.config["architecture"]).layout(cell.config)
    for m in bench["per_layer"]:
        assert callable(registry.load_metric(m["name"]))


def test_unknown_cell_is_refused():
    with pytest.raises(KeyError):
        registry.load_cell("no-such.cell")
