"""The check that decides ``correct``, driven end to end on the CPU at a
tiny size: the harness's look for a chip is skipped, the rest of a run is
the real one. A sound run is correct; a run whose timed path is broken
underneath is not, once for each fault a served cell can have on one
chip; and the control (the reference in fp8 in the program's place)
reads far above the program."""
import json
import sys
import time
from pathlib import Path

import numpy as np
import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent / "src"))

DATA = Path(__file__).resolve().parent / "data"


def tiny_cell():
    from harness.registry import Cell

    conf = json.loads((DATA / "configs" / "tiny-qwen2.json").read_text())
    mix = json.loads((DATA / "traffic" / "tiny-backlog.json").read_text())
    e2e = [{"name": n, "unit": u} for n, u in (
        ("tokens_per_s", "tokens/s"), ("tpot_p50_ms", "ms"), ("tpot_p90_ms", "ms"),
        ("setup_s", "s"))]
    return Cell("tiny", conf, mix, 1, e2e, [])


def run(control=()):
    from harness.cell import run_cell

    return run_cell(tiny_cell(), 2**31 + 77, 1.5, False, t_process=time.perf_counter(),
                    require_tpu=False, device_kind="TPU v5 lite", control=control)


def test_sound_run_is_correct_and_the_control_is_not():
    res = run(control=("fp8",))
    assert res["correct"], res["compared"]
    prog = {k: v["value"] for k, v in res["compared"].items()}
    ctl = res["control"]["fp8"]
    limits = {k: v["limit"] for k, v in res["compared"].items()}
    assert all(prog[k] <= limits[k] for k in limits)
    assert any(ctl[k] > limits[k] for k in limits), (ctl, limits)
    assert set(res["metrics"]) == {"tokens_per_s", "tpot_p50_ms", "tpot_p90_ms", "setup_s"}


def test_token_altered_where_it_is_produced(monkeypatch):
    from repro.serving.runner import DecodeRunner

    orig = DecodeRunner.step_multi

    def altered(self, *a, **kw):
        labels, unc, finals, exits = orig(self, *a, **kw)
        finals = finals.copy()
        finals[0] = (finals[0] + self.model.cfg.vocab_size // 2) % self.model.cfg.vocab_size
        return labels, unc, finals, exits

    monkeypatch.setattr(DecodeRunner, "step_multi", altered)
    assert not run()["correct"]


def test_step_that_returns_its_state_unchanged(monkeypatch):
    from repro.models.transformer import MultiStepDecodeMixin

    orig = MultiStepDecodeMixin.decode_multi

    def unchanged(self, params, cache, *a, **kw):
        _, outs = orig(self, params, cache, *a, **kw)
        return cache, outs

    monkeypatch.setattr(MultiStepDecodeMixin, "decode_multi", unchanged)
    assert not run()["correct"]


def test_sample_holds_the_longest_and_every_prompt_length():
    from harness import check

    n_tokens = np.array([5, 50, 7, 9, 11, 13, 6])
    prompt_len = np.array([128, 128, 512, 128, 2048, 128, 1024])
    a = check.sample(range(7), n_tokens, prompt_len, 3, min_tokens=90, max_requests=6)
    assert a[0] == 1 and len(a) <= 6
    assert {prompt_len[i] for i in a} == {128, 512, 1024, 2048}
    assert a == check.sample(range(7), n_tokens, prompt_len, 3, min_tokens=90, max_requests=6)
    assert check.sample([1, 0], n_tokens, prompt_len, 3, min_tokens=10, max_requests=6) == [1]


def test_readings_widest_mean_and_flips():
    from harness import check

    per = [{"final": np.array([0.0, 0.0, 0.5, 0.0]), "ramp": np.array([0.0, 0.2]), "scale": 3.0},
           {"final": np.array([0.0, 1.5, 0.0, 0.0]), "ramp": np.zeros(0), "scale": 4.0}]
    r = check.readings(per)
    assert r["final_gap_max"] == 1.5 and r["final_gap_mean"] == 0.25
    assert r["final_flips"] == 25.0 and r["final_positions"] == 8
    assert r["ramp_gap_max"] == 0.2 and r["ramp_gap_mean"] == pytest.approx(0.1)
    assert r["ramp_flips"] == 50.0 and r["logit_scale"] == 4.0
    none = check.readings([{"final": np.zeros(3), "ramp": np.zeros(0), "scale": 1.0}])
    assert none["ramp_gap_mean"] is None and none["final_gap_mean"] == 0.0


def test_verdict_needs_every_number():
    from harness import check

    assert check.verdict({"final_gap": 0.1, "ramp_gap": 0.2}, {"final_gap": 0.5, "ramp_gap": 0.5})
    assert not check.verdict({"final_gap": 0.1, "ramp_gap": None}, {"final_gap": 0.5, "ramp_gap": 0.5})
    assert not check.verdict({"final_gap": 0.6, "ramp_gap": 0.2}, {"final_gap": 0.5, "ramp_gap": 0.5})
