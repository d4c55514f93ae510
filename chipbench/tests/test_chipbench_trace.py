"""The trace reduction: on synthetic events with hand-counted answers, and
on a short trace recorded on a TPU v5e from the one-chip cell."""
import gzip
import shutil
import sys
from pathlib import Path

import numpy as np
import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

from harness import trace as T  # noqa: E402

RECORDED = Path(__file__).resolve().parent / "data" / "v5e_decode.xplane.pb.gz"


def test_synthetic_busy_self_time_and_idle_gaps():
    # one chip, 0..100 ns traced: a loop 10..50 holding two ops, an op
    # 60..70 and an all-gather 80..90; host spans step 0..55, observe
    # 55..75, nothing after 75 until the last span ends at 100
    ops = {"/device:TPU:0": [
        ("%while.1 = (...) while(...)", 10, 50),
        ("%attend_decode_paged.9 = f32[8] custom-call(...)", 12, 30),
        ("%fusion.2 = bf16[8] fusion(...)", 30, 45),
        ("%copy.3 = bf16[8] copy(...)", 60, 70),
        ("%all-gather.4 = bf16[8] all-gather(...)", 80, 90),
    ]}
    mods = {"/device:TPU:0": [("jit_decm(123)", 10, 50), ("jit_pf(9)", 60, 70)]}
    spans = [("runner.step_multi", 0, 55), ("controller.observe", 55, 75),
             ("runner.start", 95, 100)]
    r = T.reduce(ops, mods, spans)
    assert r["window_s"] == pytest.approx(100e-9)
    assert r["busy_s"] == pytest.approx(60e-9)  # 40 + 10 + 10
    assert r["collective_s"] == pytest.approx(10e-9)
    assert r["op_s"]["%while.1"] == pytest.approx(7e-9)  # 40 - 18 - 15
    assert r["op_s"]["%attend_decode_paged.9"] == pytest.approx(18e-9)
    assert r["program_s"] == pytest.approx({"jit_decm": 40e-9, "jit_pf": 10e-9})
    gaps = dict(r["idle_gaps"])
    # idle: 0..10 and 50..55 in step_multi, 55..60 and 70..75 in observe,
    # 75..80 and 90..95 between calls, 95..100 in start
    assert gaps == pytest.approx({"runner.step_multi": 15e-9, "controller.observe": 10e-9,
                                  T.BETWEEN: 10e-9, "runner.start": 5e-9})
    assert sum(gaps.values()) == pytest.approx(r["window_s"] - r["busy_s"])
    assert T.op_time(r, T.is_paged_attention) == pytest.approx(18e-9)


def test_exit_head_is_told_from_the_final_head_by_its_signature():
    exit_head = ('%body.42 = (f32[8]{0:T(128)}, f32[8]{0:T(128)S(1)}, f32[8]{0:T(128)}, '
                 's32[8]{0:T(128)S(1)}, s32[8]{0:T(128)S(1)}) custom-call(bf16[8,1536] %a, '
                 'bf16[1536,153600] %b, f32[8] %c), custom_call_target="tpu_custom_call"')
    final_head = ('%body.46 = (f32[8]{0:T(128)}, f32[8]{0:T(128)}, f32[8]{0:T(128)}, '
                  's32[8]{0:T(128)S(1)}) custom-call(bf16[8,1536] %a, bf16[1536,153600] %b), '
                  'custom_call_target="tpu_custom_call"')
    assert T.is_exit_head("%body.42", exit_head)
    assert not T.is_exit_head("%body.46", final_head)


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    if not RECORDED.is_file():
        pytest.fail(f"missing {RECORDED}")
    path = tmp_path_factory.mktemp("trace") / "v5e.xplane.pb"
    with gzip.open(RECORDED, "rb") as src, open(path, "wb") as dst:
        shutil.copyfileobj(src, dst)
    return T.load(str(path))


def test_recorded_busy_matches_a_microsecond_grid(recorded):
    ops, mods, spans = recorded
    r = T.reduce(ops, mods, spans)
    assert r["devices"] == 1 and 0 < r["busy_s"] < r["window_s"]
    lo, hi = spans[0][1], max(s[2] for s in spans)
    grid = np.zeros((hi - lo) // 1000 + 2, bool)
    for _, s, e in next(iter(ops.values())):
        s, e = max(s, lo), min(e, hi)
        if e > s:
            grid[(s - lo) // 1000:(e - lo + 999) // 1000] = True
    assert r["busy_s"] == pytest.approx(grid.sum() * 1e-6, rel=0.02)
    # self times partition the busy time; idle gaps fill the rest
    assert sum(r["op_s"].values()) == pytest.approx(r["busy_s"], rel=1e-6)
    assert sum(v for _, v in r["idle_gaps"]) == pytest.approx(r["window_s"] - r["busy_s"], rel=1e-6)


def test_recorded_kernels_come_once_per_layer_and_gather_slot(recorded):
    ops, mods, spans = recorded
    evs = next(iter(ops.values()))
    n_attn = sum(1 for n, _, _ in evs if T.is_paged_attention(T.short(n), n))
    n_exit = sum(1 for n, _, _ in evs if T.is_exit_head(T.short(n), n))
    assert n_attn > 0 and n_attn % 28 == 0  # one call per layer and decode step
    assert n_exit % 4 == 0  # one call per gather slot and ramp step
    r = T.reduce(ops, mods, spans)
    assert set(r["program_s"]) >= {"jit_decm"} or set(r["program_s"]) >= {"jit_decm0"}
