"""In-program spans (`repro.tracing`) and the engine's short-window counters.

Off, a span is one shared no-op: no clock read, no allocation. On, spans
record their parent, key and times in preallocated arrays, reduce to
self times by `summary`, land on the profiler's host line on the same
clock, and nest as the served path does: each sync window's
`engine.window` holds one `runner.prepare`, `runner.dispatch`,
`runner.wait` and `runner.drain`, in that order. Tracing changes no token
and no exit.
"""
import glob
import os
import tracemalloc

import jax
import numpy as np
import pytest

from repro import tracing


@pytest.fixture
def fresh():
    tracing.disable()
    tracing.reset()
    yield
    tracing.disable()
    tracing.reset()


class _FakeClock:
    """Stands in for the ``time`` module: ``perf_counter_ns`` returns the
    scripted values in turn."""

    def __init__(self, values):
        self.values = list(values)

    def perf_counter_ns(self):
        return self.values.pop(0)


class _NoClock:
    def perf_counter_ns(self):
        raise AssertionError("the clock was read while tracing is off")


def test_off_is_one_shared_noop_without_clock_or_allocation(fresh, monkeypatch):
    monkeypatch.setattr(tracing, "time", _NoClock())
    a, b = tracing.span("engine.window"), tracing.span("runner.wait", 7)
    assert a is b
    with a:
        pass
    tracemalloc.start()
    try:
        snap0 = tracemalloc.take_snapshot()
        for _ in range(1000):
            with tracing.span("runner.prepare"):
                with tracing.span("runner.dispatch", 3):
                    pass
        snap1 = tracemalloc.take_snapshot()
    finally:
        tracemalloc.stop()
    flt = [tracemalloc.Filter(True, tracing.__file__)]
    grown = [d for d in snap1.filter_traces(flt).compare_to(snap0.filter_traces(flt), "lineno")
             if d.size_diff > 0]
    assert grown == []
    assert tracing.spans()["name"].size == 0
    assert tracing.summary(0.0, 1e12) == {}


def test_nested_spans_parent_key_self_time_and_summary(fresh, monkeypatch):
    # window 100..200 ns holding prepare 110..130 and wait 140..190, which
    # holds a drain 150..160; then a second window 300..340 with no child
    monkeypatch.setattr(tracing, "time", _FakeClock(
        [100, 110, 130, 140, 150, 160, 190, 200, 300, 340]))
    tracing.enable()
    with tracing.span("engine.window"):
        with tracing.span("runner.prepare", 5):
            pass
        with tracing.span("runner.wait"):
            with tracing.span("runner.drain"):
                pass
    with tracing.span("engine.window"):
        pass
    s = tracing.spans()
    names = [tracing.NAMES[i] for i in s["name"]]
    assert names == ["engine.window", "runner.prepare", "runner.wait", "runner.drain",
                     "engine.window"]
    assert s["parent"].tolist() == [-1, 0, 0, 2, -1]
    assert s["key"].tolist() == [-1, 5, -1, -1, -1]
    assert s["t0_ns"].tolist() == [100, 110, 140, 150, 300]
    assert s["t1_ns"].tolist() == [200, 130, 190, 160, 340]
    out = tracing.summary(0.0, 1e-6)
    assert out["engine.window"]["count"] == 2
    assert out["engine.window"]["total_s"] == pytest.approx(140e-9)
    assert out["engine.window"]["self_s"] == pytest.approx(30e-9 + 40e-9)  # 100-20-50, 40
    assert out["engine.window"]["max_s"] == pytest.approx(100e-9)
    assert out["runner.wait"]["self_s"] == pytest.approx(40e-9)  # 50 - 10
    assert out["runner.drain"]["self_s"] == pytest.approx(10e-9)
    # only spans wholly inside the interval count
    part = tracing.summary(105e-9, 250e-9)
    assert set(part) == {"runner.prepare", "runner.wait", "runner.drain"}


def test_overflow_counts_drops_and_never_raises(fresh):
    tracing.enable(capacity=3)
    try:
        for _ in range(5):
            with tracing.span("engine.window"):
                with tracing.span("runner.wait"):
                    pass
        assert tracing.spans()["name"].size == 3
        assert tracing.dropped() == 7
        # a dropped parent leaves no dangling index behind
        assert set(tracing.spans()["parent"].tolist()) <= {-1, 0}
    finally:
        tracing.enable()  # back to the default capacity


def test_no_span_takes_a_name_the_benchmark_wraps_with():
    # the chip benchmark's wrappers annotate the same trace; a shared name
    # would change what its trace reduction reads
    import re
    from pathlib import Path

    src = Path(__file__).resolve().parents[1] / "chipbench" / "harness" / "served.py"
    wrapped = set(re.findall(r'_span\("([^"]+)"\)', src.read_text()))
    assert {"runner.start", "runner.step_multi", "controller.observe"} <= wrapped
    assert not wrapped & set(tracing.NAMES)
    assert len(set(tracing.NAMES)) == len(tracing.NAMES)


def test_spans_share_the_profilers_clock(fresh, tmp_path):
    from jax.profiler import ProfileData

    import time

    tracing.enable()
    jax.profiler.start_trace(str(tmp_path))
    try:
        for name in ("engine.admit", "runner.prefill", "engine.window", "runner.wait",
                     "controller.tune"):
            with tracing.span(name):
                time.sleep(0.002)
    finally:
        jax.profiler.stop_trace()
    rec = tracing.spans()
    paths = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"), recursive=True)
    assert paths
    starts = {}
    for plane in ProfileData.from_file(paths[0]).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name in tracing.NAMES:
                        starts[ev.name] = int(ev.start_ns)
    assert len(starts) == 5
    off = [starts[tracing.NAMES[i]] - t0 for i, t0 in zip(rec["name"], rec["t0_ns"])]
    assert max(off) - min(off) < 200_000  # ns


def test_serve_profile_dir_records_the_spans(fresh, tmp_path):
    from jax.profiler import ProfileData

    from repro.launch.serve import profiled

    with profiled(str(tmp_path)):
        with tracing.span("engine.window"):
            pass
    assert tracing.span("engine.window") is tracing.span("runner.wait")  # off again
    paths = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"), recursive=True)
    names = {ev.name for plane in ProfileData.from_file(paths[0]).planes
             for line in plane.lines for ev in line.events}
    assert "engine.window" in names


# -- the served path -----------------------------------------------------------

SPS = 4
N_TOKENS = [3, 9, 6, 4, 11, 5, 7, 2]
WINDOW_CHILDREN = ("runner.prepare", "runner.dispatch", "runner.wait", "runner.drain")


def _serve(prefill_chunk, traced):
    from repro.configs import get_config, get_tiny
    from repro.core import ApparateController, ControllerConfig, build_profile
    from repro.models import build_model
    from repro.serving import DecodeRunner, GenerativeConfig, GenerativeEngine
    from repro.serving.request import GenRequest

    cfg = get_tiny("qwen2-1.5b").replace(n_layers=3, vocab_size=128, decode_attn="paged")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(2))
    prompts = np.random.default_rng(3).integers(0, 128, (len(N_TOKENS), 8)).astype(np.int32)
    runner = DecodeRunner(model, params, prompts, max_new_tokens=max(N_TOKENS), max_slots=2,
                          n_slots=3, kv_block_size=4)
    ns = runner.n_sites
    prof_cfg = get_config("gpt2-medium").replace(n_classes=0, ramp_style="tied")
    sites = [round((i + 1) * prof_cfg.n_layers / (ns + 1)) - 1 for i in range(ns)]
    prof = build_profile(prof_cfg, mode="decode", chips=1, sites=sites, charge_kv=True)
    ctl = ApparateController(ns, prof, ControllerConfig(max_slots=2, ramp_budget_frac=1.0,
                                                        min_samples_to_tune=4))
    eng = GenerativeEngine(prof, GenerativeConfig(max_batch_size=3, steps_per_sync=SPS,
                                                  prefill_chunk=prefill_chunk), runner, ctl)
    calls = []
    orig = runner.step_multi

    def step_multi(slots, active, n_steps, thresholds):
        pos = runner._pos[list(slots)].copy()
        out = orig(slots, active, n_steps, thresholds)
        nd = out[2].shape[0]
        # the pages the paged kernel attends at each executed step, and
        # the table pages it could walk
        bs, nb = runner._bs_blk, runner._max_blocks
        attended = sum(int((p + t) // bs + 1) for t in range(nd) for p in pos)
        calls.append((int(n_steps), nd, attended, len(slots) * nd * nb))
        return out

    runner.step_multi = step_multi
    reqs = [GenRequest(rid=i, arrival_ms=0.0, slo_ms=float("inf"), item=i, prompt_len=8,
                       n_tokens=n) for i, n in enumerate(N_TOKENS)]
    if traced:
        tracing.enable()
    try:
        resp = eng.run(reqs)
    finally:
        tracing.disable()
    return resp, eng.stats(), calls, tracing.spans()


@pytest.fixture(scope="module", params=[0, 4], ids=["serial-prefill", "chunked-prefill"])
def served(request):
    tracing.disable()
    tracing.reset()
    off = _serve(request.param, traced=False)
    on = _serve(request.param, traced=True)
    tracing.reset()
    return request.param, off, on


def test_each_window_holds_one_of_each_runner_span(served):
    chunk, _, (_, stats, calls, s) = served
    names = np.asarray([tracing.NAMES[i] for i in s["name"]])
    wins = np.nonzero(names == "engine.window")[0]
    with_dispatch = 0
    for w in wins:
        kids = np.nonzero(s["parent"] == w)[0]
        got = [names[k] for k in kids if names[k].startswith("runner.")]
        if not got:
            assert chunk > 0  # every slot still prefilling: nothing to step
            continue
        with_dispatch += 1
        assert tuple(got) == WINDOW_CHILDREN
        runner_kids = [k for k in kids if names[k].startswith("runner.")]
        t0, t1 = s["t0_ns"][runner_kids], s["t1_ns"][runner_kids]
        assert (t1[:-1] <= t0[1:]).all()  # in order, not overlapping
        assert s["t0_ns"][w] <= t0[0] and t1[-1] <= s["t1_ns"][w]
    assert with_dispatch == len(calls) == stats["sync_windows"]
    # the controller's spans sit inside the replay, one decide per replayed step
    decide = np.nonzero(names == "controller.decide")[0]
    assert len(decide) == sum(c[1] for c in calls)
    assert all(names[s["parent"][d]] == "engine.replay" for d in decide)


def test_short_window_counters_match_the_calls(served):
    chunk, (_, stats, calls, _), _ = served
    asked_short = sum(1 for n, *_ in calls if n < SPS)
    assert asked_short > 0
    assert stats["short_windows_finishing"] + stats["short_windows_prefilling"] == asked_short
    assert stats["short_windows"] == sum(1 for _, nd, *_ in calls if nd < SPS)
    assert stats["short_windows_early_end"] == sum(1 for n, nd, *_ in calls if nd < n)
    assert stats["short_windows_headroom"] == 0  # slot caches hold every request whole
    assert (stats["short_windows_prefilling"] > 0) == (chunk > 0)


def test_kv_page_counters_reach_stats(served):
    _, (_, stats, calls, _), _ = served
    assert stats["kv_pages_attended"] == sum(c[2] for c in calls)
    assert stats["kv_pages_table"] == sum(c[3] for c in calls)
    assert 0 < stats["kv_pages_attended"] < stats["kv_pages_table"]


def test_tracing_changes_no_token_and_no_exit(served):
    _, (off, _, calls_off, _), (on, _, calls_on, _) = served
    assert calls_off == calls_on
    for a, b in zip(off, on):
        assert a.rid == b.rid
        assert a.tokens == b.tokens
        assert a.final_tokens == b.final_tokens
        assert a.exit_sites == b.exit_sites
