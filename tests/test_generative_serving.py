"""Generative decode serving: engine invariants (token conservation, TPT
monotonicity in exit rate, slot-based continuous batching), KV catch-up
accounting, the mixed heterogeneous cluster, and a real-model DecodeRunner
smoke. Property tests draw cases from seeded numpy generators (suite
policy: stdlib + numpy + jax + pytest only)."""
import dataclasses

import numpy as np
import pytest

from repro.configs import get_config, get_tiny
from repro.core import ApparateController, ControllerConfig, build_profile
from repro.core.controller import BatchDecisions
from repro.serving import (
    ClusterConfig,
    ClusterSimulator,
    GenerativeConfig,
    GenerativeEngine,
    MixedClusterSimulator,
    PlatformConfig,
    SyntheticDecodeRunner,
    SyntheticRunner,
    make_gen_requests,
    make_requests,
    maf_trace,
    offered_decode_qps,
    summarize_generative,
)

PROF = build_profile(
    get_config("gpt2-medium").replace(n_classes=0, ramp_style="tied"),
    mode="decode", chips=1, charge_kv=True,
)
NS = len(PROF.sites)


def _gen_reqs(n=40, tokens=16, mbs=8, load=0.7, seed=0, jitter_tokens=False):
    qps = offered_decode_qps(PROF, max_batch_size=mbs, tokens_per_request=tokens, load=load)
    arr = maf_trace(n, mean_qps=qps, seed=seed)
    nt = tokens
    if jitter_tokens:
        rng = np.random.default_rng(seed)
        nt = rng.integers(1, 2 * tokens, n)
    return make_gen_requests(arr, n_tokens=nt, prompt_len=64,
                             slo_ms=3 * PROF.vanilla_time(1))


class _StubController:
    """Deterministic exit pattern: a fixed fraction of decode tokens exits
    at one site (isolates the engine's timing model from adaptation)."""

    def __init__(self, site: int, rate: float):
        self.active = [site]
        self.site, self.rate = site, rate
        self._i = 0

    def observe(self, labels, unc, finals):
        B = len(finals)
        ex = np.full(B, -1, np.int64)
        for b in range(B):
            self._i += 1
            if (self._i * 2654435761 % 100) < self.rate * 100:
                ex[b] = self.site
        return BatchDecisions(ex, np.asarray(finals).copy(), ex >= 0)

    def total_ramp_overhead(self, bs: int = 1) -> float:
        return 0.0


# -- profile physics ----------------------------------------------------------


def test_decode_step_time_no_exits_equals_vanilla():
    for B in (1, 4, 8):
        st = PROF.decode_step_time([-1] * B, [])
        np.testing.assert_allclose(st, PROF.vanilla_time(B), rtol=1e-12)


@pytest.mark.parametrize("seed", range(6))
def test_decode_step_time_monotone_in_exits(seed):
    """Exiting strictly earlier (or more tokens) never makes a step slower."""
    rng = np.random.default_rng(seed)
    B = 8
    ex = rng.integers(-1, NS, B)
    base = PROF.decode_step_time(ex, [])
    # promote one random non-exit to an exit -> no slower
    j = int(rng.integers(B))
    ex2 = ex.copy()
    ex2[j] = int(rng.integers(NS)) if ex2[j] < 0 else max(ex2[j] - 1, 0)
    assert PROF.decode_step_time(ex2, []) <= base + 1e-12


def test_kv_fill_cost_decreases_with_depth_and_never_free():
    costs = [PROF.kv_fill_cost(s, 1) for s in range(NS)]
    assert all(b <= a + 1e-15 for a, b in zip(costs, costs[1:]))
    assert costs[0] > 0  # earliest exit owes the most catch-up
    # batching amortizes weight traffic: per-token cost shrinks with count
    assert PROF.kv_fill_cost(0, 8) < 8 * PROF.kv_fill_cost(0, 1)


def test_charge_kv_nets_savings():
    plain = dataclasses.replace(PROF, charge_kv_in_savings=False)
    for s in range(NS):
        assert PROF.savings_at_site(s, 1) <= plain.savings_at_site(s, 1) + 1e-15


# -- engine invariants --------------------------------------------------------


@pytest.mark.parametrize("seed,mbs", [(0, 2), (1, 4), (2, 8)])
def test_token_conservation_and_causality(seed, mbs):
    reqs = _gen_reqs(n=30, tokens=12, mbs=mbs, load=1.2, seed=seed, jitter_tokens=True)
    ctl = ApparateController(NS, PROF, ControllerConfig(max_slots=4))
    eng = GenerativeEngine(PROF, GenerativeConfig(max_batch_size=mbs),
                           SyntheticDecodeRunner(NS, exit_site=NS // 3), ctl)
    resp = eng.run(reqs)
    assert sorted(r.rid for r in resp) == sorted(q.rid for q in reqs)
    by_rid = {r.rid: r for r in resp}
    for q in reqs:
        r = by_rid[q.rid]
        # token conservation: exactly n_tokens released, once each
        assert len(r.tokens) == q.n_tokens
        assert len(r.release_ms) == len(r.exit_sites) == len(r.final_tokens) == q.n_tokens
        # causality + per-request monotone release order
        assert r.release_ms[0] >= q.arrival_ms - 1e-9
        assert all(b >= a - 1e-9 for a, b in zip(r.release_ms, r.release_ms[1:]))
    assert sum(len(r.tokens) for r in resp) == sum(q.n_tokens for q in reqs)
    assert eng.n_tokens == sum(q.n_tokens for q in reqs)


def test_continuous_batching_slot_reuse_never_exceeds_capacity():
    """More requests than slots: the engine must reuse freed slots mid-run
    and never run more than max_batch_size tokens in one step."""
    mbs = 3
    reqs = _gen_reqs(n=24, tokens=8, mbs=mbs, load=2.0, seed=4, jitter_tokens=True)
    eng = GenerativeEngine(PROF, GenerativeConfig(max_batch_size=mbs))
    resp = eng.run(reqs)
    assert len(resp) == 24  # all served despite 3 slots: slots were reused
    assert eng.peak_slots <= mbs
    assert max(eng.slot_history) <= mbs
    # under 2x overload the slots actually fill up
    assert eng.peak_slots == mbs


def test_tpt_monotone_in_exit_rate():
    """Paper Table 4 mechanism: higher per-token exit rates monotonically
    lower median TPT (KV catch-up included)."""
    reqs = _gen_reqs(n=30, tokens=16, mbs=8, load=0.8, seed=7)
    site = NS // 3
    p50 = []
    for rate in (0.0, 0.3, 0.6, 0.9):
        eng = GenerativeEngine(
            PROF, GenerativeConfig(max_batch_size=8),
            SyntheticDecodeRunner(NS, exit_site=site), _StubController(site, rate),
        )
        m = summarize_generative(eng.run(reqs), horizon_ms=eng.makespan_ms)
        p50.append(m["tpt_p50_ms"])
    assert all(b <= a + 1e-9 for a, b in zip(p50, p50[1:])), p50
    assert p50[-1] < p50[0]  # and the win is strict at high exit rates


def test_kv_catchup_is_charged_not_free():
    """The same exit pattern must cost strictly more wall time than a
    free-exit model (kv arrays stripped): exits are never free."""
    reqs = _gen_reqs(n=25, tokens=16, mbs=8, load=0.8, seed=9)
    free_prof = dataclasses.replace(PROF, kv_flops=None, kv_wbytes=None,
                                    kv_pibytes=None, charge_kv_in_savings=False)
    runs = {}
    for name, prof in (("charged", PROF), ("free", free_prof)):
        eng = GenerativeEngine(
            prof, GenerativeConfig(max_batch_size=8),
            SyntheticDecodeRunner(NS, exit_site=0), _StubController(0, 1.0),
        )
        eng.run(reqs)
        runs[name] = eng
    assert runs["charged"].kv_ms > 0
    assert runs["free"].kv_ms == 0
    assert runs["charged"].makespan_ms > runs["free"].makespan_ms
    # and despite the charge, exits still beat vanilla end to end
    van = GenerativeEngine(PROF, GenerativeConfig(max_batch_size=8))
    van.run(reqs)
    assert runs["charged"].busy_ms < van.busy_ms


def test_generative_ee_beats_vanilla_at_accuracy_constraint():
    """The PR's acceptance scenario: median TPT with Apparate exits strictly
    below the no-EE baseline at >=0.99 agreement, KV catch-up included."""
    reqs = _gen_reqs(n=120, tokens=24, mbs=8, load=0.6, seed=3)
    base_eng = GenerativeEngine(PROF, GenerativeConfig(max_batch_size=8))
    mb = summarize_generative(base_eng.run(reqs), horizon_ms=base_eng.makespan_ms)
    ctl = ApparateController(NS, PROF, ControllerConfig(max_slots=4, acc_constraint=0.99))
    eng = GenerativeEngine(PROF, GenerativeConfig(max_batch_size=8),
                           SyntheticDecodeRunner(NS, exit_site=NS // 3, easy_frac=0.7), ctl)
    mo = summarize_generative(eng.run(reqs), horizon_ms=eng.makespan_ms)
    assert mo["agreement"] >= 0.99
    assert mo["exit_rate"] > 0.2
    assert eng.kv_ms > 0  # catch-up actually charged
    assert mo["tpt_p50_ms"] < mb["tpt_p50_ms"]


def test_engine_config_validation():
    with pytest.raises(ValueError):
        GenerativeEngine(PROF, GenerativeConfig(max_batch_size=0))
    with pytest.raises(ValueError):
        GenerativeEngine(PROF, runner=SyntheticDecodeRunner(NS, 2))  # no controller
    with pytest.raises(ValueError):
        MixedClusterSimulator()  # no pool at all


# -- mixed heterogeneous cluster ---------------------------------------------


def test_mixed_cluster_both_pools_served_exactly_once():
    cls_prof = build_profile(get_config("gpt2-medium"), mode="decode", chips=1)
    ns_c = len(cls_prof.sites)
    pf = PlatformConfig(policy="tfserve", max_batch_size=8,
                        batch_timeout_ms=cls_prof.vanilla_time(1))
    cls_sim = ClusterSimulator(
        cls_prof, ClusterConfig(n_workers=2, dispatch="jsq", platform=pf),
        runner=SyntheticRunner(ns_c, exit_site=ns_c // 3),
        controllers=[ApparateController(ns_c, cls_prof, ControllerConfig(max_slots=4))
                     for _ in range(2)],
    )
    gens = [
        GenerativeEngine(PROF, GenerativeConfig(max_batch_size=4),
                         SyntheticDecodeRunner(NS, exit_site=NS // 3),
                         ApparateController(NS, PROF, ControllerConfig(max_slots=4)))
        for _ in range(2)
    ]
    mixed = MixedClusterSimulator(cls_sim, gens)
    exec1 = cls_prof.vanilla_time(1)
    cls_reqs = make_requests(maf_trace(150, mean_qps=1.2 * 1000.0 / exec1, seed=1),
                             slo_ms=3 * exec1)
    gen_reqs = _gen_reqs(n=30, tokens=10, mbs=4, load=1.5, seed=2)
    cls_resp, gen_resp = mixed.run(cls_reqs, gen_reqs)
    assert sorted(r.rid for r in cls_resp) == list(range(150))
    assert sorted(r.rid for r in gen_resp) == list(range(30))
    assert sum(len(r.tokens) for r in gen_resp) == sum(q.n_tokens for q in gen_reqs)
    # both generative replicas got work (greedy token-work dispatch)
    assert all(e.n_tokens > 0 for e in gens)
    assert mixed.makespan_ms >= max(e.makespan_ms for e in gens)
    with pytest.raises(ValueError):
        MixedClusterSimulator(None, gens).run(cls_reqs, [])


# -- real-model DecodeRunner smoke -------------------------------------------


@pytest.fixture(scope="module")
def decode_setup():
    import jax  # noqa: F401  (CPU)

    from repro.data import make_decode_stream
    from repro.models import build_model
    from repro.serving import DecodeRunner
    from repro.training import TrainConfig, train

    cfg = get_tiny("qwen2-1.5b").replace(n_layers=4, vocab_size=128)
    model = build_model(cfg)
    stream = make_decode_stream(128, seq_len=17, vocab=128, predict=0.95, seed=11)

    def batches(s):
        rng = np.random.default_rng(s)
        idx = rng.integers(0, len(stream.data), 16)
        toks = stream.data[idx].astype(np.int32)
        return {"tokens": toks[:, :-1], "labels": toks[:, 1:]}

    state, _ = train(model, batches, TrainConfig(steps=40, lr=3e-3), verbose=False)
    runner = DecodeRunner(model, state["params"], stream.data[:, :16],
                          max_new_tokens=10, max_slots=3)
    return cfg, model, runner


def test_decode_runner_streams_per_token_records(decode_setup):
    cfg, model, runner = decode_setup
    t0 = runner.start(0, 0)
    t1 = runner.start(1, 5)
    assert isinstance(t0, int) and isinstance(t1, int)
    lab, unc, fin = runner.step([0, 1], [0, 2])
    assert lab.shape == (2, 2) and unc.shape == (2, 2) and fin.shape == (2,)
    assert (unc >= 0).all() and (unc <= 1).all()
    # records row-ordered by sorted site regardless of caller order
    lab2, unc2, fin2 = runner.step([0, 1], [2, 0])
    assert lab2.shape == (2, 2)
    # slot freed -> stepping it again is a caller error (state removed)
    runner.free(1)
    with pytest.raises(KeyError):
        runner.step([1], [0])
    runner.free(0)


def test_decode_engine_end_to_end_with_real_model(decode_setup):
    cfg, model, runner = decode_setup
    ns = len(model.sites)
    prof_cfg = get_config("gpt2-medium").replace(n_classes=0, ramp_style="tied")
    sites = [round((i + 1) * prof_cfg.n_layers / (ns + 1)) - 1 for i in range(ns)]
    prof = build_profile(prof_cfg, mode="decode", chips=1, sites=sites, charge_kv=True)
    ctl = ApparateController(ns, prof, ControllerConfig(max_slots=3, acc_constraint=0.99))
    qps = offered_decode_qps(prof, max_batch_size=3, tokens_per_request=6, load=0.6)
    arr = maf_trace(8, mean_qps=qps, seed=5)
    reqs = make_gen_requests(arr, n_tokens=6, prompt_len=16,
                             slo_ms=3 * prof.vanilla_time(1))
    eng = GenerativeEngine(prof, GenerativeConfig(max_batch_size=3), runner, ctl)
    resp = eng.run(reqs)
    assert sum(len(r.tokens) for r in resp) == sum(q.n_tokens for q in reqs)
    m = summarize_generative(resp, horizon_ms=eng.makespan_ms)
    assert m["agreement"] >= 0.95  # released tokens track the greedy stream
    assert ctl.stats["samples"] > 0  # controller really saw per-token records


# -- full TPT sweep (slow) ----------------------------------------------------


@pytest.mark.slow
@pytest.mark.parametrize("load", [0.4, 0.8])
@pytest.mark.parametrize("easy", [0.5, 0.9])
def test_full_tpt_sweep(load, easy):
    """Full EE-vs-vanilla TPT sweep over load x easy-traffic fraction: the
    win holds across the grid at the accuracy constraint."""
    reqs = _gen_reqs(n=120, tokens=24, mbs=8, load=load, seed=int(load * 10 + easy * 100))
    base_eng = GenerativeEngine(PROF, GenerativeConfig(max_batch_size=8))
    mb = summarize_generative(base_eng.run(reqs), horizon_ms=base_eng.makespan_ms)
    ctl = ApparateController(NS, PROF, ControllerConfig(max_slots=4, acc_constraint=0.99))
    eng = GenerativeEngine(PROF, GenerativeConfig(max_batch_size=8),
                           SyntheticDecodeRunner(NS, exit_site=NS // 3, easy_frac=easy), ctl)
    mo = summarize_generative(eng.run(reqs), horizon_ms=eng.makespan_ms)
    assert mo["agreement"] >= 0.99
    assert mo["tpt_p50_ms"] < mb["tpt_p50_ms"]


# -- summarize_generative edge cases ------------------------------------------


def _finite_summary(responses, **kw):
    """Summarize under errstate(raise): any divide-by-zero/invalid inside
    the metric computation becomes a test failure, and every returned
    value must be finite (no NaN TPT percentiles)."""
    with np.errstate(all="raise"):
        out = summarize_generative(responses, **kw)
    bad = {k: v for k, v in out.items() if not np.isfinite(v)}
    assert not bad, f"non-finite metrics: {bad}"
    return out


def test_summarize_generative_empty_stream():
    out = _finite_summary([])
    assert out["n"] == 0.0 and out["tokens"] == 0.0
    assert out["tpt_p50_ms"] == 0.0 and out["tokens_per_sec"] == 0.0


def test_summarize_generative_single_token_requests():
    """One-token requests have TTFT but zero TPT samples: percentiles must
    come back 0.0, not NaN, and agreement defaults to 1.0 (the prefill
    token is the final model's own output by construction)."""
    from repro.serving import GenResponse

    resp = [
        GenResponse(rid=i, arrival_ms=i * 2.0, release_ms=[i * 2.0 + 1.5],
                    exit_sites=[-1], tokens=[7], final_tokens=[7], slo_ms=10.0)
        for i in range(5)
    ]
    out = _finite_summary(resp)
    assert out["tpt_p50_ms"] == 0.0 and out["tpt_p95_ms"] == 0.0
    assert out["tpt_mean_ms"] == 0.0
    assert out["agreement"] == 1.0 and out["exit_rate"] == 0.0
    assert out["ttft_p50_ms"] == pytest.approx(1.5)


def test_summarize_generative_single_token_through_engine():
    """End-to-end: an n_tokens=1 request stream finishes at admission
    (prefill only) and must summarize NaN-free."""
    reqs = make_gen_requests(
        maf_trace(8, mean_qps=5.0, seed=0), n_tokens=1, prompt_len=16,
        slo_ms=3 * PROF.vanilla_time(1),
    )
    eng = GenerativeEngine(PROF, GenerativeConfig(max_batch_size=4))
    out = _finite_summary(eng.run(reqs), horizon_ms=eng.makespan_ms)
    assert out["n"] == 8.0 and out["tokens"] == 8.0
    assert out["tpt_p50_ms"] == 0.0


def test_summarize_generative_zero_span_rates_are_zero():
    """A degenerate stream whose whole life is one instant (span == 0)
    must report tokens_per_sec == 0.0 — not inf, not count/eps — and
    raise nothing under errstate(raise). Regression: _per_sec used to
    divide by max(span, 1e-9), turning a zero span into an
    astronomically large bogus rate."""
    from repro.serving import GenResponse
    from repro.serving.metrics import _per_sec

    with np.errstate(all="raise"):
        assert _per_sec(5, 0.0) == 0.0
        assert _per_sec(0, 0.0) == 0.0
        assert _per_sec(3, -1.0) == 0.0  # clock skew: degenerate, not huge
        assert _per_sec(4, 2000.0) == 2.0
    # every release at t=0.0 -> derived span is exactly zero
    resp = [
        GenResponse(rid=i, arrival_ms=0.0, release_ms=[0.0, 0.0],
                    exit_sites=[-1, -1], tokens=[1, 2], final_tokens=[1, 2],
                    slo_ms=10.0)
        for i in range(2)
    ]
    out = _finite_summary(resp)
    assert out["tokens_per_sec"] == 0.0 and out["tokens"] == 4.0
    # explicit zero horizon: same guarantee through the kwarg path
    out = _finite_summary(resp, horizon_ms=0.0)
    assert out["tokens_per_sec"] == 0.0


def test_summarize_zero_span_through_engine():
    """Engine regression for the zero-span guard: summarizing a real run
    against a zero horizon must stay finite with rate 0.0 (the classifier
    summary path shares _per_sec, so it is covered by the same guard)."""
    reqs = make_gen_requests(
        maf_trace(4, mean_qps=5.0, seed=1), n_tokens=2, prompt_len=16,
        slo_ms=3 * PROF.vanilla_time(1),
    )
    eng = GenerativeEngine(PROF, GenerativeConfig(max_batch_size=4))
    out = _finite_summary(eng.run(reqs), horizon_ms=0.0)
    assert out["tokens_per_sec"] == 0.0 and out["tokens"] == 8.0


def test_summarize_generative_all_exited_at_site_zero():
    from repro.serving import GenResponse

    resp = [
        GenResponse(rid=i, arrival_ms=0.0, release_ms=[1.0, 2.0, 3.0],
                    exit_sites=[-1, 0, 0], tokens=[1, 2, 3],
                    final_tokens=[1, 2, 3], slo_ms=10.0)
        for i in range(3)
    ]
    out = _finite_summary(resp)
    assert out["exit_rate"] == 1.0 and out["agreement"] == 1.0
    assert out["tpt_p50_ms"] == pytest.approx(1.0)
    assert out["tpt_slo_miss_rate"] == 0.0


@pytest.mark.parametrize("layout", ["contiguous", "paged"])
def test_sync_window_donates_cache(layout):
    """A sync window donates the slot cache (contiguous rows or the paged
    pool) to its program on every backend: the input buffers are deleted
    after the dispatch, the runner rebinds the program's output, and the
    next window runs on it."""
    import jax

    from repro.models import build_model
    from repro.serving import DecodeRunner

    paged = layout == "paged"
    cfg = get_tiny("qwen2-1.5b").replace(
        n_layers=2, vocab_size=64, decode_attn="paged" if paged else "ref")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    prompts = np.random.default_rng(0).integers(0, 64, (2, 6)).astype(np.int32)
    r = DecodeRunner(model, params, prompts, max_new_tokens=8, max_slots=1,
                     kv_block_size=4)
    r.start(0, 0)
    r.start(1, 1)
    thr = np.zeros(1, np.float32)
    before = jax.tree.leaves(r._cache)
    _, _, f1, _ = r.step_multi([0, 1], [0], 4, thr)
    assert all(x.is_deleted() for x in before)
    assert not any(x.is_deleted() for x in jax.tree.leaves(r._cache))
    _, _, f2, _ = r.step_multi([0, 1], [0], 2, thr)
    assert f1.shape == (4, 2) and f2.shape == (2, 2)
    assert list(r._pos[:2]) == [12, 12]
