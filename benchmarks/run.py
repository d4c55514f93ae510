"""Benchmark harness — one function per paper table/figure.

Output format: ``name,us_per_call,derived`` CSV rows (us_per_call is the
latency-like quantity for the row; derived carries the figure's headline
metric, e.g. win% or accuracy).

  PYTHONPATH=src python -m benchmarks.run            # all
  PYTHONPATH=src python -m benchmarks.run fig13 t1   # substring filter
"""
from __future__ import annotations

import json
import os
import sys
import time

import numpy as np

ROWS = []

_SNAPSHOT_PATH = os.path.join(os.path.dirname(__file__), "..", "BENCH_decode.json")


def emit(name: str, us_per_call: float, derived):
    row = f"{name},{us_per_call:.3f},{derived}"
    ROWS.append(row)
    print(row, flush=True)


def snapshot(section: str, data: dict) -> None:
    """Persist a decode-perf section into BENCH_decode.json (repo root) so
    the perf trajectory of the decode/controller hot paths is recorded
    across PRs, not just printed."""
    existing = {}
    if os.path.exists(_SNAPSHOT_PATH):
        try:
            with open(_SNAPSHOT_PATH) as f:
                existing = json.load(f)
        except (OSError, ValueError):
            existing = {}
    existing[section] = data
    with open(_SNAPSHOT_PATH, "w") as f:
        json.dump(existing, f, indent=1, sort_keys=True)
        f.write("\n")


def _dom(domain, **kw):
    from benchmarks.common import get_domain

    return get_domain(domain, **kw)


def _sim_setup(dom, *, load=0.5, slo_mult=2.0, policy="tfserve", mbs=8, seed=0):
    from repro.serving import PlatformConfig, make_requests, maf_trace, video_trace

    prof = dom["profile"]
    exec1 = prof.vanilla_time(1)
    n0, n = dom["boot"], len(dom["fin"])
    if dom["cfg"].family == "resnet":
        arr = video_trace(n - n0, fps=load * 1000.0 / exec1)
    else:
        arr = maf_trace(n - n0, mean_qps=load * 1000.0 / exec1, seed=seed)
    reqs = make_requests(arr, slo_ms=slo_mult * exec1, items=np.arange(n0, n))
    pf = PlatformConfig(policy=policy, max_batch_size=mbs, batch_timeout_ms=exec1)
    return reqs, pf, prof


# --------------------------------------------------------------- paper Fig 3


def bench_fig3_knobs():
    """Tuning platform knobs trades latency against batch size/throughput."""
    from repro.serving import ServingSimulator, summarize

    dom = _dom("cv")
    for mbs in (4, 8, 16):
        reqs, pf, prof = _sim_setup(dom, load=0.85, mbs=mbs)
        pf.batch_timeout_ms = prof.vanilla_time(1) * mbs  # knob under test
        m = summarize(ServingSimulator(prof, pf).run(reqs))
        emit(f"fig3_knobs_mbs{mbs}_p50", m["p50_ms"] * 1e3, f"mean_batch={m['mean_batch']:.2f}")


# --------------------------------------------------------------- paper Fig 5


def bench_fig5_optimal_ee():
    """Optimal exits cut latency without touching throughput (upper bound)."""
    from benchmarks.common import optimal_exits

    for domain in ("cv", "nlp"):
        dom = _dom(domain)
        idx = np.arange(dom["boot"], len(dom["fin"]))
        saved = optimal_exits(dom, idx)
        van = dom["profile"].vanilla_time(1)
        emit(
            f"fig5_optimal_{domain}_p50",
            (van - np.median(saved)) * 1e3,
            f"win_pct={100 * np.median(saved) / van:.1f}",
        )


# ------------------------------------------------------------- paper Table 1


def bench_table1_threshold_adaptation():
    """One-time vs continual threshold tuning under drift."""
    from benchmarks.common import replay_continual, replay_fixed, tune_on

    for domain in ("cv_hard", "nlp"):
        dom = _dom(domain)
        ns, boot = dom["n_sites"], dom["boot"]
        active = list(range(ns))
        t_init = tune_on(dom, np.arange(0, boot), active)
        r = replay_fixed(dom, t_init.thresholds, active)
        emit(f"t1_{domain}_initial_only", r["median_win_pct"] * 10, f"acc={r['accuracy']:.3f}")
        t_uni = tune_on(dom, np.linspace(0, len(dom['fin']) - 1, boot).astype(int), active)
        r = replay_fixed(dom, t_uni.thresholds, active)
        emit(f"t1_{domain}_uniform", r["median_win_pct"] * 10, f"acc={r['accuracy']:.3f}")
        r = replay_continual(dom)
        emit(f"t1_{domain}_continual", r["median_win_pct"] * 10, f"acc={r['accuracy']:.3f}")


# -------------------------------------------------------------- paper Fig 11


def bench_fig11_tuning_speed():
    """Greedy hill-climb vs grid search: wall time + achieved savings."""
    from benchmarks.common import tune_on, window_from_records
    from repro.core import grid_search_thresholds

    dom = _dom("nlp")
    idx = np.arange(0, 512)
    active = list(range(min(4, dom["n_sites"])))
    wd = window_from_records(dom, idx)
    t0 = time.perf_counter()
    g = grid_search_thresholds(wd, active, dom["profile"], n_sites=dom["n_sites"], step=0.1)
    t_grid = time.perf_counter() - t0
    t = tune_on(dom, idx, active)
    emit("fig11_greedy", t.wall_s * 1e6, f"savings_ms={t.savings_ms:.4f}")
    emit("fig11_grid", t_grid * 1e6, f"savings_ms={g.savings_ms:.4f}")
    emit("fig11_speedup", t_grid / max(t.wall_s, 1e-9),
         f"greedy_minus_grid_ms={t.savings_ms - g.savings_ms:.5f}")


# ----------------------------------------------------------- paper Fig 13/15


def bench_fig13_latency_savings():
    """Apparate vs vanilla end-to-end serving (median + p25 wins)."""
    from repro.core import ApparateController, ControllerConfig
    from repro.serving import ClassifierRunner, ServingSimulator, summarize

    for domain in ("cv", "nlp"):
        dom = _dom(domain)
        reqs, pf, prof = _sim_setup(dom, load=0.5)
        base = summarize(ServingSimulator(prof, pf).run(reqs))
        ctl = ApparateController(
            dom["n_sites"], prof, ControllerConfig(max_slots=6, ramp_budget_frac=0.02)
        )
        runner = ClassifierRunner(dom["model"], dom["params"], dom["stream"].data, max_slots=6)
        resp = ServingSimulator(prof, pf, runner, ctl).run(reqs)
        ours = summarize(resp)
        fin = dom["fin"]
        agree = float(np.mean([r.label == fin[dom["boot"] + r.rid] for r in resp if not r.dropped]))
        for q in ("p25", "p50"):
            win = 100 * (base[f"{q}_ms"] - ours[f"{q}_ms"]) / base[f"{q}_ms"]
            emit(f"fig13_{domain}_{q}", ours[f"{q}_ms"] * 1e3, f"win_pct={win:.1f}")
        emit(f"fig13_{domain}_acc", ours["exit_rate"] * 100, f"acc={agree:.3f}")
        globals().setdefault("_FIG13", {})[domain] = (base, ours)


# -------------------------------------------------------------- paper Fig 14


def bench_fig14_tail_latency():
    """Tail latency stays within the ramp budget (throughput preserved)."""
    cache = globals().get("_FIG13")
    if not cache:
        bench_fig13_latency_savings()
        cache = globals()["_FIG13"]
    for domain, (base, ours) in cache.items():
        d95 = 100 * (ours["p95_ms"] - base["p95_ms"]) / base["p95_ms"]
        emit(f"fig14_{domain}_p95", ours["p95_ms"] * 1e3, f"delta_pct={d95:.2f}")
        emit(
            f"fig14_{domain}_throughput",
            ours.get("throughput_qps", 0.0),
            f"delta_pct={100 * (ours['throughput_qps'] - base['throughput_qps']) / base['throughput_qps']:.2f}",
        )


# ------------------------------------------------------------- paper Table 2


def bench_table2_existing_ee():
    """BranchyNet/DeeBERT-style (all ramps always on, one-time tuning) vs
    Apparate's continual adaptation."""
    from benchmarks.common import per_sample_savings, replay_continual, replay_fixed, tune_on

    for domain, name in (("cv_hard", "branchynet"), ("nlp", "deebert")):
        dom = _dom(domain)
        ns, boot = dom["n_sites"], dom["boot"]
        active = list(range(ns))  # every layer, always active
        best = (None, -1e18)
        for thr in np.arange(0.0, 1.01, 0.05):
            t = np.full(ns, thr, np.float32)
            saved, correct = per_sample_savings(dom, np.arange(boot), t, active)
            if correct.mean() >= 0.99 and saved.mean() > best[1]:
                best = (t, saved.mean())
        t_shared = best[0] if best[0] is not None else np.zeros(ns, np.float32)
        r = replay_fixed(dom, t_shared, active)
        emit(f"t2_{name}", r["median_win_pct"] * 10, f"acc={r['accuracy']:.3f}")
        t_plus = tune_on(dom, np.arange(boot), active)
        r = replay_fixed(dom, t_plus.thresholds, active)
        emit(f"t2_{name}_plus", r["median_win_pct"] * 10, f"acc={r['accuracy']:.3f}")
        r = replay_continual(dom)
        emit(f"t2_apparate_{domain}", r["median_win_pct"] * 10, f"acc={r['accuracy']:.3f}")


# ------------------------------------------------- paper Table 3 and Fig 18


def bench_table3_ramp_budget():
    from benchmarks.common import replay_continual

    dom = _dom("cv_hard")
    for budget in (0.02, 0.05, 0.10):
        r = replay_continual(dom, budget=budget, slots=12)
        emit(f"t3_budget_{int(budget * 100)}pct", r["median_win_pct"] * 10, f"acc={r['accuracy']:.3f}")


def bench_fig18_accuracy_constraint():
    from benchmarks.common import replay_continual

    dom = _dom("cv_hard")
    for acc in (0.995, 0.99, 0.97, 0.95):
        r = replay_continual(dom, acc=acc)
        emit(f"fig18_acc_{acc}", r["median_win_pct"] * 10, f"acc={r['accuracy']:.3f}")


# -------------------------------------------------------------- paper Fig 9


def bench_fig9_ramp_styles():
    """Lightweight pool+FC ramps vs heavier MLP ramps (paper's finding:
    extra ramp compute barely helps, so cheap ramps win)."""
    from benchmarks.common import replay_continual

    for style in ("fc", "mlp"):
        dom = _dom("nlp", ramp_style=style)
        r = replay_continual(dom)
        emit(f"fig9_ramps_{style}", r["median_win_pct"] * 10, f"acc={r['accuracy']:.3f}")


# ------------------------------------------------------------- paper Table 4


def bench_table4_platforms():
    """Apparate's wins are platform-insensitive (TF-Serve vs Clockwork)."""
    from repro.core import ApparateController, ControllerConfig
    from repro.serving import ClassifierRunner, ServingSimulator, summarize

    dom = _dom("cv")
    for policy in ("tfserve", "clockwork"):
        reqs, pf, prof = _sim_setup(dom, load=0.3, policy=policy)
        pf.batch_timeout_ms = prof.vanilla_time(1) * 0.25
        base = summarize(ServingSimulator(prof, pf).run(reqs))
        ctl = ApparateController(dom["n_sites"], prof, ControllerConfig(max_slots=6))
        runner = ClassifierRunner(dom["model"], dom["params"], dom["stream"].data, max_slots=6)
        ours = summarize(ServingSimulator(prof, pf, runner, ctl).run(reqs))
        win = 100 * (base["p50_ms"] - ours["p50_ms"]) / base["p50_ms"]
        emit(f"t4_{policy}_p50", ours["p50_ms"] * 1e3, f"win_pct={win:.1f}")


# -------------------------------------------------------------- paper Fig 17


def bench_fig17_slo():
    from repro.core import ApparateController, ControllerConfig
    from repro.serving import ClassifierRunner, ServingSimulator, summarize

    dom = _dom("cv")
    for slo_mult in (2.0, 4.0, 8.0):
        reqs, pf, prof = _sim_setup(dom, load=0.8, slo_mult=slo_mult, mbs=16)
        pf.batch_timeout_ms = prof.vanilla_time(1) * slo_mult / 2
        base = summarize(ServingSimulator(prof, pf).run(reqs))
        ctl = ApparateController(dom["n_sites"], prof, ControllerConfig(max_slots=6))
        runner = ClassifierRunner(dom["model"], dom["params"], dom["stream"].data, max_slots=6)
        ours = summarize(ServingSimulator(prof, pf, runner, ctl).run(reqs))
        win = 100 * (base["p50_ms"] - ours["p50_ms"]) / base["p50_ms"]
        emit(f"fig17_slo{slo_mult}x", ours["p50_ms"] * 1e3, f"win_pct={win:.1f}")


# ------------------------------------------------------ scale-out (ROADMAP)


def bench_scaleout_goodput():
    """N-worker cluster vs single worker on the bursty MAF trace: goodput
    at equal SLO, with per-replica Apparate controllers staying inside the
    ramp budget (the paper's claim, scaled out)."""
    from repro.configs import get_config
    from repro.core import ApparateController, ControllerConfig, build_profile
    from repro.serving import (
        ClusterConfig,
        ClusterSimulator,
        PlatformConfig,
        SyntheticRunner,
        make_requests,
        maf_trace,
        summarize,
    )

    prof = build_profile(get_config("gpt2-medium"), mode="decode", chips=1)
    ns = len(prof.sites)
    mbs = 8
    qps_cap = mbs * 1000.0 / prof.vanilla_time(mbs)
    arr = maf_trace(3000, mean_qps=4 * 0.6 * qps_cap, seed=7)
    reqs = make_requests(arr, slo_ms=3 * prof.vanilla_time(1))
    pf = PlatformConfig(policy="tfserve", max_batch_size=mbs,
                        batch_timeout_ms=prof.vanilla_time(1))

    def run(nw, dispatch):
        ctls = [ApparateController(ns, prof, ControllerConfig(max_slots=4)) for _ in range(nw)]
        sim = ClusterSimulator(
            prof, ClusterConfig(n_workers=nw, dispatch=dispatch, platform=pf),
            runner=SyntheticRunner(ns, exit_site=ns // 3), controllers=ctls,
        )
        m = summarize(sim.run(reqs), horizon_ms=sim.makespan_ms)
        lim = ControllerConfig().ramp_budget_frac * prof.vanilla_time(1)
        ok = all(c.total_ramp_overhead(1) <= lim + 1e-9 for c in ctls)
        return m, ok

    for nw in (1, 2, 4):
        m, ok = run(nw, "jsq")
        emit(f"scaleout_{nw}w_goodput", m["p50_ms"] * 1e3,
             f"goodput_qps={m.get('goodput_qps', 0.0):.1f};budget_ok={ok}")
    for dispatch in ("round_robin", "jsq", "slo_aware"):
        m, _ = run(4, dispatch)
        emit(f"scaleout_4w_{dispatch}", m["p50_ms"] * 1e3,
             f"goodput_qps={m.get('goodput_qps', 0.0):.1f}")


# ---------------------------------------------- generative decode (Table 4)


def bench_generative_tpt():
    """Generative decode: median time-per-token with per-token Apparate
    exits vs the no-EE baseline at the same accuracy constraint (>=0.99
    agreement), KV catch-up charged (paper §5 Table 4: 22.6–77.9% TPT
    wins). Swept over easy-traffic fractions; the profile pays the
    full-vocab token head (n_classes=0) with LM-head-tied ramps."""
    from repro.configs import get_config
    from repro.core import ApparateController, ControllerConfig, build_profile
    from repro.serving import (
        GenerativeConfig,
        GenerativeEngine,
        SyntheticDecodeRunner,
        make_gen_requests,
        maf_trace,
        offered_decode_qps,
        summarize_generative,
    )

    prof = build_profile(
        get_config("gpt2-medium").replace(n_classes=0, ramp_style="tied"),
        mode="decode", chips=1, charge_kv=True,
    )
    ns = len(prof.sites)
    mbs, tokens = 8, 24
    qps = offered_decode_qps(prof, max_batch_size=mbs, tokens_per_request=tokens, load=0.6)
    arr = maf_trace(200, mean_qps=qps, seed=3)
    reqs = make_gen_requests(arr, n_tokens=tokens, prompt_len=128,
                             slo_ms=3 * prof.vanilla_time(1))
    gcfg = GenerativeConfig(max_batch_size=mbs)
    base_eng = GenerativeEngine(prof, gcfg)
    mb = summarize_generative(base_eng.run(reqs), horizon_ms=base_eng.makespan_ms)
    emit("gen_tpt_vanilla_p50", mb["tpt_p50_ms"] * 1e3,
         f"tokens_per_sec={mb['tokens_per_sec']:.0f}")
    for easy in (0.5, 0.7, 0.9):
        ctl = ApparateController(ns, prof, ControllerConfig(max_slots=4, acc_constraint=0.99))
        eng = GenerativeEngine(
            prof, gcfg, SyntheticDecodeRunner(ns, exit_site=ns // 3, easy_frac=easy), ctl
        )
        mo = summarize_generative(eng.run(reqs), horizon_ms=eng.makespan_ms)
        win = (100 * (mb["tpt_p50_ms"] - mo["tpt_p50_ms"]) / mb["tpt_p50_ms"]
               if mb["tpt_p50_ms"] > 0 else 0.0)
        emit(
            f"gen_tpt_easy{int(easy * 100)}_p50",
            mo["tpt_p50_ms"] * 1e3,
            f"win_pct={win:.1f};agree={mo['agreement']:.3f};"
            f"exit_rate={mo['exit_rate']:.2f};kv_ms={eng.kv_ms:.1f}",
        )


# ---------------------------------------- batched single-dispatch decode


def bench_decode_dispatch():
    """Batched slot-cache decode vs the per-slot B=1 loop on a real tiny
    LM: jitted dispatches issued per decode step (the tentpole claim:
    B -> 1) and step wall-clock at B in {1, 4, 8}, flash-decode wrapper
    ('ref' oracle on CPU; 'kernel' is the same call on TPU)."""
    import jax

    from repro.configs import get_tiny
    from repro.models import build_model
    from repro.serving import DecodeRunner, LoopDecodeRunner

    cfg = get_tiny("qwen2-1.5b").replace(n_layers=4, vocab_size=128, decode_attn="ref")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    prompts = rng.integers(0, 128, (8, 12)).astype(np.int32)
    act = [0, len(model.sites) - 1]
    iters = 8
    snap = {}
    for B in (1, 4, 8):
        wall = {}
        for name, cls in (("loop", LoopDecodeRunner), ("batched", DecodeRunner)):
            r = cls(model, params, prompts, max_new_tokens=iters + 4, max_slots=3)
            for s in range(B):
                r.start(s, s)
            r.step(list(range(B)), act)  # warmup: compile the step shape
            r.dispatches = 0
            t0 = time.perf_counter()
            for _ in range(iters):
                r.step(list(range(B)), act)
            us = (time.perf_counter() - t0) / iters * 1e6
            d = r.dispatches / iters
            emit(f"decode_dispatch_{name}_B{B}", us, f"dispatches_per_step={d:.1f}")
            snap[f"{name}_B{B}"] = {"us_per_step": us, "dispatches_per_step": d}
            wall[name] = us
        emit(f"decode_dispatch_win_B{B}", wall["loop"] / wall["batched"],
             f"batched_speedup_x={wall['loop'] / wall['batched']:.2f}")
        snap[f"speedup_B{B}"] = wall["loop"] / wall["batched"]
    snapshot("decode_dispatch", snap)


def bench_tune_wall():
    """Controller adaptation hot loop: threshold-tuning wall time,
    vectorized (one batched simulate_exits pass per round) vs the
    sequential reference — results asserted bit-identical."""
    from repro.configs import get_config
    from repro.core import build_profile, tune_thresholds, tune_thresholds_reference

    prof = build_profile(get_config("gpt2-medium"), mode="decode", chips=1)
    ns = len(prof.sites)
    rng = np.random.default_rng(0)
    N = 2048
    unc = rng.random((N, ns)).astype(np.float32)
    valid = np.ones((N, ns), bool)
    correct = rng.random((N, ns)) < (1 - 0.3 * unc)
    wd = (unc, correct, valid)
    act = list(range(6))
    reps = 5
    t0 = time.perf_counter()
    vec = [tune_thresholds(wd, act, prof, n_sites=ns) for _ in range(reps)][-1]
    t_vec = (time.perf_counter() - t0) / reps
    t0 = time.perf_counter()
    ref = [tune_thresholds_reference(wd, act, prof, n_sites=ns) for _ in range(reps)][-1]
    t_ref = (time.perf_counter() - t0) / reps
    identical = bool(
        np.array_equal(vec.thresholds, ref.thresholds)
        and vec.savings_ms == ref.savings_ms
        and vec.rounds == ref.rounds
    )
    emit("tune_wall_vectorized", t_vec * 1e6, f"rounds={vec.rounds}")
    emit("tune_wall_reference", t_ref * 1e6, f"identical={identical}")
    emit("tune_wall_speedup", t_ref / t_vec, f"speedup_x={t_ref / t_vec:.2f}")
    snapshot("tune_wall", {
        "us_vectorized": t_vec * 1e6,
        "us_reference": t_ref * 1e6,
        "speedup_x": t_ref / t_vec,
        "identical": identical,
        "rounds": int(vec.rounds),
    })


def bench_paged_kv():
    """Paged vs contiguous batched decode on a real tiny LM under a
    staggered continuous-batching workload (2 of 16 slots concurrently
    live): peak KV-cache bytes must scale with live tokens (block pool)
    rather than n_slots * max_len (contiguous rows), at the SAME dispatch
    count and bit-identical greedy tokens; step wall-clock recorded."""
    import jax

    from repro.configs import get_tiny
    from repro.models import build_model
    from repro.serving import DecodeRunner

    cfg = get_tiny("qwen2-1.5b").replace(n_layers=4, vocab_size=128, decode_attn="ref")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    prompts = rng.integers(0, 128, (16, 16)).astype(np.int32)
    n_slots, max_new, bs_blk, kv_blocks = 16, 16, 8, 12  # cache_len 32 = 4 blocks
    act = [0, len(model.sites) - 1]

    def staggered(r):
        """4 waves of 2 short-lived requests; at most 2 slots live at once
        (of n_slots capacity — the concurrency headroom paging buys)."""
        toks, wall, steps = [], 0.0, 0
        for w in range(4):
            s0, s1 = (2 * w) % n_slots, (2 * w + 1) % n_slots
            toks.append(r.start(s0, 2 * w))
            toks.append(r.start(s1, 2 * w + 1))
            for _ in range(6):
                t0 = time.perf_counter()
                _, _, fin = r.step([s0, s1], act)
                wall += time.perf_counter() - t0
                steps += 1
                toks.extend(int(t) for t in fin)
            r.free(s0)
            r.free(s1)
        return toks, wall / steps * 1e6

    cont = DecodeRunner(model, params, prompts, max_new_tokens=max_new,
                        max_slots=3, n_slots=n_slots)
    paged = DecodeRunner(build_model(cfg.replace(decode_attn="paged")), params,
                         prompts, max_new_tokens=max_new, max_slots=3,
                         n_slots=n_slots, kv_block_size=bs_blk, kv_blocks=kv_blocks)
    staggered(cont), staggered(paged)  # warmup: compile both paths
    tc, us_c = staggered(cont)
    tp, us_p = staggered(paged)
    identical = tc == tp
    dispatches_equal = cont.dispatches == paged.dispatches
    bc, bp = cont.cache_bytes(), paged.cache_bytes()
    st = paged.kv_stats()
    emit("paged_kv_step_contiguous", us_c, f"cache_bytes={bc}")
    emit("paged_kv_step_paged", us_p,
         f"cache_bytes={bp};identical={identical};dispatches_equal={dispatches_equal}")
    emit("paged_kv_bytes_ratio", bc / bp,
         f"peak_blocks={st['peak_blocks']};peak_tokens={st['peak_token_capacity']};"
         f"contig_tokens={cont._rows * cont._cache_len}")
    snapshot("paged_kv", {
        "us_per_step_contiguous": us_c,
        "us_per_step_paged": us_p,
        "contiguous_cache_bytes": bc,
        "paged_cache_bytes": bp,
        "bytes_ratio": bc / bp,
        "peak_blocks": int(st["peak_blocks"]),
        "peak_token_capacity": int(st["peak_token_capacity"]),
        "block_size": int(st["block_size"]),
        "identical": bool(identical),
        "dispatches_equal": bool(dispatches_equal),
    })


# ---------------------------------------------- chunked prefill interleaving


def bench_chunked_prefill():
    """Chunked prefill on the unified engine: TTFT/TPT p95 with and without
    ``--prefill-chunk`` on a long-prompt + short-decode mix. Unchunked, a
    512-token prefill stalls every in-flight decode slot (whole prefills
    land in the TPT tail); chunked, prefill work co-schedules between
    decode steps, so TPT p95 must come back DOWN to decode scale while
    TTFT stays within the interleave bound (one co-scheduled decode step
    per chunk). Gate rows: ``tpt_p95_le_unchunked`` and
    ``ttft_within_bound`` must both be True. Also checks the engine-facade
    equivalence smoke (facade == frozen pre-refactor loop on a seeded
    schedule) so CI catches a drifting core without the full fuzz."""
    from repro.configs import get_config
    from repro.core import build_profile
    from repro.serving import (
        GenerativeConfig,
        GenerativeEngine,
        GenRequest,
        ReferenceGenerativeEngine,
        maf_trace,
        offered_decode_qps,
        summarize_generative,
    )

    prof = build_profile(
        get_config("gpt2-medium").replace(n_classes=0, ramp_style="tied"),
        mode="decode", chips=1, charge_kv=True,
    )
    mbs, chunk, long_prompt = 8, 64, 512
    qps = offered_decode_qps(prof, max_batch_size=mbs, tokens_per_request=16, load=0.7)
    arr = maf_trace(60, mean_qps=qps, seed=1)
    reqs = [
        GenRequest(rid=k, arrival_ms=float(t), slo_ms=3 * prof.vanilla_time(1),
                   item=k, prompt_len=long_prompt if k % 5 == 4 else 32,
                   n_tokens=4 if k % 5 == 4 else 16)
        for k, t in enumerate(arr)
    ]
    runs = {}
    for name, pc in (("unchunked", 0), ("chunked", chunk)):
        eng = GenerativeEngine(prof, GenerativeConfig(max_batch_size=mbs,
                                                      prefill_chunk=pc))
        runs[name] = (summarize_generative(eng.run(reqs), horizon_ms=eng.makespan_ms), eng)
    mu, mc = runs["unchunked"][0], runs["chunked"][0]
    n_chunks_max = -(-long_prompt // chunk)
    ttft_bound = mu["ttft_p95_ms"] + n_chunks_max * prof.vanilla_time(mbs)
    tpt_ok = mc["tpt_p95_ms"] <= mu["tpt_p95_ms"] + 1e-9
    ttft_ok = mc["ttft_p95_ms"] <= ttft_bound + 1e-9
    emit("chunked_prefill_unchunked_tpt_p95", mu["tpt_p95_ms"] * 1e3,
         f"ttft_p95_ms={mu['ttft_p95_ms']:.2f}")
    emit("chunked_prefill_chunked_tpt_p95", mc["tpt_p95_ms"] * 1e3,
         f"ttft_p95_ms={mc['ttft_p95_ms']:.2f};tpt_p95_le_unchunked={tpt_ok};"
         f"ttft_within_bound={ttft_ok}")
    win = (100 * (mu["tpt_p95_ms"] - mc["tpt_p95_ms"]) / mu["tpt_p95_ms"]
           if mu["tpt_p95_ms"] > 0 else 0.0)
    emit("chunked_prefill_tpt_p95_win", win, f"win_pct={win:.1f}")
    # engine-facade equivalence smoke (full fuzz: tests/test_engine_equivalence.py)
    facade = GenerativeEngine(prof, GenerativeConfig(max_batch_size=mbs))
    ref = ReferenceGenerativeEngine(prof, GenerativeConfig(max_batch_size=mbs))
    fa, fb = facade.run(reqs), ref.run(reqs)
    identical = [(r.rid, r.release_ms, r.tokens) for r in fa] == [
        (r.rid, r.release_ms, r.tokens) for r in fb]
    emit("chunked_prefill_facade_smoke", facade.makespan_ms, f"identical={identical}")
    snapshot("chunked_prefill", {
        "chunk_tokens": chunk,
        "unchunked_tpt_p95_ms": mu["tpt_p95_ms"],
        "chunked_tpt_p95_ms": mc["tpt_p95_ms"],
        "tpt_p95_win_pct": win,
        "unchunked_ttft_p95_ms": mu["ttft_p95_ms"],
        "chunked_ttft_p95_ms": mc["ttft_p95_ms"],
        "ttft_bound_ms": ttft_bound,
        "tpt_p95_le_unchunked": bool(tpt_ok),
        "ttft_within_bound": bool(ttft_ok),
        "facade_identical": bool(identical),
        "prefill_chunks": int(runs["chunked"][1].n_chunks),
    })


# ------------------------------------------------------------------ kernels


def bench_kernels():
    """Kernel wrappers vs oracles: wall time of the jnp reference path and
    the kernel's max error against it. The kernels run compiled on an
    accelerator and in interpret mode only on the CPU platform."""
    import jax
    import jax.numpy as jnp

    interpret = jax.devices()[0].platform == "cpu"

    from repro.kernels.ramp_head import ramp_head_stats, ramp_head_stats_ref
    from repro.kernels.ssd import ssd_chunked, ssd_chunked_ref

    h = jax.random.normal(jax.random.PRNGKey(0), (8, 256))
    w = jax.random.normal(jax.random.PRNGKey(1), (256, 4096)) * 0.05
    ref = jax.jit(ramp_head_stats_ref)
    ref(h, w)[0].block_until_ready()
    t0 = time.perf_counter()
    for _ in range(50):
        ref(h, w)[0].block_until_ready()
    us = (time.perf_counter() - t0) / 50 * 1e6
    mk = ramp_head_stats(h, w, interpret=interpret, block_v=1024)
    mr = ref(h, w)
    err = float(jnp.max(jnp.abs(mk[0] - mr[0])))
    emit("kernel_ramp_head_ref", us, f"kernel_max_err={err:.2e}")

    ks = jax.random.split(jax.random.PRNGKey(2), 5)
    x = jax.random.normal(ks[0], (2, 4, 128, 32))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (2, 4, 128)))
    A = -jnp.exp(jax.random.normal(ks[2], (4,)) * 0.3)
    Bm = jax.random.normal(ks[3], (2, 128, 16)) * 0.5
    Cm = jax.random.normal(ks[4], (2, 128, 16)) * 0.5
    ref2 = jax.jit(lambda *a: ssd_chunked_ref(*a, chunk=32))
    ref2(x, dt, A, Bm, Cm)[0].block_until_ready()
    t0 = time.perf_counter()
    for _ in range(20):
        ref2(x, dt, A, Bm, Cm)[0].block_until_ready()
    us = (time.perf_counter() - t0) / 20 * 1e6
    yk, _ = ssd_chunked(x, dt, A, Bm, Cm, chunk=32, interpret=interpret)
    yr, _ = ref2(x, dt, A, Bm, Cm)
    err = float(jnp.max(jnp.abs(yk - yr)))
    emit("kernel_ssd_ref", us, f"kernel_max_err={err:.2e}")


from benchmarks.bench_paged_families import bench_paged_families  # noqa: E402
from benchmarks.bench_prefix_cache import bench_prefix_cache  # noqa: E402
from benchmarks.bench_sharded_decode import bench_sharded_decode  # noqa: E402
from benchmarks.bench_steps_per_sync import bench_steps_per_sync  # noqa: E402

ALL = [
    bench_fig3_knobs,
    bench_fig5_optimal_ee,
    bench_table1_threshold_adaptation,
    bench_fig11_tuning_speed,
    bench_fig13_latency_savings,
    bench_fig14_tail_latency,
    bench_table2_existing_ee,
    bench_table3_ramp_budget,
    bench_fig18_accuracy_constraint,
    bench_fig9_ramp_styles,
    bench_table4_platforms,
    bench_fig17_slo,
    bench_scaleout_goodput,
    bench_generative_tpt,
    bench_decode_dispatch,
    bench_tune_wall,
    bench_paged_kv,
    bench_paged_families,
    bench_chunked_prefill,
    bench_prefix_cache,
    bench_steps_per_sync,
    bench_sharded_decode,
    bench_kernels,
]


def main() -> None:
    filters = [a for a in sys.argv[1:] if not a.startswith("-")]
    print("name,us_per_call,derived")
    failed = []
    for fn in ALL:
        name = fn.__name__
        if filters and not any(f in name for f in filters):
            continue
        t0 = time.perf_counter()
        try:
            fn()
        except Exception as e:  # pragma: no cover
            # keep running the other benchmarks, but the run fails
            emit(f"{name}_ERROR", 0.0, repr(e)[:120])
            failed.append(name)
        print(f"# {name} done in {time.perf_counter() - t0:.1f}s", flush=True)
    if failed:
        sys.exit(f"benchmarks failed: {', '.join(failed)}")


if __name__ == "__main__":
    main()
