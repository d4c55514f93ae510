"""Production serving launcher: end-to-end Apparate serving on a trained
(tiny) model with a drifting synthetic workload. With ``--workers N`` the
stream is served by the scale-out cluster engine: a dispatcher spreads
load across N replicas, each with its own Apparate controller. With
``--mode generative`` the workload is autoregressive decode: each request
generates ``--decode-tokens`` tokens through the continuous-batching
engine with per-token early exits and KV catch-up accounting.

  PYTHONPATH=src python -m repro.launch.serve --domain cv --n 3000
  PYTHONPATH=src python -m repro.launch.serve --workers 4 --dispatch jsq
  PYTHONPATH=src python -m repro.launch.serve --mode generative --decode-tokens 16

``--profile-dir DIR`` records a profiler trace of the run into ``DIR``
with the program's spans (``repro.tracing``) on the host line, beside the
device's operations; open it in TensorBoard's profile plugin or xprof.
"""
from __future__ import annotations

import argparse
import contextlib
import json

import numpy as np

from repro import tracing
from repro.configs import get_bench, get_config, get_tiny
from repro.core import ApparateController, ControllerConfig, build_profile
from repro.data import make_decode_stream, make_image_stream, make_token_stream
from repro.launch.compile_cache import use_compile_cache
from repro.launch.tuning import PRESETS, apply_preset
from repro.models import build_model
from repro.serving import (
    AdmissionConfig,
    AdmissionPolicy,
    ClassifierRunner,
    ClusterConfig,
    ClusterSimulator,
    DecodeRunner,
    GenerativeConfig,
    GenerativeEngine,
    PlatformConfig,
    ServingSimulator,
    ShardedDecodeRunner,
    make_gen_requests,
    make_requests,
    maf_trace,
    offered_decode_qps,
    savings_vs,
    summarize,
    summarize_cluster,
    summarize_generative,
    video_trace,
)
from repro.training import TrainConfig, train


def build_domain(domain: str, n: int, seed: int = 2):
    """Train a paper-shape bench model on the bootstrap split (first 10%,
    paper §4) and return (model, params, stream, profile)."""
    if domain == "cv":
        cfg = get_bench("resnet18").replace(n_classes=10)
        model = build_model(cfg)
        stream = make_image_stream(n, img_size=cfg.img_size, n_classes=10, mode="cv", seed=seed)
        batch_key = "images"
        prof_cfg = get_config("resnet18").replace(resnet_widths=(64, 128, 256, 512), img_size=224)
        lr, steps = 3e-3, 150
    else:
        cfg = get_bench("bert-base").replace(n_classes=10)
        model = build_model(cfg)
        stream = make_token_stream(n, seq_len=32, vocab=cfg.vocab_size, n_classes=10, mode="nlp", seed=seed)
        batch_key = "tokens"
        prof_cfg = get_config("bert-base")
        lr, steps = 1e-3, 200
    boot = max(n // 10, 256)

    def batches(s):
        rng = np.random.default_rng(s)
        idx = rng.integers(0, boot, 64)
        return {batch_key: stream.data[idx], "labels": stream.labels[idx]}

    state, _ = train(model, batches, TrainConfig(steps=steps, lr=lr), verbose=False)
    profile = build_profile(prof_cfg, mode="decode", chips=1)
    return cfg, model, state["params"], stream, profile, boot


def serve(domain: str, n: int, *, policy="tfserve", budget=0.02, acc=0.99,
          load=0.5, seed=2, slots=6, workers=1, dispatch="jsq", admission=False,
          admission_slack=1.0, verbose=True):
    cfg, model, params, stream, prof, boot = build_domain(domain, n, seed)
    runner = ClassifierRunner(model, params, stream.data, max_slots=slots)
    ccfg = ControllerConfig(max_slots=slots, ramp_budget_frac=budget, acc_constraint=acc)
    exec1 = prof.vanilla_time(1)
    n_serve = n - boot
    # the offered load scales with the cluster: each replica sees ~`load`
    if domain == "cv":
        arrivals = video_trace(n_serve, fps=workers * load * 1000.0 / exec1)
    else:
        arrivals = maf_trace(n_serve, mean_qps=workers * load * 1000.0 / exec1, seed=seed)
    reqs = make_requests(arrivals, slo_ms=2 * exec1, items=np.arange(boot, n))
    pf = PlatformConfig(policy=policy, max_batch_size=8, batch_timeout_ms=exec1)

    def adm():
        return (AdmissionPolicy(AdmissionConfig(slack=admission_slack))
                if admission else None)

    base_sim = ClusterSimulator(
        prof, ClusterConfig(n_workers=workers, dispatch=dispatch, platform=pf,
                            admission=adm()))
    base = base_sim.run(reqs)
    ctls = [ApparateController(len(model.sites), prof, ccfg) for _ in range(workers)]
    sim = ClusterSimulator(
        prof, ClusterConfig(n_workers=workers, dispatch=dispatch, platform=pf,
                            admission=adm()),
        runner=runner, controllers=ctls)
    resp = sim.run(reqs)
    van = runner.vanilla_labels(n)
    agree = float(np.mean([r.label == van[boot + r.rid] for r in resp if not r.dropped]))
    rep_b = summarize_cluster(base, horizon_ms=base_sim.makespan_ms, n_workers=workers)
    rep_o = summarize_cluster(resp, horizon_ms=sim.makespan_ms, n_workers=workers)
    mb, mo = rep_b["aggregate"], rep_o["aggregate"]
    out = {
        "domain": domain, "workers": workers, "dispatch": dispatch,
        "vanilla": mb, "apparate": mo, "accuracy": agree,
        "wins": savings_vs(mb, mo),
        "controllers": [dict(c.stats) for c in ctls],
        "active_ramps": [list(map(int, c.active)) for c in ctls],
    }
    if admission:
        out["admission"] = {"vanilla": base_sim.cfg.admission.stats(),
                            "apparate": sim.cfg.admission.stats()}
    if workers > 1:
        out["per_worker"] = rep_o["workers"]
        out["worker_stats"] = sim.worker_stats()
    if verbose:
        print(json.dumps(out, indent=1, default=float))
    return out


def build_generative_engine(model, params, prompts, profile, gcfg, ccfg, *,
                            max_new_tokens, kv_block_size=0, kv_blocks=None,
                            prefix_cache=False, mesh=None, admission=None):
    """The served generative path: a ``DecodeRunner`` over ``model`` and
    ``params`` (a ``ShardedDecodeRunner`` when ``mesh`` is given), an
    ``ApparateController`` over the model's ramp sites, and the
    ``GenerativeEngine`` that drives both with ``gcfg.max_batch_size``
    decode slots and ``ccfg.max_slots`` ramp gather slots.
    ``kv_block_size > 0`` pages the KV cache (the model config must then
    pick a ``decode_attn='paged*'`` path). The runner and controller are
    ``engine.runner`` and ``engine.controller``."""
    rkw = dict(max_new_tokens=max_new_tokens, max_slots=ccfg.max_slots,
               n_slots=gcfg.max_batch_size)
    if kv_block_size:
        rkw.update(kv_block_size=kv_block_size, kv_blocks=kv_blocks,
                   prefix_cache=prefix_cache)
    if mesh is not None:
        runner = ShardedDecodeRunner(model, params, prompts, mesh=mesh, **rkw)
    else:
        runner = DecodeRunner(model, params, prompts, **rkw)
    ctl = ApparateController(len(model.sites), profile, ccfg)
    return GenerativeEngine(profile, gcfg, runner, ctl, admission=admission)


def serve_generative(n=48, *, decode_tokens=16, budget=0.02, acc=0.99, load=0.5,
                     seed=2, slots=4, layers=6, kv_block_size=0, kv_blocks=None,
                     prefill_chunk=0, admission=False, admission_slack=1.0,
                     prefix_cache=False, preempt="none", steps_per_sync=1,
                     tp=1, dp=1, pp=1, verbose=True):
    """End-to-end generative decode serving on a trained tiny LM: vanilla
    (no-EE) vs Apparate per-token exits, KV catch-up charged, at the same
    accuracy constraint. The latency profile uses the full qwen2-1.5b
    shape truncated to the tiny model's layer count, so sites align with
    the served model while step times reflect production scale.

    ``kv_block_size > 0`` switches the decode cache to the PAGED block
    pool (``decode_attn='paged'``): KV memory scales with live tokens
    instead of ``n_slots * max_len``; ``kv_blocks`` caps the pool (default
    auto-sizes to full slot capacity).

    ``prefill_chunk > 0`` splits each prompt's prefill into chunks
    co-scheduled with in-flight decode steps (the unified engine's
    chunked-prefill path; ``DecodeRunner`` prefills the slot cache
    incrementally). ``admission`` enables the SLO-aware admission policy
    (drop hopeless streams at admission, shed doomed slots mid-run).

    ``prefix_cache`` (paged only) shares cached prompt-prefix blocks
    across slots via the refcounted allocator — repeated prompts skip
    their prefill entirely. ``preempt`` picks the pool-exhaustion
    reaction: 'swap' moves a victim's blocks to a host buffer and
    readmits it later; 'shed' discards the victim; 'none' propagates
    ``PoolExhausted`` (legacy).

    ``steps_per_sync > 1`` dispatches decode SYNC WINDOWS: up to that
    many decode steps per jitted while_loop with on-device exit decisions
    against a stale threshold copy, one controller round-trip per window
    (``GenerativeConfig.steps_per_sync``).

    ``tp`` / ``dp`` > 1 serve through ``ShardedDecodeRunner`` on a
    ``(data, model)`` mesh (tensor-parallel attention/MLP, per-device KV
    shards — bit-identical to the single-device runner); ``pp`` > 1
    additionally reports an exit-gated PIPELINE decode window demo on a
    ``(stage,)`` mesh. Both need enough backend devices (on CPU export
    ``XLA_FLAGS=--xla_force_host_platform_device_count=N`` first)."""
    if prefix_cache and not kv_block_size:
        raise ValueError("--prefix-cache requires --kv-block-size > 0 (paged KV)")
    if preempt != "none" and not kv_block_size:
        raise ValueError("--preempt requires --kv-block-size > 0 (paged KV)")
    # decode_attn='ref' routes single-token attention through the
    # flash-decode wrapper (kernels/decode_attention) — the jnp oracle on
    # CPU; 'kernel' is the Pallas path on real hardware. 'paged' is the
    # block-pool analogue ('paged-kernel' on real hardware).
    tiny = get_tiny("qwen2-1.5b").replace(
        n_layers=layers, vocab_size=128,
        decode_attn="paged" if kv_block_size else "ref",
    )
    model = build_model(tiny)
    seq_len = 24
    stream = make_decode_stream(max(2 * n, 256), seq_len=seq_len + 1,
                                vocab=tiny.vocab_size, predict=0.96, seed=seed)

    def batches(s):
        rng = np.random.default_rng(s)
        idx = rng.integers(0, len(stream.data), 32)
        toks = stream.data[idx].astype(np.int32)
        return {"tokens": toks[:, :-1], "labels": toks[:, 1:]}

    state, _ = train(model, batches, TrainConfig(steps=300, lr=3e-3), verbose=False)
    # production-scale decode profile (the paper's GPT-2 generative setup):
    # n_classes=0 restores the full-vocab token-serving head (the classifier
    # profiles serve 2-way sentiment) with ramps tied to the LM head; the
    # tiny model's K sites map to the same fractional depths of the full
    # stack, exactly like the CV launcher pairing a bench resnet with the
    # full resnet18 profile
    ns = len(model.sites)
    prof_cfg = get_config("gpt2-medium").replace(n_classes=0, ramp_style="tied")
    sites = [round((i + 1) * prof_cfg.n_layers / (ns + 1)) - 1 for i in range(ns)]
    prof = build_profile(prof_cfg, mode="decode", chips=1, sites=sites, charge_kv=True)
    assert ns == len(prof.sites), (ns, len(prof.sites))
    mbs = slots * 2
    qps = offered_decode_qps(prof, max_batch_size=mbs, tokens_per_request=decode_tokens, load=load)
    arr = maf_trace(n, mean_qps=qps, seed=seed)
    reqs = make_gen_requests(arr, n_tokens=decode_tokens, prompt_len=seq_len,
                             slo_ms=3 * prof.vanilla_time(1))
    gcfg = GenerativeConfig(max_batch_size=mbs, prefill_chunk=prefill_chunk,
                            preempt=preempt, steps_per_sync=steps_per_sync)

    def adm():
        return (AdmissionPolicy(AdmissionConfig(slack=admission_slack))
                if admission else None)

    base_eng = GenerativeEngine(prof, gcfg, admission=adm())
    mb = summarize_generative(base_eng.run(reqs), horizon_ms=base_eng.makespan_ms)
    mesh = None
    if tp > 1 or dp > 1:
        from repro.launch.mesh import make_serving_mesh
        mesh = make_serving_mesh(tp=tp, dp=dp)
    eng = build_generative_engine(
        model, state["params"], stream.data[:, :seq_len], prof, gcfg,
        ControllerConfig(max_slots=slots, ramp_budget_frac=budget, acc_constraint=acc),
        max_new_tokens=decode_tokens + 2, kv_block_size=kv_block_size,
        kv_blocks=kv_blocks, prefix_cache=prefix_cache, mesh=mesh,
        admission=adm())
    runner, ctl = eng.runner, eng.controller
    mo = summarize_generative(eng.run(reqs), horizon_ms=eng.makespan_ms)
    out = {
        "mode": "generative", "n": n, "decode_tokens": decode_tokens,
        "vanilla": mb, "apparate": mo,
        # single-token streams have no TPT samples (percentiles are 0.0):
        # there is no per-token win to report, not a NaN/crash
        "tpt_p50_win_pct": (
            100.0 * (mb["tpt_p50_ms"] - mo["tpt_p50_ms"]) / mb["tpt_p50_ms"]
            if mb["tpt_p50_ms"] > 0 else 0.0
        ),
        "engine": eng.stats(), "controller": dict(ctl.stats),
        "active_ramps": list(map(int, ctl.active)),
        "kv_cache": runner.kv_stats(),
    }
    if prefill_chunk:
        out["prefill_chunk"] = prefill_chunk
    if steps_per_sync > 1:
        out["steps_per_sync"] = steps_per_sync
    if preempt != "none":
        out["preempt"] = preempt
    if admission:
        out["admission"] = {"vanilla": base_eng.admission.stats(),
                            "apparate": eng.admission.stats()}
    if tp > 1 or dp > 1:
        out["mesh"] = {"tp": tp, "dp": dp}
    if pp > 1:
        out["pipeline"] = pipeline_escape_demo(
            tiny, state["params"], stream.data[:, :seq_len], pp,
            n_steps=decode_tokens)
    if verbose:
        print(json.dumps(out, indent=1, default=float))
    return out


def pipeline_escape_demo(tiny, params, prompts, pp, *, n_steps=16, thr=0.6):
    """Exit-gated pipeline decode window on a (stage,) mesh: decode the
    same window with thresholds OFF (every row rides all stages) and ON
    (rows clearing a boundary ramp's uncertainty bar skip all later
    stages); reports per-stage work counters for both."""
    import jax.numpy as jnp

    from repro.distributed.pipeline import pipeline_decode_window
    from repro.launch.mesh import make_serving_mesh

    # the paged-pool config is irrelevant here: the pipeline path reads
    # the contiguous slot cache, so rebuild a 'ref' view over same params
    model = build_model(tiny.replace(decode_attn="ref"))
    mesh = make_serving_mesh(pp=pp)
    B = max(pp, (min(8, len(prompts)) // pp) * pp)
    toks = jnp.asarray(prompts[:B], jnp.int32)
    seq_len = toks.shape[1]
    cache, outs = model.prefill(
        params, toks, cache_len=seq_len + n_steps + 1, moe_impl="dense")
    last = outs["final"]["label"].reshape(B, 1).astype(jnp.int32)
    pos = jnp.full((B,), seq_len, jnp.int32)
    # boundary ramps: the active sites sitting at each stage's last layer
    sites = list(model.sites)
    nsl = len(model.plan.period)
    bounds = [(s + 1) * (model.plan.n_periods // pp) * nsl - 1
              for s in range(pp - 1)]
    act = [sites.index(b) for b in bounds if b in sites]
    _, _, _, _, st_off = pipeline_decode_window(
        model, params, cache, last, pos, n_steps, mesh=mesh)
    kw = {}
    if act:
        kw = dict(active_sites=jnp.asarray(act, jnp.int32),
                  thresholds=jnp.full((len(act),), thr, jnp.float32))
    _, _, exit_rec, alive, st_on = pipeline_decode_window(
        model, params, cache, last, pos, n_steps, mesh=mesh, **kw)
    return {
        "stages": pp, "batch": B, "n_steps": n_steps, "threshold": thr,
        "boundary_sites": act,
        "stage_steps_no_exit": list(map(int, st_off)),
        "stage_steps_exit": list(map(int, st_on)),
        "rows_exited": int(B - int(alive.sum())),
        "exits_recorded": int((exit_rec >= 0).sum()),
        "later_stage_work_saved_pct": (
            100.0 * (1.0 - float(st_on[1:].sum()) / float(st_off[1:].sum()))
            if pp > 1 and float(st_off[1:].sum()) else 0.0),
    }


@contextlib.contextmanager
def profiled(profile_dir):
    """Record the program's spans and a profiler trace into
    ``profile_dir`` while the block runs (nothing where it is None)."""
    if profile_dir is None:
        yield
        return
    import jax

    tracing.enable()
    jax.profiler.start_trace(profile_dir)
    try:
        yield
    finally:
        jax.profiler.stop_trace()
        tracing.disable()


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--mode", default="classify", choices=["classify", "generative"])
    ap.add_argument("--domain", default="cv", choices=["cv", "nlp"])
    ap.add_argument("--n", type=int, default=None)
    ap.add_argument("--decode-tokens", type=int, default=16)
    ap.add_argument("--kv-block-size", type=int, default=0,
                    help="generative: >0 pages the decode KV cache into "
                         "blocks of this many tokens (0 = contiguous rows)")
    ap.add_argument("--kv-blocks", type=int, default=None,
                    help="generative: total paged KV pool blocks "
                         "(default: auto-size to full slot capacity)")
    ap.add_argument("--prefill-chunk", type=int, default=0,
                    help="generative: >0 splits each prompt's prefill into "
                         "chunks of this many tokens, co-scheduled with "
                         "in-flight decode steps (0 = serial prefill)")
    ap.add_argument("--prefix-cache", action="store_true",
                    help="generative + paged: share cached prompt-prefix "
                         "blocks across slots (refcount + copy-on-write); "
                         "repeated prompts skip their prefill (TTFT ~ 0)")
    ap.add_argument("--preempt", default="none", choices=["none", "swap", "shed"],
                    help="generative + paged: pool-exhaustion reaction — "
                         "swap a victim's KV to host and readmit it later, "
                         "shed it outright, or propagate the error")
    ap.add_argument("--steps-per-sync", type=int, default=1,
                    help="generative: decode steps per controller sync; "
                         ">1 fuses them into one on-device while_loop "
                         "window with device-side exit decisions (stale "
                         "thresholds between syncs, records replayed at "
                         "the boundary)")
    ap.add_argument("--tp", type=int, default=1,
                    help="generative: tensor-parallel degree — decode "
                         "through ShardedDecodeRunner on a (data, model) "
                         "mesh with per-device KV shards (needs tp*dp "
                         "backend devices; bit-identical to --tp 1)")
    ap.add_argument("--dp", type=int, default=1,
                    help="generative: data-parallel degree of the decode "
                         "mesh (contiguous KV only)")
    ap.add_argument("--pp", type=int, default=1,
                    help="generative: >1 adds an exit-gated pipeline "
                         "decode window demo over this many stages on a "
                         "(stage,) mesh (reports per-stage work saved)")
    ap.add_argument("--mesh-shape", default=None, metavar="DPxTP",
                    help="generative: '<dp>x<tp>' shorthand that "
                         "overrides --dp/--tp (e.g. '1x4', '2x2')")
    ap.add_argument("--runtime-preset", default="none",
                    choices=["none"] + sorted(PRESETS),
                    help="apply an XLA/allocator env preset before the "
                         "run (see repro.launch.tuning; flags already "
                         "exported in the environment win)")
    ap.add_argument("--admission", action="store_true",
                    help="enable the SLO-aware admission policy: drop "
                         "hopeless requests at admission; generative mode "
                         "also sheds doomed slots mid-stream")
    ap.add_argument("--admission-slack", type=float, default=1.0,
                    help="deadline slack multiplier for --admission")
    ap.add_argument("--policy", default="tfserve", choices=["tfserve", "clockwork"])
    ap.add_argument("--budget", type=float, default=0.02)
    ap.add_argument("--acc", type=float, default=0.99)
    ap.add_argument("--load", type=float, default=0.5)
    ap.add_argument("--workers", type=int, default=1)
    ap.add_argument("--dispatch", default="jsq",
                    choices=["round_robin", "jsq", "slo_aware"])
    ap.add_argument("--profile-dir", default=None, metavar="DIR",
                    help="record a profiler trace of the run into DIR, with "
                         "the engine, runner and controller spans beside "
                         "the device's operations")
    args = ap.parse_args(argv)
    # env presets must land before any jax backend work in the run
    apply_preset(args.runtime_preset)
    use_compile_cache()
    if args.mesh_shape:
        try:
            args.dp, args.tp = (int(x) for x in args.mesh_shape.lower().split("x"))
        except ValueError:
            ap.error("--mesh-shape must look like '<dp>x<tp>', e.g. 1x4")
    with profiled(args.profile_dir):
        if args.mode == "generative":
            serve_generative(args.n if args.n is not None else 48,
                             decode_tokens=args.decode_tokens,
                             budget=args.budget, acc=args.acc, load=args.load,
                             kv_block_size=args.kv_block_size, kv_blocks=args.kv_blocks,
                             prefill_chunk=args.prefill_chunk,
                             admission=args.admission,
                             admission_slack=args.admission_slack,
                             prefix_cache=args.prefix_cache,
                             preempt=args.preempt,
                             steps_per_sync=args.steps_per_sync,
                             tp=args.tp, dp=args.dp, pp=args.pp)
        else:
            serve(args.domain, args.n if args.n is not None else 3000,
                  policy=args.policy, budget=args.budget,
                  acc=args.acc, load=args.load, workers=args.workers,
                  dispatch=args.dispatch, admission=args.admission,
                  admission_slack=args.admission_slack)


if __name__ == "__main__":
    main()
