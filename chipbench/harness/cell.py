"""One run of one cell: set-up, the measured window, the check, the result.

Set-up: the weights drawn on the device from the seed, the served engine
built, every program the mix uses warmed (one wave per sync-window
length, every prompt length in each, with and without ramps), the
backlog sized from the step time the warm-up measured, and the engine run
until ``settle_completions`` requests have finished (in a traced run,
and then for ``trace_seconds`` under the profiler). The window opens at
the next sync window's dispatch and lasts ``seconds``; the run stops at
the first call after it. ``setup_s`` is from process start to the
window's opening.
"""
from __future__ import annotations

import math
import sys
import time
from typing import Optional, Sequence

import numpy as np

from harness import check, costs, served, stats, traffic, weights
from harness import trace as tracemod
from harness.registry import BENCH_DIR, load_arch, load_reference, read_metrics


class NoChip(RuntimeError):
    """No accelerator, or fewer chips than the cell asks for."""


def log(*parts):
    print(*parts, file=sys.stderr, flush=True)


def devices_for(chips: int, require_tpu: bool):
    import jax

    devs = jax.devices()
    if require_tpu and devs[0].platform != "tpu":
        raise NoChip(f"no TPU found (JAX platform {devs[0].platform!r})")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX finds {len(devs)}")
    return devs[:chips]


def memory_peak(devs) -> Optional[int]:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use") for d in devs]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def make_mesh(conf, devs):
    tp = int(conf["serving"].get("tp", 1))
    if tp == 1:
        return None
    from jax.sharding import Mesh

    return Mesh(np.asarray(devs[:tp]).reshape(1, tp), ("data", "model"))


def param_shardings(model, mesh):
    if mesh is None:
        return None
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.models import layers as LY

    return jax.tree.map(lambda sp: NamedSharding(mesh, sp),
                        model.tp_param_specs(LY.TEST_AXES),
                        is_leaf=lambda x: isinstance(x, P))


def draw_weights(conf, arch, seed, mesh):
    """The benchmark's weights, in the layout of the program's model."""
    import jax

    from repro.models import build_model

    g = arch.dims(conf)
    model = build_model(served.program_config(conf))
    weights.check_layout(arch.layout(conf), model.abstract())
    if tuple(model.sites) != weights.ramp_sites(g["L"]):
        raise ValueError(f"the program's ramp sites {model.sites} are not the "
                         f"benchmark's {weights.ramp_sites(g['L'])}")
    return jax.block_until_ready(
        arch.draw(conf, weights.key_from_seed(seed), param_shardings(model, mesh)))


def warm_up(inst, conf, g, mix, seed) -> Optional[float]:
    """Serve the warm-up waves with ramps, then without (the controller
    drops every ramp at times), and return the host time of one decode
    step of a full-length window."""
    s, V = conf["serving"], g["V"]
    waves = traffic.warmup_waves(mix, s["slots"])
    step_s = None
    for ramps in (True, False):
        for j, (pl, nt) in enumerate(waves):
            bl = traffic.make_backlog(mix, seed + (1000 if ramps else 2000) + j, len(pl), V)
            bl.prompt_len[:], bl.n_tokens[:] = pl, nt
            if not ramps:
                inst.eng.controller.active = []
            served.serve(inst, bl)
            w = inst.tl.view(served.WINDOW)
            if ramps and len(w) and nt[0] - 1 == s["steps_per_sync"]:
                i = w[-1]
                step_s = (inst.tl.t1[i] - inst.tl.t0[i]) / max(int(inst.tl.a[i]), 1)
    return step_s


def run_cell(cell, seed: int, seconds: float, trace: bool, *, t_process: float,
             require_tpu: bool = True, trace_dir: Optional[str] = None,
             device_kind: Optional[str] = None, bench_dir=BENCH_DIR,
             control: Sequence[str] = ()) -> dict:
    """One run. With ``control`` (precisions among ``int8``, ``fp8``) the
    result also carries each control's readings at the same positions
    (``control.py``; the benchmark's own runs never do)."""
    import jax

    conf, mix = cell.config, cell.traffic
    devs = devices_for(cell.chips, require_tpu)
    kind = device_kind or devs[0].device_kind
    pk = costs.peaks(kind)
    compiles = served.Compiles()
    arch = load_arch(conf["architecture"], bench_dir)
    g, s = arch.dims(conf), conf["serving"]
    width, max_new = max(mix["prompt_lengths"]), int(mix["output_tokens"]["max"])

    t = time.perf_counter()
    mesh = make_mesh(conf, devs)
    params = draw_weights(conf, arch, seed, mesh)
    init_s = time.perf_counter() - t
    eng = served.build(conf, params, prompt_width=width, max_new=max_new, mesh=mesh)
    inst = served.Instrumented(eng, served.Timeline(1 << 12), traced=trace)

    t = time.perf_counter()
    c0 = compiles.count()
    step_s = warm_up(inst, conf, g, mix, seed)
    warm_compiles = compiles.count() - c0
    inst.observe_controller(served.fresh_controller(conf, eng))
    warm_s = time.perf_counter() - t

    # the backlog: twice what the warm-up's step time says the window finishes
    mean_out = float(np.mean(traffic.output_lengths(mix)))
    rate = s["slots"] / (mean_out * (step_s or 0.01))  # requests/s
    settle = int(mix["settle_completions"])
    n = (s["slots"] + settle + int(math.ceil(mix["backlog_factor"] * rate * seconds))
         + int(mix["deck"]))
    backlog = traffic.make_backlog(mix, seed, n, g["V"])

    # -- the window --------------------------------------------------------
    tl = inst.tl = served.Timeline()
    # A traced run records its trace once the engine has settled, and
    # opens the window after it: the profiler's start and stop stay out
    # of the window's host numbers.
    tr = {"on": False, "done": not trace, "t0": None, "t1": None}
    trace_s = float(mix.get("trace_seconds", 4.0))

    def trace_hook():
        now = time.perf_counter()
        if not tr["done"] and not tr["on"] and inst.completed >= settle:
            jax.profiler.start_trace(trace_dir, profiler_options=tracemod.profile_options())
            tr["on"], tr["t0"] = True, time.perf_counter()
        elif tr["on"] and now >= tr["t0"] + trace_s:
            tr["t1"] = now
            jax.profiler.stop_trace()
            tr["on"], tr["done"] = False, True

    if trace:
        inst.on_call = trace_hook
        inst.gate = lambda: tr["done"]
    opened = {}

    def on_open():
        opened["compiles"], opened["names"] = compiles.count(), len(compiles.names)

    inst.on_open = on_open
    gc_pauses = served.GcPauses()
    t_settle = time.perf_counter()
    try:
        closed = served.serve(inst, backlog, seconds=seconds, settle=settle)
    finally:
        gc_pauses.close()
    if tr["on"]:
        jax.profiler.stop_trace()
    if not closed:
        raise RuntimeError(f"the backlog of {n} requests ran dry before the window closed")
    t_open, t_close = inst.t_open, inst.t_close
    c_window = compiles.count() - opened["compiles"]
    mem_peak = memory_peak(devs)

    # -- end-to-end numbers ---------------------------------------------------
    req = stats.requests(tl, backlog.n_tokens)
    n_tok = stats.tokens_between(tl, t_open, t_close)
    done, tpot = stats.tpot_ms(req, backlog.n_tokens, t_open, t_close)
    p50, _ = stats.percentile(tpot, 50)
    p90, beyond90 = stats.percentile(tpot, 90)
    setup_s = t_open - t_process
    e2e = {"tokens_per_s": n_tok / seconds, "tpot_p50_ms": p50, "tpot_p90_ms": p90,
           "setup_s": setup_s}
    host = stats.host_record(tl, req, backlog.prompt_len, t_open, t_close, s["slots"],
                             s["ramp_slots"])
    ttft = [1e3 * (req["first"][i] - t_settle) for i in done]
    log(f"[setup] init_s={init_s:.3f} warm_s={warm_s:.3f} warm_compiles={warm_compiles} "
        f"settle_s={t_open - t_settle:.3f} setup_s={setup_s:.3f} "
        f"compile_s={compiles.compile_s:.3f} persistent_cache_hits={compiles.cache_hits}")
    log(f"[window] seconds={seconds} compiles_in_window={c_window} tokens={n_tok} "
        f"finished={len(done)} beyond_p90={beyond90} backlog={n} "
        f"admitted={int((req['count'] > 0).sum())} windows={host['windows']} "
        f"steps={host['steps']} exits={host['exits']} "
        f"empty_active_windows={host['empty_active']} warm_step_ms={1e3 * (step_s or 0):.3f}")
    hg = stats.host_gaps(tl, t_open, t_close, gc_pauses.spans)
    log("[host] (total s, longest s, count) " + " ".join(f"{k}={v}" for k, v in hg.items()))
    log("[ramps] agreement with the final head by site: " + " ".join(
        f"{k}:{a / max(t, 1):.4f}/{t}" for k, (a, t) in sorted(host["agree"].items())))
    log(f"[queue] ttft_p50_ms={np.percentile(ttft, 50) if ttft else None} "
        f"ttft_max_ms={max(ttft) if ttft else None} (queue position in a backlog, not a latency)")
    if c_window:
        raise RuntimeError(f"{c_window} programs were compiled inside the window: "
                           f"{compiles.names[opened['names']:]}")

    # -- free the program's state, then the reference ---------------------------
    served.release_live_slots(inst)
    responses = {r.rid: r for r in inst.adapter.responses}
    for x in jax.tree.leaves(inst.runner._cache):
        x.delete()
    inst.runner._cache = None
    ck = conf["check"]
    items = check.sample(done, backlog.n_tokens, backlog.prompt_len, seed,
                         min_tokens=ck["min_tokens"], max_requests=ck["max_requests"])
    ref = load_reference(conf["architecture"], bench_dir).Reference(
        conf, params, weights.ramp_sites(g["L"]), seq_len=width + max_new, n_pos=max_new)
    t = time.perf_counter()
    per, per_ctl = [], {}
    for it in items:
        args = (ref, backlog.prompts[it, :int(backlog.prompt_len[it])],
                np.asarray(responses[it].final_tokens), check.ramp_records(tl, req, it))
        per.append(check.gaps(*args))
        for q in control:
            per_ctl.setdefault(q, []).append(check.gaps(*args, quant=q))
    read = check.readings(per)
    limits = ck["limits"]
    correct = check.verdict(read, limits) and len(items) > 0
    log(f"[check] requests={len(items)} "
        f"served_tokens={int(sum(backlog.n_tokens[i] for i in items))} "
        f"longest={int(max(backlog.n_tokens[i] for i in items)) if items else 0} "
        f"reference_s={time.perf_counter() - t:.3f} "
        + " ".join(f"{k}={v}" for k, v in read.items()))

    # -- the result ----------------------------------------------------------------
    device = {"platform": devs[0].platform, "kind": kind, "count": len(devs),
              "memory_peak_bytes": mem_peak}
    result = {"correct": bool(correct), "attempted": int(len(done)), "failed": 0}
    if trace:
        red = th = None
        path = tracemod.find_xplane(trace_dir)
        if path and tr["t1"]:
            red = tracemod.reduce(*tracemod.load(path))
            th = stats.host_record(tl, req, backlog.prompt_len, tr["t0"], tr["t1"],
                                   s["slots"], s["ramp_slots"])
        log(f"[trace] seconds={None if red is None else red['window_s']} "
            f"host_windows={None if th is None else th['windows']} "
            f"programs={None if red is None else red['program_s']}")
        record = {"arch": arch, "dims": g, "peaks": pk, "chips": cell.chips, "seconds": seconds,
                  "host": host, "memory_peak_bytes": mem_peak, "trace": red,
                  "trace_host": th}
        metrics = read_metrics([m["name"] for m in cell.per_layer], record, bench_dir)
        result["metrics"] = {m["name"]: {"value": metrics[m["name"]], "unit": m["unit"]}
                             for m in cell.per_layer if metrics[m["name"]] is not None}
        if red is not None:
            device["busy_s"], device["window_s"] = red["busy_s"], red["window_s"]
            result["breakdown"] = {"device_ops": [list(x) for x in red["device_ops"]],
                                   "idle_gaps": [list(x) for x in red["idle_gaps"]]}
    else:
        result["metrics"] = {m["name"]: {"value": e2e[m["name"]], "unit": m["unit"]}
                             for m in cell.end_to_end if e2e[m["name"]] is not None}
    result["device"] = device
    if control:
        result["readings"] = read
        result["control"] = {q: check.readings(v) for q, v in per_ctl.items()}
    result["compared"] = {k: {"value": read[k], "limit": v} for k, v in limits.items()}
    for k, v in result["compared"].items():
        log(f"[compared] {k}={v['value']} limit={v['limit']}")
    return result
