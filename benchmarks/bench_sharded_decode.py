"""Sharded mesh-decode benchmark (gate rows for CI).

Measures what the `(data, model)` mesh actually buys on 4 devices: in
this process where the backend has them (a TPU host), else on forced
host devices (`--xla_force_host_platform_device_count=4`) in a CPU
subprocess — the parent benchmark process already initialized its
single-device backend.

Three claims, all gated:

* **Bit-identity** — `ShardedDecodeRunner` at tp=2 and tp=4 must stream
  back the exact records (labels, uncertainties, finals, exit sites) of
  the single-device batched runner across sync windows: the tiled
  all_gather combine is a pure concatenation, so sharding is a placement
  change, not a numerics change.
* **Per-device KV scaling** — every paged-pool leaf shards its head
  axis over `model`, so per-device peak KV bytes must be
  ≤ single-device bytes / tp + one block of slack (it is exact for the
  head counts here).
* **Pipeline escapes** — `pipeline_decode_window` with a near-1.0
  threshold at the stage-boundary ramp must show later stages doing
  strictly less row-steps than stage 0 at the same dispatch count
  (1 windowed dispatch either way): exited rows never enter later
  stages.

The us/token trend across tp is snapshotted, not gated — host-device
collectives on one core model communication structure, not speed.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import time

_ROOT = os.path.join(os.path.dirname(__file__), "..")
_SRC = os.path.join(_ROOT, "src")

N_STEPS = 16
N_ROWS = 3


def _measure() -> dict:
    """Run every measurement on this process's first 4 devices."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh

    from repro.configs import get_tiny
    from repro.distributed.pipeline import pipeline_decode_window
    from repro.models import build_model
    from repro.models.transformer import LM
    from repro.serving import DecodeRunner, ShardedDecodeRunner

    cfg = get_tiny("qwen2-1.5b").replace(n_layers=4, vocab_size=128,
                                         n_kv_heads=4, decode_attn="paged")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(5))
    prompts = np.random.default_rng(6).integers(0, 128, (8, 12)).astype(np.int32)
    kw = dict(max_new_tokens=N_STEPS + 2, max_slots=N_ROWS, kv_block_size=4)
    act = list(range(min(2, len(model.sites))))
    thr = np.zeros(len(act), np.float32)  # strict <: never exits -> full windows

    out = {"tp": {}}
    ref = None
    for tp in (1, 2, 4):
        r = (DecodeRunner(model, params, prompts, **kw) if tp == 1
             else ShardedDecodeRunner(model, params, prompts, tp=tp, **kw))
        for timed in (False, True):  # pass 1 compiles + records, pass 2 times
            for s in range(N_ROWS):
                r.start(s, s)
            recs, idx = [], 0
            t0 = time.perf_counter()
            while idx < N_STEPS:
                rec = r.step_multi(list(range(N_ROWS)), act, 4, thr)
                recs.append(rec)
                idx += rec[2].shape[0]
            wall = time.perf_counter() - t0
            stats = r.kv_stats()
            block_bytes = stats["cache_bytes"] / max(r._alloc.n_blocks, 1)
            for s in range(N_ROWS):
                r.free(s)
        flat = [np.concatenate([np.asarray(x[i]) for x in recs]) for i in range(4)]
        ident = ref is None or all(np.array_equal(a, b) for a, b in zip(ref, flat))
        if ref is None:
            ref = flat
        per_dev = stats.get("per_device_cache_bytes", stats["cache_bytes"])
        out["tp"][str(tp)] = {
            "us_per_token": wall / (N_STEPS * N_ROWS) * 1e6,
            "identical": bool(ident),
            "cache_bytes": float(stats["cache_bytes"]),
            "per_device_cache_bytes": float(per_dev),
            "kv_scaled": bool(per_dev <= stats["cache_bytes"] / tp + block_bytes),
        }

    # pipeline escapes: S=2 ring, thresholds off vs ~1.0 at the boundary ramp
    mp = LM(cfg.replace(decode_attn="ref"))
    pp = mp.init(jax.random.PRNGKey(5))
    B, S0, n = 4, 8, 8
    toks = jax.random.randint(jax.random.PRNGKey(1), (B, S0), 0, cfg.vocab_size)
    cache, outs = mp.prefill(pp, toks, cache_len=S0 + n + 1, moe_impl="dense")
    last = outs["final"]["label"].reshape(B, 1).astype(jnp.int32)
    pos = jnp.full((B,), S0, jnp.int32)
    S = 2
    sites = list(mp.sites)
    Lp, ns = mp.plan.n_periods // S, len(mp.plan.period)
    a = [sites.index(b) for b in [(s + 1) * Lp * ns - 1 for s in range(S - 1)]
         if b in sites]
    mesh = Mesh(np.array(jax.devices()[:S]), ("stage",))
    pres = {}
    for tag, th in (("no_exit", 0.0), ("exit", 0.9999)):
        _, _, xr, alive, steps = pipeline_decode_window(
            mp, pp, cache, last, pos, n, mesh=mesh,
            active_sites=jnp.asarray(a, jnp.int32),
            thresholds=jnp.asarray([th] * len(a), jnp.float32))
        pres[tag] = {"stage_steps": [int(v) for v in np.asarray(steps)],
                     "exits": int((np.asarray(xr) >= 0).sum()),
                     "dispatches": 1}
    out["pipeline"] = {"stages": S, "batch": B, "n_steps": n,
                       "boundary_sites": a, **pres}
    return out


def _measure_child() -> None:
    """Child-process entry: forced host devices exist only in a process
    whose backend starts with them."""
    print("JSON::" + json.dumps(_measure()))


def _run() -> dict:
    """Measure in this process when its backend has 4 devices (a TPU
    host); otherwise, on CPU only, in a child with 4 forced host devices.
    A child never needs an accelerator this process may already hold."""
    import jax

    if len(jax.devices()) >= 4:
        return _measure()
    if jax.devices()[0].platform != "cpu":
        raise RuntimeError(f"sharded decode needs 4 devices, have {len(jax.devices())}")
    env = dict(os.environ)
    env["XLA_FLAGS"] = (
        "--xla_force_host_platform_device_count=4 --xla_cpu_multi_thread_eigen=false"
    )
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = os.pathsep.join([_SRC, _ROOT])
    env["OMP_NUM_THREADS"] = "1"
    r = subprocess.run(
        [sys.executable, "-c",
         "from benchmarks.bench_sharded_decode import _measure_child; _measure_child()"],
        capture_output=True, text=True, timeout=560, env=env)
    if r.returncode != 0:
        raise RuntimeError(f"sharded subprocess failed:\n{r.stderr[-2000:]}")
    payload = next(l for l in r.stdout.splitlines() if l.startswith("JSON::"))
    return json.loads(payload[len("JSON::"):])


def bench_sharded_decode():
    from benchmarks.run import emit, snapshot

    out = _run()

    for tp, row in sorted(out["tp"].items(), key=lambda kv: int(kv[0])):
        ratio = row["per_device_cache_bytes"] / row["cache_bytes"]
        emit(f"sharded_decode_tp{tp}", row["us_per_token"],
             f"identical={row['identical']};per_device_kv_ratio={ratio:.3f}")

    pipe = out["pipeline"]
    no_exit, ex = pipe["no_exit"], pipe["exit"]
    # equal dispatch counts, strictly less later-stage row-steps with exits
    escape = (ex["dispatches"] == no_exit["dispatches"]
              and ex["exits"] > 0
              and ex["stage_steps"][-1] < ex["stage_steps"][0]
              and no_exit["stage_steps"][-1] == no_exit["stage_steps"][0])
    saved = 1.0 - ex["stage_steps"][-1] / max(no_exit["stage_steps"][-1], 1)
    emit("sharded_decode_pipeline", 0.0,
         f"stage_steps_no_exit={no_exit['stage_steps']};"
         f"stage_steps_exit={ex['stage_steps']};"
         f"later_stage_work_saved={saved:.2f}")

    ident2 = out["tp"]["2"]["identical"]
    ident4 = out["tp"]["4"]["identical"]
    kv_scaled = out["tp"]["2"]["kv_scaled"] and out["tp"]["4"]["kv_scaled"]
    emit("sharded_decode_gate", out["tp"]["2"]["us_per_token"],
         f"identical_tp2={ident2};identical_tp4={ident4};"
         f"kv_scaled={kv_scaled};pipeline_escape={escape}")

    snapshot("sharded_decode", {
        "identical_tp2": bool(ident2),
        "identical_tp4": bool(ident4),
        "kv_scaled": bool(kv_scaled),
        "pipeline_escape": bool(escape),
        "tp": out["tp"],
        "pipeline": pipe,
    })
