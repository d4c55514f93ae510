#!/usr/bin/env python3
"""Smoke run of the served path on TPU chips.

    python chip_smoke.py               # one chip
    python chip_smoke.py --four-chips  # the sharded paths, one 2x2 host

One chip: the decode kernels at qwen2-1.5b's served shapes against their
jnp references, then ``qwen2-1.5b`` at its published widths (bf16, weights
drawn from ``--seed``) served through the same objects ``serve_generative``
wires together: the paged ``DecodeRunner`` with the Pallas decode-attention
kernel, the fused ``ramp_head_exit`` kernel, ``steps_per_sync=4`` sync
windows, an ``ApparateController`` and a ``GenerativeEngine``.

Four chips (``--four-chips``, and nothing else): ``ShardedDecodeRunner``
at tp=4 over ``qwen1.5-32b`` at its published widths with its depth cut
so that it exceeds one chip and fits four; the same widths cut to two
layers at tp=1 and tp=4 with their logits compared; and a pp=4
``pipeline_decode_window`` over ``qwen2-1.5b``.

The script runs on a TPU only: it exits non-zero, printing no result,
where JAX finds none. Every phase that fails raises. The last line of
stdout is ``{"ok": true, "device": {...}}`` with the device as JAX
reports it. The persistent compilation cache lives where
``JAX_COMPILATION_CACHE_DIR`` says, else in ``.jax_cache`` here.
"""
from __future__ import annotations

import argparse
import functools
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

# served shapes (one chip)
N_REQ, PROMPT, NEW_TOK = 16, 128, 32
SLOTS, BLOCK, SYNC, RAMP_SLOTS = 8, 16, 4, 4
# parity tolerances: bf16 operands, f32 accumulation in kernel and reference
ATTN_ATOL = ATTN_RTOL = 2e-2  # paged decode attention output (bf16)
HEAD_TOL = 1e-3  # ramp_head_exit max logit and logsumexp, absolute
TIE_GAP = 1e-3  # a label may differ only where the top-2 logit gap is below this
# four chips: qwen1.5-32b depth for the tp=4 runner (exceeds one chip,
# fits four).
BIG_LAYERS = 16
# tp=4 vs tp=1 logits: the same f32 weights run through differently tiled
# matmuls (the column-sharded ones are a quarter as wide). At the TPU's
# default precision every matmul rounds its f32 operands to bf16 (2**-9
# relative), at points XLA picks per program, so the two programs differ
# by bf16 noise that hides a real fault. At "highest" precision operands
# stay f32 and only the accumulation order differs (about 1e-6 relative).
# Logits must agree within LOGIT_RTOL of the largest |logit|, and labels
# on the rows whose top-2 gap is wider than twice that (pp=4 compares
# step 0's labels the same way).
LOGIT_RTOL = 1e-3

COMPILES = {"n": 0, "s": 0.0, "cache_hits": 0}


def log(tag, **kw):
    print(f"[{tag}] " + " ".join(f"{k}={v}" for k, v in kw.items()), flush=True)


def _on_duration(event, secs, **_):
    if event == "/jax/core/compile/backend_compile_duration":
        COMPILES["n"] += 1
        COMPILES["s"] += secs


def _on_event(event, **_):
    if event == "/jax/compilation_cache/cache_hits":
        COMPILES["cache_hits"] += 1


def spec(x):
    import jax

    return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=x.sharding)


def assert_kernel(fn, *args, what):
    """The compiled program holds a Mosaic kernel (not an interpreted or
    jnp fallback). A program that already ran is served from the jit
    cache, so this costs no compile."""
    import jax

    text = fn.lower(*jax.tree.map(spec, args)).compile().as_text()
    n = text.count("tpu_custom_call")
    log("program", what=what, tpu_custom_call=n)
    if not n:
        raise AssertionError(f"{what}: no tpu_custom_call in the compiled program")


def memory(devices):
    out = []
    for d in devices:
        st = d.memory_stats() or {}
        out.append({"id": d.id, "bytes_in_use": st.get("bytes_in_use"),
                    "peak_bytes_in_use": st.get("peak_bytes_in_use"),
                    "bytes_limit": st.get("bytes_limit")})
    return out


# ---------------------------------------------------------------------------
# one chip


def kernel_parity(seed, cfg):
    """Paged decode attention and ramp_head_exit at the served shapes,
    each against its jnp reference under full f32 matmul precision."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels.decode_attention import (
        paged_decode_attention,
        paged_decode_attention_ref,
    )
    from repro.kernels.ramp_head import ramp_head_exit, ramp_head_exit_ref

    B, H, KH, hd = SLOTS, cfg.n_heads, cfg.n_kv_heads, cfg.hd
    nb = -(-(PROMPT + NEW_TOK) // BLOCK)
    P = B * nb + 1
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    q = jax.random.normal(ks[0], (B, H, hd), jnp.bfloat16)
    kp = jax.random.normal(ks[1], (P, BLOCK, KH, hd), jnp.bfloat16)
    vp = jax.random.normal(ks[2], (P, BLOCK, KH, hd), jnp.bfloat16)
    rng = np.random.default_rng(seed)
    table = jnp.asarray(1 + rng.permutation(P - 1).reshape(B, nb), jnp.int32)
    pos = jnp.asarray(np.r_[0, nb * BLOCK - 1, rng.integers(0, nb * BLOCK, B - 2)],
                      jnp.int32)
    out = jax.jit(paged_decode_attention)(q, kp, vp, table, pos)
    with jax.default_matmul_precision("highest"):
        ref = jax.jit(paged_decode_attention_ref)(q, kp, vp, table, pos)
    o, r = np.asarray(out, np.float32), np.asarray(ref, np.float32)
    err = float(np.abs(o - r).max())
    ok = bool(np.isfinite(o).all() and np.allclose(o, r, atol=ATTN_ATOL, rtol=ATTN_RTOL))
    log("parity", kernel="paged_decode_attention", shape=list(q.shape),
        pool=list(kp.shape), max_abs_err=err, atol=ATTN_ATOL, rtol=ATTN_RTOL, ok=ok)
    if not ok:
        raise AssertionError("paged decode attention disagrees with its reference")

    d, Vp, V = cfg.d_model, cfg.padded_vocab, cfg.vocab_size
    h = jax.random.normal(ks[3], (B, d), jnp.bfloat16)
    w = (jax.random.normal(ks[4], (d, Vp), jnp.float32) * 0.02).astype(jnp.bfloat16)
    thr = jnp.asarray([0.0, 1.0] * (B // 2), jnp.float32)  # never / always
    km, ks_, _, kidx, kmask = jax.jit(
        functools.partial(ramp_head_exit, v_limit=V))(h, w, thr)
    with jax.default_matmul_precision("highest"):
        rm, rs, _, ridx, rmask = jax.jit(ramp_head_exit_ref)(h, w[:, :V], thr)
        top2 = jax.jit(lambda h, w: jax.lax.top_k(
            jnp.dot(h.astype(jnp.float32), w.astype(jnp.float32)), 2)[0])(h, w[:, :V])
    km, rm = np.asarray(km), np.asarray(rm)
    klse, rlse = km + np.log(np.asarray(ks_)), rm + np.log(np.asarray(rs))
    gap = np.asarray(top2[:, 0] - top2[:, 1])
    label_ok = (np.asarray(kidx) == np.asarray(ridx)) | (gap < TIE_GAP)
    m_err, lse_err = float(np.abs(km - rm).max()), float(np.abs(klse - rlse).max())
    ok = bool(m_err <= HEAD_TOL and lse_err <= HEAD_TOL and label_ok.all()
              and np.array_equal(np.asarray(kmask), np.asarray(rmask))
              and np.array_equal(np.asarray(kmask), np.asarray(thr) > 0))
    log("parity", kernel="ramp_head_exit", h=list(h.shape), w=list(w.shape),
        v_limit=V, max_logit_err=m_err, lse_err=lse_err, tol=HEAD_TOL,
        labels_equal=int((np.asarray(kidx) == np.asarray(ridx)).sum()), rows=B,
        tie_gap=TIE_GAP, exit_mask=np.asarray(kmask).tolist(), ok=ok)
    if not ok:
        raise AssertionError("ramp_head_exit disagrees with its reference")


def serve_phase(seed, cfg):
    """Serve N_REQ requests through the GenerativeEngine. The first half
    runs with every threshold at 0 (no exit can fire, full sync windows);
    the second with the deepest active ramp's threshold at the 75th
    percentile of the uncertainties the first half recorded there, so the
    device's exit mask fires."""
    import jax
    import numpy as np

    from repro.core import ControllerConfig, build_profile
    from repro.launch.serve import build_generative_engine
    from repro.models import build_model
    from repro.models.common import param_bytes
    from repro.serving import GenerativeConfig, make_gen_requests

    for v in (cfg.decode_attn, cfg.pallas_head):
        if "interpret" in v:
            raise AssertionError(f"interpret-mode kernel on the chip path: {v!r}")
    model = build_model(cfg)
    dev = jax.devices()[0]
    t0 = time.perf_counter()
    # one init program (each leaf's draw fused into its cast) rather than
    # one compile per distinct leaf
    params = jax.block_until_ready(jax.jit(model.init)(jax.random.PRNGKey(seed)))
    log("serve", model=cfg.name, n_layers=cfg.n_layers, d_model=cfg.d_model,
        padded_vocab=cfg.padded_vocab, ramp_sites=len(model.sites),
        param_bytes=param_bytes(model.schema()), init_s=time.perf_counter() - t0,
        decode_attn=cfg.decode_attn, pallas_head=cfg.pallas_head)
    prompts = np.random.default_rng(seed).integers(
        0, cfg.vocab_size, (N_REQ, PROMPT)).astype(np.int32)
    prof = build_profile(cfg, mode="decode", chips=1, charge_kv=True)
    # adaptation frozen: acc_constraint 0 never triggers threshold tuning
    # and no ramp adjustment falls inside the run, so the device decides
    # exits from exactly the thresholds set here
    ccfg = ControllerConfig(max_slots=RAMP_SLOTS, ramp_budget_frac=1.0,
                            acc_constraint=0.0, adjust_every=10**9)
    eng = build_generative_engine(
        model, params, prompts, prof,
        GenerativeConfig(max_batch_size=SLOTS, steps_per_sync=SYNC), ccfg,
        max_new_tokens=NEW_TOK, kv_block_size=BLOCK)
    runner, ctl = eng.runner, eng.controller
    act = sorted(ctl.active)
    if not act:
        raise AssertionError("controller activated no ramps")
    reqs = make_gen_requests(np.zeros(N_REQ), n_tokens=NEW_TOK, prompt_len=PROMPT,
                             slo_ms=1e9)
    half = N_REQ // 2
    resps, walls = [], []
    for i, part in enumerate((reqs[:half], reqs[half:])):
        if i == 1:
            unc = ctl.window.unc[: ctl.window.count, act[-1]]
            ctl.thresholds[act[-1]] = np.float32(np.percentile(unc[np.isfinite(unc)], 75))
        c0, w0 = COMPILES["n"], eng.n_windows
        t0 = time.perf_counter()
        out = eng.run(part)
        jax.block_until_ready(runner._cache)
        walls.append(time.perf_counter() - t0)
        exits = sum(int((np.asarray(r.exit_sites) >= 0).sum()) for r in out)
        log("serve", run=i, requests=len(part), thresholds=ctl.thresholds[act].tolist(),
            wall_s=walls[-1], windows=eng.n_windows - w0,
            compiles=COMPILES["n"] - c0, exits_fired=exits)
        resps += out
    n_tok = [len(r.tokens) for r in resps]
    exits = [int((np.asarray(r.exit_sites) >= 0).sum()) for r in resps]
    vocab_ok = all(0 <= t < cfg.vocab_size for r in resps for t in r.tokens)
    st = eng.stats()
    log("serve", requests=len(resps), tokens_per_request=sorted(set(n_tok)),
        tokens=sum(n_tok), exits_fired=sum(exits), active_ramps=act,
        dispatches=runner.dispatches, windows=eng.n_windows,
        serve_wall_s=sum(walls), kv=json.dumps(runner.kv_stats()))
    log("serve", modeled_ttft_tpt="(simulated clock, not measured)",
        modeled_busy_ms=st["busy_ms"], modeled_steps=st["steps"])
    if len(resps) != N_REQ or any(n != NEW_TOK for n in n_tok) or not vocab_ok:
        raise AssertionError(f"requests did not return all their tokens: {n_tok}")
    if not sum(exits[half:]) or sum(exits[:half]):
        raise AssertionError(f"exit mask: {sum(exits[:half])} exits at threshold 0, "
                             f"{sum(exits[half:])} with the threshold set")

    nb = runner._max_blocks
    assert_kernel(runner._decode_multi_fn_paged(SYNC), params, runner._cache,
                  jax.ShapeDtypeStruct((SLOTS, 1), np.int32),
                  jax.ShapeDtypeStruct((SLOTS,), np.int32),
                  jax.ShapeDtypeStruct((SLOTS, nb), np.int32),
                  jax.ShapeDtypeStruct((RAMP_SLOTS,), np.int32),
                  jax.ShapeDtypeStruct((RAMP_SLOTS,), np.float32),
                  jax.ShapeDtypeStruct((), np.int32),
                  jax.ShapeDtypeStruct((SLOTS,), np.bool_),
                  what="decode_multi (paged attention + ramp_head_exit)")
    assert_kernel(runner._prefill_fn_paged(), params, runner._cache,
                  jax.ShapeDtypeStruct((1, PROMPT), np.int32),
                  jax.ShapeDtypeStruct((-(-PROMPT // BLOCK),), np.int32),
                  jax.ShapeDtypeStruct((0,), np.int32),
                  what="prefill (ramp_head_stats)")
    mem = memory([dev])[0]
    log("memory", **mem)
    if mem["peak_bytes_in_use"] is None or mem["peak_bytes_in_use"] >= 16e9:
        raise AssertionError(f"peak device bytes {mem['peak_bytes_in_use']}")


def one_chip(seed):
    from repro.configs import get_config

    cfg = get_config("qwen2-1.5b").replace(decode_attn="paged-kernel",
                                           pallas_head="tpu")
    kernel_parity(seed, cfg)
    serve_phase(seed, cfg)


# ---------------------------------------------------------------------------
# four chips


def shard_like(mesh, specs):
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    return jax.tree.map(lambda sp: NamedSharding(mesh, sp), specs,
                        is_leaf=lambda x: isinstance(x, P))


def paged_from_contiguous(cache, bs):
    """A paged pool holding a contiguous prefill cache: row b's virtual
    block j sits at pool block 1 + b*nb + j (block 0 is the trash block).
    Every leaf is an attention k/v ``(L, B, S, KH, hd)``."""
    import jax
    import jax.numpy as jnp

    def leaf(x):
        L, B, S = x.shape[:3]
        blocks = x.reshape((L, B * (S // bs), bs) + x.shape[3:])
        return jnp.concatenate([jnp.zeros_like(blocks[:, :1]), blocks], axis=1)

    return jax.tree.map(leaf, cache)


def tp_logits_phase(seed, cfg, mesh):
    """One paged decode step of the same f32 weights and cache at tp=1
    and at tp=4: the final head's logits must agree within LOGIT_RTOL of
    the largest. The weights go through the host between the two, since
    one chip cannot hold both the whole model and its tp=4 shard."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.models import build_model
    from repro.models import layers as LY

    model = build_model(cfg)
    if model.plan.prefix or model.plan.suffix:
        raise AssertionError("expected a uniform attention stack")
    B, V = SLOTS, cfg.vocab_size
    nb = -(-(PROMPT + NEW_TOK) // BLOCK)
    params = jax.jit(model.init)(jax.random.PRNGKey(seed))
    toks = jnp.asarray(np.random.default_rng(seed).integers(0, V, (B, PROMPT)), jnp.int32)
    cache, outs = jax.jit(functools.partial(
        model.prefill, cache_len=nb * BLOCK, moe_impl="dense"))(params, toks)
    pool = {"blocks": paged_from_contiguous(cache["blocks"], BLOCK)}
    del cache
    last = outs["final"]["label"].reshape(B, 1).astype(jnp.int32)
    pos = jnp.full((B,), PROMPT, jnp.int32)
    tables = jnp.asarray(1 + np.arange(B * nb).reshape(B, nb), jnp.int32)
    dec1 = jax.jit(lambda p, c, t, po, tb: model.decode(
        p, c, t, po, block_tables=tb, moe_impl="dense", with_logits=True)[1])
    with jax.default_matmul_precision("highest"):
        l1 = np.asarray(dec1(params, pool, last, pos, tables)["final"]["logits"][:, :V])
    host = jax.device_get(params)
    for x in jax.tree.leaves(params):
        x.delete()
    del params
    axes = LY.TEST_AXES
    params = jax.device_put(host, shard_like(mesh, model.tp_param_specs(axes)))
    pool = jax.device_put(pool, shard_like(mesh, model.tp_cache_specs(pool, axes)))
    del host
    dec4 = jax.jit(lambda p, c, t, po, tb: model.decode_sharded(
        p, c, t, po, mesh=mesh, block_tables=tb, with_logits=True)[1])
    with jax.default_matmul_precision("highest"):
        l4 = np.asarray(dec4(params, pool, last, pos, tables)["final"]["logits"][:, :V])
    err = float(np.abs(l4 - l1).max())
    atol = LOGIT_RTOL * float(np.abs(l1).max())
    decided = decided_rows(l1, atol)
    same = l4.argmax(-1) == l1.argmax(-1)
    ok = bool(np.isfinite(l4).all() and err <= atol and same[decided].all())
    log("tp_logits", model=cfg.name, n_layers=cfg.n_layers, dtype=cfg.dtype, rows=B,
        vocab=V, max_abs_err=err, atol=atol, labels_equal=int(same.sum()),
        decided_rows=int(decided.sum()), logit_scale=float(np.abs(l1).max()), ok=ok)
    if not ok:
        raise AssertionError("tp=4 logits disagree with tp=1")


def decided_rows(logits, atol):
    """Rows whose top-2 logit gap is wider than 2 * atol."""
    import numpy as np

    top2 = np.sort(logits, axis=-1)[:, -2:]
    return (top2[:, 1] - top2[:, 0]) > 2 * atol


def tp_runner_phase(seed, cfg, mesh, one_chip_bytes):
    """ShardedDecodeRunner at tp=4 (paged, Pallas kernels) over a model
    that exceeds one chip: weights drawn straight into their shards,
    8 prompts prefilled, two sync windows decoded."""
    import jax
    import numpy as np

    from repro.models import build_model
    from repro.models import layers as LY
    from repro.models.common import param_bytes
    from repro.serving import ShardedDecodeRunner

    model = build_model(cfg)
    total = param_bytes(model.schema())
    shardings = shard_like(mesh, model.tp_param_specs(LY.TEST_AXES))
    t0 = time.perf_counter()
    params = jax.block_until_ready(
        jax.jit(model.init, out_shardings=shardings)(jax.random.PRNGKey(seed)))
    per_dev = {}
    for x in jax.tree.leaves(params):
        for sh in x.addressable_shards:
            per_dev[sh.device.id] = per_dev.get(sh.device.id, 0) + sh.data.nbytes
    log("tp_runner", model=cfg.name, n_layers=cfg.n_layers, ramp_style=cfg.ramp_style,
        param_bytes=total, param_bytes_per_device=per_dev,
        init_s=time.perf_counter() - t0)
    if total <= one_chip_bytes or max(per_dev.values()) >= one_chip_bytes:
        raise AssertionError("the cut model must exceed one chip and fit four")
    if len(per_dev) != 4:
        raise AssertionError(f"params live on {sorted(per_dev)}")
    prompts = np.random.default_rng(seed).integers(
        0, cfg.vocab_size, (SLOTS, PROMPT)).astype(np.int32)
    runner = ShardedDecodeRunner(model, params, prompts, mesh=mesh,
                                 max_new_tokens=2 * SYNC + 1, max_slots=2,
                                 n_slots=SLOTS, kv_block_size=BLOCK)
    t0 = time.perf_counter()
    first = [runner.start(s, s) for s in range(SLOTS)]
    act = [0, len(model.sites) - 1]
    thr = np.zeros(2, np.float32)
    fins = [runner.step_multi(list(range(SLOTS)), act, SYNC, thr)[2] for _ in range(2)]
    jax.block_until_ready(runner._cache)
    fins = np.concatenate(fins)
    mem = memory(jax.devices()[:4])
    log("tp_runner", tp=runner.tp, prompts=SLOTS, first_tokens=first,
        decoded=list(fins.shape), wall_s=time.perf_counter() - t0,
        dispatches=runner.dispatches, kv=json.dumps(runner.kv_stats()))
    log("tp_runner", per_device_memory=json.dumps(mem))
    if fins.shape != (2 * SYNC, SLOTS) or not ((0 <= fins) & (fins < cfg.vocab_size)).all():
        raise AssertionError(f"decoded tokens {fins}")
    if min(m["bytes_in_use"] for m in mem) < 0.5 * min(per_dev.values()):
        raise AssertionError("arrays are not spread over the four devices")


def pp_phase(seed, cfg, mesh):
    """pipeline_decode_window at pp=4: stage s holds periods
    [s*L/4, (s+1)*L/4) of the weights and the KV cache. Thresholds off,
    so every stage does every row-step; step 0's tokens are checked
    against plain single-program decode (f32 at "highest" precision, as
    for the tp logits) on the decided rows."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import PartitionSpec as P

    from repro.distributed.pipeline import pipeline_decode_window
    from repro.models import build_model
    from repro.models.common import is_info

    model = build_model(cfg)
    specs = jax.tree.map(lambda _: P(), model.schema(),
                         is_leaf=is_info)
    specs["blocks"] = jax.tree.map(
        lambda i: P("stage", *([None] * (len(i.shape) - 1))), model.schema()["blocks"],
        is_leaf=is_info)
    params = jax.jit(model.init, out_shardings=shard_like(mesh, specs))(
        jax.random.PRNGKey(seed))
    B, n = SLOTS, SYNC
    toks = jnp.asarray(np.random.default_rng(seed).integers(
        0, cfg.vocab_size, (B, PROMPT)), jnp.int32)
    cache, outs = jax.jit(functools.partial(
        model.prefill, cache_len=PROMPT + n + 1, moe_impl="dense"))(params, toks)
    last = outs["final"]["label"].reshape(B, 1).astype(jnp.int32)
    pos = jnp.full((B,), PROMPT, jnp.int32)
    with jax.default_matmul_precision("highest"):
        _, ref = jax.jit(lambda p, c, t, po: model.decode(
            p, c, t, po, moe_impl="dense", with_logits=True))(params, cache, last, pos)
        l1 = np.asarray(ref["final"]["logits"][:, :cfg.vocab_size])
        decided = decided_rows(l1, LOGIT_RTOL * float(np.abs(l1).max()))
        t0 = time.perf_counter()
        _, tok_rec, exit_rec, alive, steps = pipeline_decode_window(
            model, params, cache, last, pos, n, mesh=mesh)
    tok_rec = np.asarray(tok_rec)
    steps = np.asarray(steps).tolist()
    same0 = tok_rec[0] == l1.argmax(-1)
    log("pp_window", model=cfg.name, stages=4, periods_per_stage=model.plan.n_periods // 4,
        rows=B, n_steps=n, stage_steps=steps, exits=int((np.asarray(exit_rec) >= 0).sum()),
        step0_tokens_equal=int(same0.sum()), decided_rows=int(decided.sum()),
        wall_s=time.perf_counter() - t0,
        per_device_memory=json.dumps(memory(jax.devices()[:4])))
    if steps != [B * n] * 4 or not bool(np.asarray(alive).all()):
        raise AssertionError(f"pipeline window did not complete: {steps}")
    if not same0[decided].all():
        raise AssertionError("pipeline step 0 disagrees with plain decode")


def four_chips(seed):
    import gc

    from repro.configs import get_config
    from repro.core.profiles import peaks
    from repro.launch.mesh import make_serving_mesh

    import jax

    one_chip = peaks(jax.devices()[0].device_kind)["hbm_bytes"]
    base = get_config("qwen1.5-32b").replace(decode_attn="paged-kernel")
    # the TP path replicates ramp heads on every device: 12 untied
    # d x V heads at d=5120 are 18.9 GB, so the ramps share the LM head.
    # Two layers in f32 are 10.5 GB, which one chip holds for tp=1.
    tp_logits_phase(seed, base.replace(n_layers=2, ramp_style="tied", pallas_head="off",
                                       dtype="float32"),
                    make_serving_mesh(tp=4))
    gc.collect()
    cut = base.replace(n_layers=BIG_LAYERS, ramp_style="tied", pallas_head="tpu")
    log("cut", model=base.name, n_layers=f"{base.n_layers}->{cut.n_layers}",
        ramp_style=f"{base.ramp_style}->{cut.ramp_style}",
        widths="published (d_model, heads, kv_heads, d_ff, vocab)")
    tp_runner_phase(seed, cut, make_serving_mesh(tp=4), one_chip)
    gc.collect()
    pp_phase(seed, get_config("qwen2-1.5b").replace(decode_attn="ref", ramp_style="tied",
                                                    dtype="float32"),
             make_serving_mesh(pp=4))


# ---------------------------------------------------------------------------


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the tp=4 / pp=4 sharded paths on four chips")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    from repro.launch.compile_cache import use_compile_cache

    cache_dir = use_compile_cache()
    import jax

    from repro.core.profiles import peaks

    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"chip_smoke: no TPU found (JAX platform {devs[0].platform!r})",
              file=sys.stderr)
        return 1
    need = 4 if args.four_chips else 1
    if len(devs) < need:
        print(f"chip_smoke: needs {need} TPU devices, found {len(devs)}",
              file=sys.stderr)
        return 1
    pk = peaks(devs[0].device_kind)  # a kind without published peaks raises
    jax.monitoring.register_event_duration_secs_listener(_on_duration)
    jax.monitoring.register_event_listener(_on_event)
    log("device", platform=devs[0].platform, kind=repr(devs[0].device_kind),
        count=len(devs), peak_bf16_flops=pk["flops_bf16"], hbm_bw=pk["hbm_bw"],
        compile_cache=cache_dir)
    t0 = time.perf_counter()
    (four_chips if args.four_chips else one_chip)(args.seed)
    log("done", wall_s=time.perf_counter() - t0, compiles=COMPILES["n"],
        compile_s=COMPILES["s"], persistent_cache_hits=COMPILES["cache_hits"])
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
