"""Per-arch reduced-config smoke tests: one forward/train step on CPU,
shape checks, no NaNs; decode-vs-prefill consistency (KV-cache/SSM-state
correctness) for every family."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCH_IDS, get_bench, get_tiny
from repro.models import build_model

LM_ARCHS = [a for a in ARCH_IDS if a != "seamless-m4t-large-v2"]


def _batch(cfg, key, B=2, S=16):
    toks = jax.random.randint(key, (B, S), 0, cfg.vocab_size)
    batch = {"tokens": toks, "labels": toks}
    if cfg.family == "encdec":
        batch["frames"] = jax.random.normal(key, (B, 24, cfg.d_frontend)) * 0.1
    if cfg.cross_attn_every:
        batch["image_embeds"] = (
            jax.random.normal(key, (B, cfg.n_image_tokens, cfg.d_frontend)) * 0.1
        )
    return batch


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_arch_loss_step(arch):
    cfg = get_tiny(arch)
    m = build_model(cfg)
    params = m.init(jax.random.PRNGKey(0))
    batch = _batch(cfg, jax.random.PRNGKey(1))
    kw = {"moe_impl": "dense"} if cfg.family == "lm" else {}
    loss, mets = m.loss(params, batch, **kw)
    assert jnp.isfinite(loss), (arch, mets)
    assert float(loss) > 0
    # one gradient step leaves params finite
    grads = jax.grad(lambda p: m.loss(p, batch, **kw)[0])(params)
    gn = sum(float(jnp.sum(jnp.abs(g))) for g in jax.tree.leaves(grads))
    assert np.isfinite(gn) and gn > 0, arch


@pytest.mark.parametrize("arch", LM_ARCHS)
def test_arch_decode_matches_prefill(arch):
    cfg = get_tiny(arch)
    m = build_model(cfg)
    params = m.init(jax.random.PRNGKey(0))
    B, S = 2, 12
    toks = jax.random.randint(jax.random.PRNGKey(1), (B, S + 1), 0, cfg.vocab_size)
    kw = {}
    if cfg.cross_attn_every:
        kw["image_embeds"] = (
            jax.random.normal(jax.random.PRNGKey(2), (B, cfg.n_image_tokens, cfg.d_frontend)) * 0.1
        )
    active = jnp.arange(min(2, len(m.sites)), dtype=jnp.int32)
    cache, _ = m.prefill(
        params, toks[:, :S], cache_len=S + 4, active_sites=active, moe_impl="dense", **kw
    )
    _, outs_d = m.decode(
        params, cache, toks[:, S : S + 1], jnp.int32(S), active_sites=active, moe_impl="dense"
    )
    _, outs_ref = m.prefill(
        params, toks[:, : S + 1], cache_len=S + 4, active_sites=active, moe_impl="dense", **kw
    )
    np.testing.assert_allclose(
        np.asarray(outs_d["final"]["maxprob"]),
        np.asarray(outs_ref["final"]["maxprob"]),
        rtol=2e-2, atol=2e-2,
    )
    assert (
        np.asarray(outs_d["final"]["label"]) == np.asarray(outs_ref["final"]["label"])
    ).all(), arch


def test_encdec_decode_matches_prefill():
    cfg = get_tiny("seamless-m4t-large-v2")
    m = build_model(cfg)
    params = m.init(jax.random.PRNGKey(0))
    frames = jax.random.normal(jax.random.PRNGKey(1), (2, 24, cfg.d_frontend)) * 0.1
    toks = jax.random.randint(jax.random.PRNGKey(2), (2, 9), 0, cfg.vocab_size)
    active = jnp.arange(1, dtype=jnp.int32)
    cache, _ = m.prefill(params, frames, toks[:, :8], cache_len=12, active_sites=active)
    _, od = m.decode(params, cache, toks[:, 8:9], jnp.int32(8), active_sites=active)
    _, oref = m.prefill(params, frames, toks[:, :9], cache_len=12, active_sites=active)
    np.testing.assert_allclose(
        np.asarray(od["final"]["maxprob"]), np.asarray(oref["final"]["maxprob"]),
        rtol=2e-2, atol=2e-2,
    )


@pytest.mark.parametrize("arch", ["resnet18", "bert-base"])
def test_paper_models(arch):
    cfg = get_tiny(arch)
    m = build_model(cfg)
    params = m.init(jax.random.PRNGKey(0))
    if arch.startswith("resnet"):
        x = jax.random.normal(jax.random.PRNGKey(1), (4, cfg.img_size, cfg.img_size, 3))
        batch = {"images": x, "labels": jnp.asarray([0, 1, 2, 3]) % cfg.n_classes}
        outs = m.forward(params, x, active_sites=list(m.sites))
    else:
        x = jax.random.randint(jax.random.PRNGKey(1), (4, 16), 0, cfg.vocab_size)
        batch = {"tokens": x, "labels": jnp.asarray([0, 1, 0, 1])}
        outs = m.forward(params, x, active_sites=list(m.sites))
    assert outs["ramps"]["label"].shape == (len(m.sites), 4)
    assert np.isfinite(np.asarray(outs["ramps"]["maxprob"])).all()
    loss, _ = m.loss(params, batch)
    assert jnp.isfinite(loss)


def test_ramp_gather_no_recompile_semantics():
    """Dynamic active-site gather: changing the active set changes outputs
    without retracing (same jitted fn, different int32 array)."""
    cfg = get_tiny("qwen2-1.5b")
    m = build_model(cfg)
    params = m.init(jax.random.PRNGKey(0))
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 8), 0, cfg.vocab_size)
    traces = {"n": 0}

    # nested jit is the point here: the test counts retraces of this fn
    @jax.jit  # repro: allow[jit-cache-hygiene]
    def f(p, t, active):
        traces["n"] += 1
        _, outs = m.prefill(p, t, active_sites=active, with_cache=False, moe_impl="dense")
        return outs["ramps"]["label"]

    l1 = f(params, toks, jnp.asarray([0, 1], jnp.int32))
    l2 = f(params, toks, jnp.asarray([1, 1], jnp.int32))
    assert traces["n"] == 1, "ramp-set change must not retrace"
    assert (np.asarray(l1)[1] == np.asarray(l2)[1]).all()


def test_tied_ramp_style():
    cfg = get_tiny("qwen2-1.5b").replace(ramp_style="tied")
    m = build_model(cfg)
    params = m.init(jax.random.PRNGKey(0))
    assert "head" not in params["ramps"]
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 8), 0, cfg.vocab_size)
    _, outs = m.prefill(
        params, toks, active_sites=jnp.asarray([0, 1], jnp.int32),
        with_cache=False, moe_impl="dense",
    )
    assert np.isfinite(np.asarray(outs["ramps"]["maxprob"])).all()


def test_mla_absorbed_equivalence():
    """Latent-space MLA decode == naive materialized decode (math identity)."""
    cfg = get_tiny("deepseek-v2-lite-16b")
    m = build_model(cfg)
    params = m.init(jax.random.PRNGKey(0))
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 9), 0, cfg.vocab_size)
    act = jnp.arange(1, dtype=jnp.int32)
    cache, _ = m.prefill(params, toks[:, :8], cache_len=12, active_sites=act, moe_impl="dense")
    _, o_naive = m.decode(params, cache, toks[:, 8:9], jnp.int32(8), active_sites=act, moe_impl="dense")
    m2 = build_model(cfg.replace(mla_absorbed=True))
    _, o_abs = m2.decode(params, cache, toks[:, 8:9], jnp.int32(8), active_sites=act, moe_impl="dense")
    np.testing.assert_allclose(
        np.asarray(o_abs["final"]["maxprob"]), np.asarray(o_naive["final"]["maxprob"]),
        rtol=1e-4, atol=1e-4,
    )
    assert (
        np.asarray(o_abs["final"]["label"]) == np.asarray(o_naive["final"]["label"])
    ).all()


@pytest.mark.parametrize("init", ["normal:0.02", "ssm_a", "dt_bias", "ones"])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_param_init_matches_eager_draw(init, dtype):
    """A leaf drawn in one jitted program (narrow dtypes, so a whole-leaf
    f32 draw never exists on the device) holds the values of the eager
    f32 draw cast to its dtype."""
    from repro.models.common import ParamInfo, _draw

    info = ParamInfo(shape=(16, 24), dtype=dtype, init=init)
    key = jax.random.PRNGKey(3)
    got = info.initialize(key)
    want = _draw(key, (16, 24), jnp.dtype(dtype), init)
    assert got.dtype == jnp.dtype(dtype)
    np.testing.assert_array_equal(np.asarray(got, np.float32), np.asarray(want, np.float32))
