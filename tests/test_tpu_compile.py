"""The served path's kernels and decode step, compiled for a described TPU
v5e at real widths.

Interpret mode runs a kernel's Python, not its Mosaic lowering: a
BlockSpec the chip's (8, 128) tiling rejects, or a program that does not
fit 16 GB, passes every interpret-mode test. These compiles target a
``v5e:2x2`` topology described by the installed TPU compiler; nothing
runs, so they need no chip. The topology is described inside a fixture
(never at import) and every test skips where it cannot be described.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config

QWEN2 = get_config("qwen2-1.5b")
B, BS = 8, 16


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep the cache off around them
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(one_chip, fn, *shapes):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip) for s, dt in shapes]
    return jax.jit(fn).lower(*args).compile()  # repro: allow[jit-cache-hygiene] — one compile per test


def _has_kernel(compiled):
    return "tpu_custom_call" in compiled.as_text()


# 2560: the served cell's 160 table blocks; 2000: 125 blocks, which compute
# blocks of 16 pages do not divide
@pytest.mark.parametrize("max_len", [160, 1024, 2560, 2000])
def test_paged_decode_attention_compiles(one_chip, max_len):
    from repro.kernels.decode_attention import paged_decode_attention

    H, KH, hd = QWEN2.n_heads, QWEN2.n_kv_heads, QWEN2.hd
    nb = max_len // BS
    pool = (B * nb + 1, BS, KH, hd)
    c = _compile(one_chip, paged_decode_attention,
                 ((B, H, hd), jnp.bfloat16), (pool, jnp.bfloat16),
                 (pool, jnp.bfloat16), ((B, nb), jnp.int32), ((B,), jnp.int32))
    assert _has_kernel(c)


@pytest.mark.parametrize("S", [130, 1024, 1000])
def test_contiguous_decode_attention_compiles(one_chip, S):
    """Cache lengths that are not multiples of 8 take the whole cache as one
    tile (S <= 512) or walk 512-row tiles with a masked tail."""
    from repro.kernels.decode_attention import decode_attention

    H, KH, hd = QWEN2.n_heads, QWEN2.n_kv_heads, QWEN2.hd
    c = _compile(one_chip, decode_attention,
                 ((B, H, hd), jnp.bfloat16), ((B, KH, S, hd), jnp.bfloat16),
                 ((B, KH, S, hd), jnp.bfloat16), ((B,), jnp.int32))
    assert _has_kernel(c)


@pytest.mark.parametrize("model", ["qwen2-1.5b", "qwen1.5-32b"])
@pytest.mark.parametrize("kind", ["exit", "stats"])
def test_ramp_head_compiles(one_chip, kind, model):
    """A full-vocabulary head; at d=5120 the default vocab tile's weight
    block would overflow scoped VMEM, so the kernel takes a narrower one."""
    from repro.kernels.ramp_head import ramp_head_exit, ramp_head_stats

    cfg = get_config(model)
    d, Vp, V = cfg.d_model, cfg.padded_vocab, cfg.vocab_size
    if kind == "exit":
        c = _compile(one_chip, lambda h, w, t: ramp_head_exit(h, w, t, v_limit=V),
                     ((B, d), jnp.bfloat16), ((d, Vp), jnp.bfloat16),
                     ((B,), jnp.float32))
    else:
        c = _compile(one_chip, lambda h, w: ramp_head_stats(h, w, v_limit=V),
                     ((B, d), jnp.bfloat16), ((d, Vp), jnp.bfloat16))
    assert _has_kernel(c)


def test_paged_mla_compiles(one_chip):
    from repro.kernels.decode_attention import paged_mla_decode_attention

    cfg = get_config("deepseek-v2-lite-16b")
    H, r, dr = cfg.n_heads, cfg.kv_lora_rank, cfg.qk_rope_dim
    nb = 1024 // BS
    P = B * nb + 1
    c = _compile(one_chip,
                 lambda ql, qp, cp, kp, t, pos: paged_mla_decode_attention(
                     ql, qp, cp, kp, t, pos, scale=0.07),
                 ((B, H, r), jnp.bfloat16), ((B, H, dr), jnp.bfloat16),
                 ((P, BS, r), jnp.bfloat16), ((P, BS, dr), jnp.bfloat16),
                 ((B, nb), jnp.int32), ((B,), jnp.int32))
    assert _has_kernel(c)


def test_served_decode_window_fits_one_chip(one_chip):
    """The whole qwen2-1.5b sync-window program the chip serves (paged
    Pallas attention, fused ramp_head_exit, 4 active ramps, 8 slots)
    compiles for one v5e and fits its 16 GB."""
    from repro.models import build_model
    from repro.models.common import is_info
    from repro.serving import DecodeRunner

    cfg = QWEN2.replace(decode_attn="paged-kernel", pallas_head="tpu")
    model = build_model(cfg)
    K, n_max = 4, 4
    r = DecodeRunner(model, None, np.zeros((1, 128), np.int32), max_new_tokens=32,
                     max_slots=K, n_slots=B, kv_block_size=BS)
    nb = r._max_blocks

    def abstract(schema):
        return jax.tree.map(
            lambda i: jax.ShapeDtypeStruct(i.shape, i.dtype, sharding=one_chip),
            schema, is_leaf=is_info)

    def a(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    c = r._decode_multi_fn_paged(n_max).lower(
        abstract(model.schema()), abstract(model.paged_cache_schema(B * nb + 1, BS)),
        a((B, 1), jnp.int32), a((B,), jnp.int32), a((B, nb), jnp.int32),
        a((K,), jnp.int32), a((K,), jnp.float32), a((), jnp.int32),
        a((B,), jnp.bool_)).compile()
    assert _has_kernel(c)
    mem = c.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             - mem.alias_size_in_bytes + mem.temp_size_in_bytes)
    assert total < 16e9, total
