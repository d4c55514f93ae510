"""Flash-decode Pallas kernel: single-token query against a long KV cache.

The dominant cost of decode attention is streaming the KV cache HBM→VMEM;
this kernel does one pass with online-softmax accumulation (grid:
(B·KH, S/bs), key tiles innermost sequential). `pos` masks cache slots
beyond the current length — a scalar (shared cache length) or an int32[B]
array of per-row lengths (batched slot caches, where continuous batching
leaves every row at a different decode position); it rides in by scalar
prefetch. GQA is folded into the block: each program holds the G query
heads that share one kv head, so a key tile is read once per group. The
online-softmax running max / sum live in VMEM scratch.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(pos_ref, q_ref, k_ref, v_ref, o_ref, m_sc, l_sc, *, bs, scale, n_s, S, KH):
    js = pl.program_id(1)
    pos = pos_ref[pl.program_id(0) // KH]
    q = q_ref[0].astype(jnp.float32)  # (G, hd)
    k = k_ref[0].astype(jnp.float32)  # (bs, hd)
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * scale  # (G, bs)
    kpos = js * bs + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    # (kpos < S) masks the padded tail tile when bs does not divide S —
    # those lanes hold unspecified pad values (NaN in interpret mode).
    # k is laundered through the `s` mask; v must be zeroed explicitly or
    # the masked 0-weight lanes still poison the p@v dot (0 * NaN).
    mask = (kpos <= pos) & (kpos < S)
    s = jnp.where(mask, s, NEG_INF)
    vpos = js * bs + jax.lax.broadcasted_iota(jnp.int32, k.shape, 0)
    v = jnp.where((vpos <= pos) & (vpos < S), v_ref[0].astype(jnp.float32), 0.0)

    @pl.when(js == 0)
    def _init():
        m_sc[...] = jnp.full(m_sc.shape, NEG_INF, jnp.float32)
        l_sc[...] = jnp.zeros(l_sc.shape, jnp.float32)
        o_ref[...] = jnp.zeros(o_ref.shape, jnp.float32)

    m_old = m_sc[...]  # (G, 1)
    m_new = jnp.maximum(m_old, jnp.max(s, axis=-1, keepdims=True))
    alpha = jnp.exp(m_old - m_new)
    p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
    l_sc[...] = l_sc[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
    o_ref[0] = o_ref[0] * alpha + jnp.dot(p, v, preferred_element_type=jnp.float32)
    m_sc[...] = m_new

    @pl.when(js == n_s - 1)
    def _final():
        o_ref[0] = o_ref[0] / jnp.maximum(l_sc[...], 1e-30)


def decode_attention(
    q: jax.Array,  # (B, H, hd) single query token
    k: jax.Array,  # (B, KH, S, hd) cache
    v: jax.Array,
    pos,  # int32 scalar or (B,): cache length - 1 per row (attend to <= pos)
    *,
    block_s: int = 512,
    interpret: bool = False,
) -> jax.Array:
    B, H, hd = q.shape
    KH, S = k.shape[1], k.shape[2]
    G = H // KH
    # cache lengths are arbitrary prompt_len + max_new sums: a cache that
    # fits one tile is taken whole (a block dim equal to the array's is
    # always legal); a longer one walks (8, 128)-aligned tiles and the
    # non-dividing tail tile is padded and masked off in-kernel
    bs = S if S <= block_s else max(8, block_s - block_s % 8)
    n_s = (S + bs - 1) // bs
    scale = 1.0 / math.sqrt(hd)
    qf = q.reshape(B * KH, G, hd)
    kf = k.reshape(B * KH, S, hd)
    vf = v.reshape(B * KH, S, hd)
    # (B,) per-row position; a scalar broadcasts to every row
    pos_arr = jnp.broadcast_to(jnp.asarray(pos, jnp.int32).reshape(-1), (B,))

    def row_map(bk, js, pos_ref):
        return (bk, 0, 0)

    def kv_map(bk, js, pos_ref):
        return (bk, js, 0)

    kernel = functools.partial(_kernel, bs=bs, scale=scale, n_s=n_s, S=S, KH=KH)
    o = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,  # per-row positions
            grid=(B * KH, n_s),
            in_specs=[
                pl.BlockSpec((1, G, hd), row_map),
                pl.BlockSpec((1, bs, hd), kv_map),
                pl.BlockSpec((1, bs, hd), kv_map),
            ],
            out_specs=pl.BlockSpec((1, G, hd), row_map),
            scratch_shapes=[pltpu.VMEM((G, 1), jnp.float32),
                            pltpu.VMEM((G, 1), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((B * KH, G, hd), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(pos_arr, qf, kf, vf)
    return o.reshape(B, H, hd).astype(q.dtype)
