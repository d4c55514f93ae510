"""Paged flash-decode Pallas kernel: single-token query against a block
KV pool.

The cache is a global pool of fixed-size blocks ``(P, bs, KH, hd)`` plus a
per-row block table ``int32[B, nb]`` mapping virtual token position
``t`` to pool slot ``(table[b, t // bs], t % bs)``. The kernel walks the
block table per row — the table and per-row positions ride in as
scalar-prefetch operands so the KV BlockSpec index map can resolve
``table[b, j]`` before the tile DMA issues (the vLLM paged-attention
pattern). The partially-filled last block is masked the same way the
contiguous kernel masks its padded tail tile: ``kpos <= pos`` kills the
scores and ``v`` is zeroed under the mask so stale pool lanes cannot
poison the p@v dot.

Grid: ``(B, nb)``, one row per program, key blocks innermost. Each
program holds ALL H query heads and a whole ``(bs, KH, hd)`` pool block —
the block's last two dims equal the pool's, which is what the TPU's
(8, 128) tiling rule demands of a block over a ``(P, bs, KH, hd)`` array.
GQA is resolved in-kernel: every kv head ``kh`` scores all H queries and
a head-group mask keeps the G rows that belong to it (the extra KH-fold
score FLOPs are noise next to streaming the block). The online-softmax
running max / sum live in VMEM scratch.

Table entries past a row's allocated blocks must still be VALID pool
indices (the allocator keeps them at 0, the reserved trash block): they
are fully masked, but the index map dereferences them.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(tab_ref, pos_ref, q_ref, k_ref, v_ref, o_ref, m_sc, l_sc,
            *, bs, scale, nb, KH, G):
    js = pl.program_id(1)
    pos = pos_ref[pl.program_id(0)]
    q = q_ref[0].astype(jnp.float32)  # (H, hd)
    H = q.shape[0]
    kpos = js * bs + jax.lax.broadcasted_iota(jnp.int32, (H, bs), 1)
    # kpos <= pos masks both unwritten offsets of the partial last block
    # and whole unallocated blocks (their table entries point at the trash
    # block)
    mask = kpos <= pos
    group = jax.lax.broadcasted_iota(jnp.int32, (H, bs), 0) // G
    s = jnp.full((H, bs), NEG_INF, jnp.float32)
    for kh in range(KH):
        k = k_ref[0, :, kh, :].astype(jnp.float32)  # (bs, hd)
        skh = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale  # (H, bs)
        s = jnp.where(group == kh, skh, s)
    s = jnp.where(mask, s, NEG_INF)

    @pl.when(js == 0)
    def _init():
        m_sc[...] = jnp.full(m_sc.shape, NEG_INF, jnp.float32)
        l_sc[...] = jnp.zeros(l_sc.shape, jnp.float32)
        o_ref[...] = jnp.zeros(o_ref.shape, jnp.float32)

    m_old = m_sc[...]  # (H, 1)
    m_new = jnp.maximum(m_old, jnp.max(s, axis=-1, keepdims=True))
    alpha = jnp.exp(m_old - m_new)
    p = jnp.where(mask, jnp.exp(s - m_new), 0.0)  # (H, bs)
    l_sc[...] = l_sc[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
    # v is zeroed under the same mask, laid out along its rows, so stale
    # pool values can't poison the p@v dot (0 * NaN)
    vmask = js * bs + jax.lax.broadcasted_iota(jnp.int32, (bs, q.shape[1]), 0) <= pos
    pv = jnp.zeros(q.shape, jnp.float32)
    for kh in range(KH):
        v = jnp.where(vmask, v_ref[0, :, kh, :].astype(jnp.float32), 0.0)  # (bs, hd)
        pkh = jnp.where(group == kh, p, 0.0)
        pv = pv + jnp.dot(pkh, v, preferred_element_type=jnp.float32)
    o_ref[0] = o_ref[0] * alpha + pv
    m_sc[...] = m_new

    @pl.when(js == nb - 1)
    def _final():
        o_ref[0] = o_ref[0] / jnp.maximum(l_sc[...], 1e-30)


def paged_decode_attention(
    q: jax.Array,  # (B, H, hd) single query token per row
    k_pool: jax.Array,  # (P, bs, KH, hd) global block pool
    v_pool: jax.Array,
    block_table: jax.Array,  # int32 (B, nb): pool block id per virtual block
    pos,  # int32 (B,): cache length - 1 per row (attend to <= pos)
    *,
    interpret: bool = False,
) -> jax.Array:
    B, H, hd = q.shape
    P, bs, KH, _ = k_pool.shape
    nb = block_table.shape[1]
    scale = 1.0 / math.sqrt(hd)
    table = jnp.asarray(block_table, jnp.int32)
    pos_arr = jnp.broadcast_to(jnp.asarray(pos, jnp.int32).reshape(-1), (B,))

    def q_map(b, js, tab_ref, pos_ref):
        return (b, 0, 0)

    def kv_map(b, js, tab_ref, pos_ref):
        return (tab_ref[b, js], 0, 0, 0)

    kernel = functools.partial(_kernel, bs=bs, scale=scale, nb=nb, KH=KH,
                               G=H // KH)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,  # block table + per-row positions
        grid=(B, nb),
        in_specs=[
            pl.BlockSpec((1, H, hd), q_map),
            pl.BlockSpec((1, bs, KH, hd), kv_map),
            pl.BlockSpec((1, bs, KH, hd), kv_map),
        ],
        out_specs=pl.BlockSpec((1, H, hd), q_map),
        scratch_shapes=[pltpu.VMEM((H, 1), jnp.float32),
                        pltpu.VMEM((H, 1), jnp.float32)],
    )
    o = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, hd), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(table, pos_arr, q, k_pool, v_pool)
    return o.astype(q.dtype)
