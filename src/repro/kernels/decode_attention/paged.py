"""Paged flash-decode Pallas kernel: single-token query against a block
KV pool.

The cache is a global pool of fixed-size blocks ``(P, bs, KH, hd)`` plus a
per-row block table ``int32[B, nb]`` mapping virtual token position
``t`` to pool slot ``(table[b, t // bs], t % bs)``. The table and per-row
positions ride in as scalar-prefetch operands; the pools are passed whole
(``pl.ANY``, wherever XLA keeps them) and the kernel copies pages itself.

Grid: ``(B, ceil(nb / ppb))``, one row per program, compute blocks of
``ppb`` pages innermost. A row attends to ``ceil((pos + 1) / bs)`` live
pages (at most ``nb``). A compute block copies only the row's live pages,
one async copy of a whole ``(bs, KH, hd)`` page each into a VMEM buffer
``(ppb, bs, KH, hd)`` (the TPU compiler refuses a copy of one kv head's
rows out of a pool tiled over ``(KH, hd)``, and slices a page out at all
only where ``hd`` fills whole 128-lane tiles: ``ops.attend_decode_paged``
gives other pools the gathered reference). A block wholly past ``pos``
issues no copy and no compute. The copies are double-buffered across grid
steps: before computing a block the kernel starts the copies of the next
live block, the same row's next or the next row's first. Table entries of
dead pages are never read, so they may point anywhere.

GQA: q enters as ``(KH, G, hd)`` and each kv head scores its own G
queries with one ``(G, hd) x (hd, ppb * bs)`` dot. The online softmax and
the accumulation run in f32. Inside the last live page ``kpos <= pos``
kills the scores and ``v`` is zeroed under the mask, so stale pool lanes
and buffer slots that were not copied this block cannot poison the p@v
dot (0 * NaN).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
# tokens one grid step covers, and the VMEM the double-buffered K and V
# page buffers may take
_BLOCK_TOKENS = 256
_BUFFER_BYTES = 4 << 20


def pages_per_block(bs: int, nb: int, KH: int, hd: int, itemsize: int) -> int:
    """Pages a compute block covers: about ``_BLOCK_TOKENS`` tokens, no more
    than the table holds, and halved until two K and two V buffers fit
    ``_BUFFER_BYTES``."""
    ppb = max(1, min(nb, _BLOCK_TOKENS // bs))
    while ppb > 1 and 4 * ppb * bs * KH * hd * itemsize > _BUFFER_BYTES:
        ppb //= 2
    return ppb


def _kernel(tab_ref, pos_ref, q_ref, k_pool, v_pool, o_ref,
            k_buf, v_buf, sems, m_sc, l_sc, flags,
            *, bs, ppb, nb, scale):
    b, j = pl.program_id(0), pl.program_id(1)
    n_rows, n_blk = pl.num_programs(0), pl.num_programs(1)
    KH, G, hd = q_ref.shape[1:]
    T = ppb * bs

    def n_live(r):
        return jnp.clip(pos_ref[r] // bs + 1, 0, nb)

    def page_copies(r, blk, slot, op):
        """Start (or wait for) the copies of row ``r``'s live pages in
        compute block ``blk`` into buffer ``slot``."""
        live = n_live(r)
        for i in range(ppb):
            @pl.when(blk * ppb + i < live)
            def _():
                page = tab_ref[r, blk * ppb + i]
                for c, (pool, buf) in enumerate(((k_pool, k_buf), (v_pool, v_buf))):
                    cp = pltpu.make_async_copy(pool.at[page], buf.at[slot, i],
                                               sems.at[c, slot])
                    getattr(cp, op)()

    @pl.when((b == 0) & (j == 0))
    def _reset():
        flags[0] = 0  # buffer the current block lands in
        flags[1] = 0  # 1 when the previous step already started its copies

    @pl.when(j == 0)
    def _init():
        m_sc[...] = jnp.full(m_sc.shape, NEG_INF, jnp.float32)
        l_sc[...] = jnp.zeros(l_sc.shape, jnp.float32)
        o_ref[...] = jnp.zeros(o_ref.shape, jnp.float32)

    live = n_live(b)

    @pl.when(j * ppb < live)
    def _block():
        slot = flags[0]

        @pl.when(flags[1] == 0)
        def _():
            page_copies(b, j, slot, "start")

        same_row = (j + 1) * ppb < live
        nxt = jnp.minimum(b + 1, n_rows - 1)
        has_next = same_row | ((b + 1 < n_rows) & (n_live(nxt) > 0))

        @pl.when(has_next)
        def _():
            page_copies(jnp.where(same_row, b, nxt), jnp.where(same_row, j + 1, 0),
                        1 - slot, "start")

        flags[0] = 1 - slot
        flags[1] = has_next.astype(jnp.int32)
        page_copies(b, j, slot, "wait")

        # kpos <= last masks the unwritten offsets of the last live page
        # and every page of the block that was not copied
        last = jnp.minimum(pos_ref[b], live * bs - 1)
        mask = j * T + jax.lax.broadcasted_iota(jnp.int32, (G, T), 1) <= last
        vmask = j * T + jax.lax.broadcasted_iota(jnp.int32, (T, hd), 0) <= last
        q = q_ref[0].astype(jnp.float32)  # (KH, G, hd)
        for kh in range(KH):
            k = k_buf[slot, :, :, kh, :].reshape(T, hd).astype(jnp.float32)  # (T, hd)
            s = jax.lax.dot_general(
                q[kh], k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale  # (G, T)
            s = jnp.where(mask, s, NEG_INF)
            m_old = m_sc[kh]  # (G, 1)
            m_new = jnp.maximum(m_old, jnp.max(s, axis=-1, keepdims=True))
            alpha = jnp.exp(m_old - m_new)
            p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
            l_sc[kh] = l_sc[kh] * alpha + jnp.sum(p, axis=-1, keepdims=True)
            v = v_buf[slot, :, :, kh, :].reshape(T, hd).astype(jnp.float32)
            v = jnp.where(vmask, v, 0.0)
            o_ref[0, kh] = o_ref[0, kh] * alpha + jnp.dot(
                p, v, preferred_element_type=jnp.float32)
            m_sc[kh] = m_new

    @pl.when(j == n_blk - 1)
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
    if hd % 128 and not interpret:
        raise ValueError(f"the TPU compiler slices a page out of the pool only for a "
                         f"head size that is a multiple of 128, got {hd}")
    G = H // KH
    nb = block_table.shape[1]
    ppb = pages_per_block(bs, nb, KH, hd, k_pool.dtype.itemsize)
    scale = 1.0 / math.sqrt(hd)
    table = jnp.asarray(block_table, jnp.int32)
    pos_arr = jnp.broadcast_to(jnp.asarray(pos, jnp.int32).reshape(-1), (B,))

    def row_map(b, j, tab_ref, pos_ref):
        return (b, 0, 0, 0)

    kernel = functools.partial(_kernel, bs=bs, ppb=ppb, nb=nb, scale=scale)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,  # block table + per-row positions
        grid=(B, pl.cdiv(nb, ppb)),
        in_specs=[
            pl.BlockSpec((1, KH, G, hd), row_map),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((1, KH, G, hd), row_map),
        scratch_shapes=[
            pltpu.VMEM((2, ppb, bs, KH, hd), k_pool.dtype),
            pltpu.VMEM((2, ppb, bs, KH, hd), v_pool.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),  # (K or V, buffer)
            pltpu.VMEM((KH, G, 1), jnp.float32),
            pltpu.VMEM((KH, G, 1), jnp.float32),
            pltpu.SMEM((2,), jnp.int32),
        ],
    )
    o = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, KH, G, hd), jnp.float32),
        # a step may start the next row's copies: both axes run in order
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=pltpu.InterpretParams() if interpret else False,
    )(table, pos_arr, q.reshape(B, KH, G, hd), k_pool, v_pool)
    return o.reshape(B, H, hd).astype(q.dtype)
