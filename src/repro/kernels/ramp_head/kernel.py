"""Fused ramp-head confidence kernel (the paper's per-ramp record, §3.2).

Computes, for pooled hidden states h (B, d) against a ramp/LM head
W (d, V): argmax label, max logit, logsumexp and Σ l·eˡ accumulators —
WITHOUT materializing the (B, V) logits in HBM. Vocab is tiled through
VMEM with an online (max, Σe, Σl·e, argmax) merge; this is the TPU-native
analogue of streaming the paper's ~1KB per-ramp records: O(V) compute,
O(1) memory.

Grid: (B/bb, V/bv) with the vocab dimension innermost (sequential
accumulation); batch tiles are parallel. All accumulators live in VMEM
output blocks whose index map ignores the vocab index. The vocab tile
shrinks for wide heads so the double-buffered ``(d, bv)`` weight tile
stays inside the TPU's scoped VMEM (``_vocab_tile``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


# VMEM the weight tile may take: two pipeline buffers plus, where h is
# wider than w, the tile's in-kernel upcast (v5e scoped VMEM is 16 MiB)
_W_TILE_VMEM = 12 * 2**20


def _vocab_tile(d: int, V: int, h_dtype, w_dtype, block_v: int) -> int:
    """Vocab tile: ``min(block_v, V)``, or, where its weight tile would
    overflow ``_W_TILE_VMEM``, the largest multiple of 128 dividing V that
    fits."""
    cd = jnp.promote_types(h_dtype, w_dtype)
    per_col = d * (2 * jnp.dtype(w_dtype).itemsize
                   + (cd.itemsize if cd != jnp.dtype(w_dtype) else 0))
    bv = min(block_v, V)
    if bv * per_col <= _W_TILE_VMEM:
        return bv
    fit = [c for c in range(128, bv, 128) if V % c == 0 and c * per_col <= _W_TILE_VMEM]
    if not fit:
        raise ValueError(f"no vocab tile of V={V} fits VMEM at d={d}")
    return fit[-1]


def _kernel(h_ref, w_ref, m_ref, s_ref, t_ref, idx_ref, *, bv: int, v_limit: int):
    j = pl.program_id(1)
    h = h_ref[...]
    w = w_ref[...]
    # operands meet in their common dtype: bf16 x bf16 products are exact
    # in the f32 accumulator, so a bf16 weight tile is never upcast
    cd = jnp.promote_types(h.dtype, w.dtype)
    logits = jnp.dot(
        h.astype(cd), w.astype(cd), preferred_element_type=jnp.float32
    )  # (bb, bv)
    bb = logits.shape[0]
    col = jax.lax.broadcasted_iota(jnp.int32, logits.shape, 1)
    # mask padded-vocab columns (vocab rounded up for even sharding)
    logits = jnp.where(col + j * bv < v_limit, logits, -1e30)
    tile_max = jnp.max(logits, axis=-1)  # (bb,)
    tile_arg = jnp.min(
        jnp.where(logits == tile_max[:, None], col, jnp.int32(bv)), axis=-1
    ) + j * bv
    e = jnp.exp(logits - tile_max[:, None])
    tile_s = jnp.sum(e, axis=-1)
    tile_t = jnp.sum(logits * e, axis=-1)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = tile_max
        s_ref[...] = tile_s
        t_ref[...] = tile_t
        idx_ref[...] = tile_arg

    @pl.when(j > 0)
    def _merge():
        m_old = m_ref[...]
        new_m = jnp.maximum(m_old, tile_max)
        a = jnp.exp(m_old - new_m)
        b = jnp.exp(tile_max - new_m)
        s_ref[...] = s_ref[...] * a + tile_s * b
        t_ref[...] = t_ref[...] * a + tile_t * b
        idx_ref[...] = jnp.where(tile_max > m_old, tile_arg, idx_ref[...])
        m_ref[...] = new_m


def _exit_kernel(h_ref, w_ref, thr_ref, m_ref, s_ref, t_ref, idx_ref, exit_ref,
                 *, bv: int, v_limit: int):
    """Fused ramp-head + uncertainty + threshold compare: the streaming
    stats kernel plus, once the last vocab tile has merged, an in-VMEM
    exit decision ``(1 − maxprob) < threshold`` per row (strict ``<``, so
    a zero threshold can never trigger — matching ``simulate_exits``).
    The per-row EXIT MASK is all that leaves the kernel beyond the stats;
    the host never has to compare uncertainties to decide an exit."""
    _kernel(h_ref, w_ref, m_ref, s_ref, t_ref, idx_ref, bv=bv, v_limit=v_limit)

    @pl.when(pl.program_id(1) == pl.num_programs(1) - 1)
    def _decide():
        # after the final merge s_ref holds the full softmax normalizer:
        # maxprob = 1/s, so uncertainty = 1 − 1/s — never materializes (B,V)
        unc = 1.0 - 1.0 / s_ref[...]
        exit_ref[...] = (unc < thr_ref[...]).astype(jnp.int32)


def ramp_head_stats(
    h: jax.Array,
    w: jax.Array,
    *,
    block_b: int = 8,
    block_v: int = 1024,
    interpret: bool = False,
    v_limit: int | None = None,
):
    """h: (B, d); w: (d, V). Returns (m, s, t, argmax):
    m = max logit, s = Σ e^{l−m}, t = Σ l·e^{l−m}, argmax (B,) int32.
    Columns >= v_limit (padded vocab) are masked to −inf."""
    B, d = h.shape
    V = w.shape[1]
    bb = min(block_b, B)
    bv = _vocab_tile(d, V, h.dtype, w.dtype, block_v)
    assert B % bb == 0 and V % bv == 0, (B, V, bb, bv)
    grid = (B // bb, V // bv)
    kernel = functools.partial(_kernel, bv=bv, v_limit=v_limit if v_limit is not None else V)
    m, s, t, idx = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bb, d), lambda i, j: (i, 0)),
            pl.BlockSpec((d, bv), lambda i, j: (0, j)),
        ],
        out_specs=[
            pl.BlockSpec((bb,), lambda i, j: (i,)),
            pl.BlockSpec((bb,), lambda i, j: (i,)),
            pl.BlockSpec((bb,), lambda i, j: (i,)),
            pl.BlockSpec((bb,), lambda i, j: (i,)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B,), jnp.float32),
            jax.ShapeDtypeStruct((B,), jnp.float32),
            jax.ShapeDtypeStruct((B,), jnp.float32),
            jax.ShapeDtypeStruct((B,), jnp.int32),
        ],
        interpret=interpret,
    )(h, w)
    return m, s, t, idx


def ramp_head_exit(
    h: jax.Array,
    w: jax.Array,
    thresholds: jax.Array,
    *,
    block_b: int = 8,
    block_v: int = 1024,
    interpret: bool = False,
    v_limit: int | None = None,
):
    """Fused exit variant: h (B, d), w (d, V), thresholds (B,) f32.
    Returns (m, s, t, argmax, exit_mask) — exit_mask (B,) int32 is 1 where
    ``(1 − maxprob) < threshold`` (strict: threshold 0 precludes exiting).
    One extra (B,)-sized output vs ``ramp_head_stats``; no extra HBM."""
    B, d = h.shape
    V = w.shape[1]
    bb = min(block_b, B)
    bv = _vocab_tile(d, V, h.dtype, w.dtype, block_v)
    assert B % bb == 0 and V % bv == 0, (B, V, bb, bv)
    grid = (B // bb, V // bv)
    kernel = functools.partial(
        _exit_kernel, bv=bv, v_limit=v_limit if v_limit is not None else V
    )
    m, s, t, idx, mask = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bb, d), lambda i, j: (i, 0)),
            pl.BlockSpec((d, bv), lambda i, j: (0, j)),
            pl.BlockSpec((bb,), lambda i, j: (i,)),
        ],
        out_specs=[
            pl.BlockSpec((bb,), lambda i, j: (i,)),
            pl.BlockSpec((bb,), lambda i, j: (i,)),
            pl.BlockSpec((bb,), lambda i, j: (i,)),
            pl.BlockSpec((bb,), lambda i, j: (i,)),
            pl.BlockSpec((bb,), lambda i, j: (i,)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B,), jnp.float32),
            jax.ShapeDtypeStruct((B,), jnp.float32),
            jax.ShapeDtypeStruct((B,), jnp.float32),
            jax.ShapeDtypeStruct((B,), jnp.int32),
            jax.ShapeDtypeStruct((B,), jnp.int32),
        ],
        interpret=interpret,
    )(h, w, thresholds.astype(jnp.float32))
    return m, s, t, idx, mask
