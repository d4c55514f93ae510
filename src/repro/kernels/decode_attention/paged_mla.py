"""Paged flash-decode Pallas kernel for MLA latent attention.

MLA decodes against a COMPRESSED latent cache, not per-head k/v: the
pools hold one shared latent stream per layer — ``c (P, bs, r)`` (which
doubles as the value stream) and the rope key ``k_pe (P, bs, dr)``. With
the absorbed decode trick the query arrives already projected into
latent space (``q_lat = q_nope @ w_uk``), so the score is

    s[b, h, t] = (q_lat[b, h] . c[b, t] + q_pe[b, h] . k_pe[b, t]) * scale

and the context is the probability-weighted latent ``sum_t p_t c[b, t]``
— MQA-like: all H heads walk the same latent blocks, no GQA grouping, so
one program holds all H heads of a row and reads each latent block once.

The block walk mirrors ``paged.py`` (grid ``(B, nb)``): the per-row block
table and positions ride in as scalar-prefetch operands so the latent
BlockSpec index maps resolve ``table[b, j]`` before the tile DMA issues;
the online-softmax running max / sum live in VMEM scratch and the
division happens on the last block. ``kpos <= pos`` masks both the
partial last block and whole unallocated blocks (trash-block table
entries), and ``c`` is zeroed under the mask so stale pool lanes cannot
poison the p@c dot.

``scale`` must be supplied by the caller (1/sqrt(dn + dr) in MLA): it is
not derivable from the latent shapes.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(tab_ref, pos_ref, ql_ref, qp_ref, c_ref, kp_ref, o_ref, m_sc, l_sc,
            *, bs, scale, nb):
    js = pl.program_id(1)
    pos = pos_ref[pl.program_id(0)]
    ql = ql_ref[0].astype(jnp.float32)  # (H, r)
    qp = qp_ref[0].astype(jnp.float32)  # (H, dr)
    c = c_ref[0].astype(jnp.float32)  # (bs, r)
    kp = kp_ref[0].astype(jnp.float32)  # (bs, dr)
    nt = (((1,), (1,)), ((), ()))  # contract the feature dims: q @ k.T
    s = (
        jax.lax.dot_general(ql, c, nt, preferred_element_type=jnp.float32)
        + jax.lax.dot_general(qp, kp, nt, preferred_element_type=jnp.float32)
    ) * scale  # (H, bs)
    kpos = js * bs + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    mask = kpos <= pos
    s = jnp.where(mask, s, NEG_INF)
    cpos = js * bs + jax.lax.broadcasted_iota(jnp.int32, c.shape, 0)
    cv = jnp.where(cpos <= pos, c, 0.0)  # value stream IS the latent

    @pl.when(js == 0)
    def _init():
        m_sc[...] = jnp.full(m_sc.shape, NEG_INF, jnp.float32)
        l_sc[...] = jnp.zeros(l_sc.shape, jnp.float32)
        o_ref[...] = jnp.zeros(o_ref.shape, jnp.float32)

    m_old = m_sc[...]  # (H, 1)
    m_new = jnp.maximum(m_old, jnp.max(s, axis=-1, keepdims=True))
    alpha = jnp.exp(m_old - m_new)
    p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
    l_sc[...] = l_sc[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
    o_ref[0] = o_ref[0] * alpha + jnp.dot(p, cv, preferred_element_type=jnp.float32)
    m_sc[...] = m_new

    @pl.when(js == nb - 1)
    def _final():
        o_ref[0] = o_ref[0] / jnp.maximum(l_sc[...], 1e-30)


def paged_mla_decode_attention(
    q_lat: jax.Array,  # (B, H, r) absorbed query, latent space
    q_pe: jax.Array,  # (B, H, dr) rope query
    c_pool: jax.Array,  # (P, bs, r) latent block pool (keys AND values)
    kpe_pool: jax.Array,  # (P, bs, dr) shared rope-key block pool
    block_table: jax.Array,  # int32 (B, nb)
    pos,  # int32 (B,): attend to virtual positions <= pos
    *,
    scale: float,
    interpret: bool = False,
) -> jax.Array:
    B, H, r = q_lat.shape
    dr = q_pe.shape[-1]
    P, bs, _ = c_pool.shape
    nb = block_table.shape[1]
    table = jnp.asarray(block_table, jnp.int32)
    pos_arr = jnp.broadcast_to(jnp.asarray(pos, jnp.int32).reshape(-1), (B,))

    def q_map(b, js, tab_ref, pos_ref):
        return (b, 0, 0)

    def kv_map(b, js, tab_ref, pos_ref):
        return (tab_ref[b, js], 0, 0)

    kernel = functools.partial(_kernel, bs=bs, scale=scale, nb=nb)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,  # block table + per-row positions
        grid=(B, nb),
        in_specs=[
            pl.BlockSpec((1, H, r), q_map),
            pl.BlockSpec((1, H, dr), q_map),
            pl.BlockSpec((1, bs, r), kv_map),
            pl.BlockSpec((1, bs, dr), kv_map),
        ],
        out_specs=pl.BlockSpec((1, H, r), q_map),
        scratch_shapes=[pltpu.VMEM((H, 1), jnp.float32),
                        pltpu.VMEM((H, 1), jnp.float32)],
    )
    o = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, r), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(table, pos_arr, q_lat, q_pe, c_pool, kpe_pool)
    return o.astype(q_lat.dtype)
