"""Jitted wrappers for flash-decode (contiguous and paged layouts)."""
from __future__ import annotations

from functools import partial

import jax

from repro.kernels.decode_attention.kernel import decode_attention
from repro.kernels.decode_attention.paged import paged_decode_attention
from repro.kernels.decode_attention.paged_mla import paged_mla_decode_attention
from repro.kernels.decode_attention.ref import (
    decode_attention_ref,
    paged_decode_attention_ref,
    paged_mla_decode_attention_ref,
)


@partial(jax.jit, static_argnames=("use_kernel", "interpret"))
def attend_decode(q, k, v, pos, *, use_kernel=True, interpret=False):
    if use_kernel:
        return decode_attention(q, k, v, pos, interpret=interpret)
    return decode_attention_ref(q, k, v, pos)


@partial(jax.jit, static_argnames=("use_kernel", "interpret"))
def attend_decode_paged(q, k_pool, v_pool, block_table, pos, *,
                        use_kernel=True, interpret=False):
    # the kernel copies whole pages, which the TPU compiler can slice out
    # of the pool only where the head size fills whole 128-lane tiles;
    # other pools take the gathered reference
    if use_kernel and (interpret or q.shape[-1] % 128 == 0):
        return paged_decode_attention(q, k_pool, v_pool, block_table, pos,
                                      interpret=interpret)
    return paged_decode_attention_ref(q, k_pool, v_pool, block_table, pos)


@partial(jax.jit, static_argnames=("scale", "use_kernel", "interpret"))
def attend_decode_paged_mla(q_lat, q_pe, c_pool, kpe_pool, block_table, pos,
                            *, scale, use_kernel=True, interpret=False):
    if use_kernel:
        return paged_mla_decode_attention(
            q_lat, q_pe, c_pool, kpe_pool, block_table, pos,
            scale=scale, interpret=interpret,
        )
    return paged_mla_decode_attention_ref(
        q_lat, q_pe, c_pool, kpe_pool, block_table, pos, scale=scale
    )
