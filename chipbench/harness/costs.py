"""Peaks of each chip, and the operations and bytes each kernel needs,
computed from shapes. A whole decode step's operations depend on the
architecture and are counted in ``arch/<architecture>.py``.

``PEAKS`` is keyed by ``jax.Device.device_kind``. Source: Google Cloud
documentation, "TPU v5e" (197 TFLOP/s bf16, 16 GB of HBM at 819 GB/s,
1,600 Gbit/s of inter-chip interconnect = 4 links of 50 GB/s). A kind
that is not listed is an error, never a default.

Counts are what the algorithm needs, not what an implementation happens
to move: attention reads the keys and values of the positions a row
attends to (not whole padded blocks), a head reads its weights once per
call. Operands are bf16 (2 bytes); a multiply-add is 2 operations.
"""
from __future__ import annotations

from typing import Dict

PEAKS = {
    "TPU v5 lite": {
        "flops_bf16": 197e12,
        "hbm_bytes": 16e9,
        "hbm_bw": 819e9,
        "ici_bw_per_link": 50e9,
    },
}

BYTES = 2  # bf16


def peaks(device_kind: str) -> dict:
    if device_kind not in PEAKS:
        raise KeyError(f"no published peaks for device kind {device_kind!r}; "
                       f"known: {sorted(PEAKS)}")
    return PEAKS[device_kind]


def paged_attention(g: Dict[str, int], ctx_row_steps: int, row_steps: int):
    """One layer's single-token attention over the cache, summed over
    ``row_steps`` rows whose attended positions add up to
    ``ctx_row_steps``: (operations, bytes). Reads K and V of every
    attended position (kv heads x head size), the query and writes the
    output (heads x head size)."""
    H, K, hd = g["H"], g["K"], g["hd"]
    flops = 4 * ctx_row_steps * H * hd  # q.k and p.v
    nbytes = BYTES * (2 * ctx_row_steps * K * hd + 2 * row_steps * H * hd)
    return flops, nbytes


def head(g: Dict[str, int], rows: int):
    """One full-vocabulary head over ``rows`` hidden states: the (d, V)
    weight read once, the rows read, and a few numbers per row written."""
    d, V = g["d"], g["V"]
    return 2 * rows * d * V, BYTES * (d * V + rows * d)

