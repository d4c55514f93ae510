"""Operation and byte counts against hand counts at the served shapes."""
import json
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

from harness import costs  # noqa: E402
from harness.registry import load_arch  # noqa: E402

ARCH = load_arch("qwen2")
G = ARCH.dims(json.loads((BENCH / "configs" / "qwen2-1.5b.json").read_text()))


def test_served_dims():
    assert (G["d"], G["H"], G["K"], G["hd"], G["F"], G["V"], G["L"], G["S"]) == (
        1536, 12, 2, 128, 8960, 151936, 28, 12)
    assert G["Vp"] == 153600


def test_paged_attention_one_row_1000_positions():
    # q.k and p.v: 2 * 2 * 1000 positions * 12 heads * 128
    # K and V of 1000 positions * 2 kv heads * 128 * 2 B, q and out 12 * 128 * 2 B
    assert costs.paged_attention(G, 1000, 1) == (6_144_000, 1_030_144)


def test_head_eight_rows():
    # 2 * 8 * 1536 * 151936; the 1536 x 151936 bf16 weight plus 8 rows read
    assert costs.head(G, 8) == (3_733_979_136, 466_771_968)


def test_layer_and_decode_token():
    # q/k/v 1536 * (12 + 2 * 2) * 128, o 1536 * 1536, MLP 3 * 1536 * 8960
    assert ARCH.layer_matmul_flops(G) == 93_585_408
    # 28 layers at a 1000-position context, then the output head
    assert ARCH.decode_flops(G, 1, 1000) == 3_259_170_816


def test_unknown_chip_is_an_error():
    with pytest.raises(KeyError):
        costs.peaks("TPU v9 imaginary")
    assert costs.peaks("TPU v5 lite")["hbm_bw"] == 819e9
