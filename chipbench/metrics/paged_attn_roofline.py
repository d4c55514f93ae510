"""The paged decode-attention kernel's share of its roofline (%): the
least time the chip could take for the attention the traced decode steps
needed (keys and values of every attended position, per layer; the
larger of the operation and byte bounds), over the kernel's device time.
Moves tpot_p50_ms."""
from harness import costs
from harness.trace import is_paged_attention, op_time


def read(r):
    t, h = r["trace"], r["trace_host"]
    if not t or not h or not h["row_steps"]:
        return None
    secs = op_time(t, is_paged_attention)
    if secs <= 0:
        return None
    g, pk, n = r["dims"], r["peaks"], r["chips"]
    f, b = costs.paged_attention(g, h["ctx_row_steps"], h["row_steps"])
    least = g["L"] * max(f / (pk["flops_bf16"] * n), b / (pk["hbm_bw"] * n))
    return 100.0 * least / secs
