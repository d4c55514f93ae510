"""The fused ramp-head exit kernel's share of its roofline (%): every
call reads its full-vocabulary head once; the least time for the traced
decode steps' calls (one per gather slot and step) over the kernel's
device time. A tied head is replicated, so each chip reads all of it.
Moves tpot_p50_ms."""
from harness import costs
from harness.trace import is_exit_head, op_time


def read(r):
    t, h = r["trace"], r["trace_host"]
    if not t or not h or not h["steps"] or not h["active_ramps"]:
        return None
    secs = op_time(t, is_exit_head)
    if secs <= 0:
        return None
    g, pk = r["dims"], r["peaks"]
    calls = h["ramp_calls"]
    f, b = costs.head(g, h["slots"])
    least = calls * max(f / pk["flops_bf16"], b / pk["hbm_bw"])
    return 100.0 * least / secs
