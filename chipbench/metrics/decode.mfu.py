"""Decode's share of the chips' peak (%): the backbone's and the final
head's operations for every token decoded in the window (ramp heads and
prefills excluded), over the window's seconds and the chips' bf16 peak.
Moves tokens_per_s."""


def read(r):
    h = r["host"]
    if not h["row_steps"]:
        return None
    f = r["arch"].decode_flops(r["dims"], h["row_steps"], h["ctx_row_steps"])
    return 100.0 * f / r["seconds"] / (r["peaks"]["flops_bf16"] * r["chips"])
