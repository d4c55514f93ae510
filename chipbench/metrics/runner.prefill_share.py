"""Share of the window's host time spent in runner.start, the serial
prefills that stall every decoding row (%). Moves tpot_p90_ms."""


def read(r):
    h = r["host"]
    if not h["span_s"]:
        return None
    return 100.0 * h["prefill_s"] / h["span_s"]
