"""Host time per sync window spent in the engine itself, outside every
call into the runner and the controller (ms). Moves tpot_p50_ms."""


def read(r):
    h = r["host"]
    if not h["windows"]:
        return None
    return 1e3 * (h["span_s"] - h["calls_s"]) / h["windows"]
