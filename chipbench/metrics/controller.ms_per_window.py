"""Host time in controller.observe per sync window (ms): the replay of
every window step's records, threshold tuning and ramp adjustment.
Moves tpot_p50_ms."""


def read(r):
    h = r["host"]
    if not h["windows"]:
        return None
    return 1e3 * h["controller_s"] / h["windows"]
