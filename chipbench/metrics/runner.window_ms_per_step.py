"""Host time of runner.step_multi per decode step (ms): dispatch, the
device's window and the one sync. Moves tpot_p50_ms."""


def read(r):
    h = r["host"]
    if not h["steps"]:
        return None
    return 1e3 * h["window_s"] / h["steps"]
