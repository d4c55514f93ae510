"""Peak device memory in use on the fullest chip after the window (GB,
the runtime's peak_bytes_in_use). Moves tokens_per_s."""


def read(r):
    b = r["memory_peak_bytes"]
    return None if b is None else b / 1e9
