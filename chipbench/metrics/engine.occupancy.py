"""Live decode rows over slot-steps in the window (%). A slot left empty,
or a row the engine did not step, lowers it. Moves tokens_per_s."""


def read(r):
    h = r["host"]
    if not h["steps"]:
        return None
    return 100.0 * h["row_steps"] / (h["slots"] * h["steps"])
