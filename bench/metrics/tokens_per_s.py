"""All tokens of all steps completed in the window, over the window's
length (host clock; each step ends when its loss reaches the host)."""


def read(r):
    if r.work.get("unit") != "step":
        return None
    return r.work["tokens_per_step"] * len(r.unit_s) / r.window_s
