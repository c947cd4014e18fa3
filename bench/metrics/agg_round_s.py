"""The whole window over the rounds completed in it, in s (host clock;
each round ends when its mean is ready on the device)."""


def read(r):
    if r.work.get("unit") != "round" or not r.unit_s:
        return None
    return r.window_s / len(r.unit_s)
