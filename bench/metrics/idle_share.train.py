"""Share of the traced window in which no operation ran on the device,
in % (device trace; averaged over the chips)."""


def read(r):
    if r.trace is None:
        return None
    t0, t1 = r.trace.window("bench." + r.work["unit"])
    return 100.0 * (1.0 - r.trace.busy_s(t0, t1) / ((t1 - t0) / 1e9))
