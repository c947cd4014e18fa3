"""Device time of the secure aggregation's kernels per step, in ms: the
share_gen and reconstruct kernels of every gradient leaf (device trace;
averaged over the chips, i.e. per party)."""

PATTERN = r"share_gen|reconstruct"


def read(r):
    if r.trace is None:
        return None
    spans = r.trace.spans("bench.step")
    s = r.trace.op_s(PATTERN, spans[0][0], spans[-1][1])
    return s / len(spans) * 1e3 if s > 0 else None
