"""The secure aggregation kernels' share of their roofline, in %.

The least time one party's share_gen + reconstruct need, the HBM bytes
the algorithm must move (bench/work/secure_agg_bytes.py: 8D + 8mD per
step) over the chip's HBM bandwidth (bench/peaks.json), divided by the
kernels' device time per step.  HBM-bound by this count."""

PATTERN = r"share_gen|reconstruct"


def read(r):
    if r.trace is None:
        return None
    spans = r.trace.spans("bench.step")
    s = r.trace.op_s(PATTERN, spans[0][0], spans[-1][1]) / len(spans)
    if s <= 0 or "hbm_bytes_per_s" not in r.peaks:
        return None
    need = r.work["secure_agg_bytes_per_party_step"] / r.peaks[
        "hbm_bytes_per_s"]
    return 100.0 * need / s
