"""The party engine's share of its round's roofline, in %.

The least time a round needs, the HBM bytes it must move
(bench/work/share_sum_bytes.py: 4lD + 8mD + 4D) over the chip's HBM
bandwidth (bench/peaks.json), divided by the device's busy time per
round: every op of the round (share_gen_batch, the relayouts of the
[l, m, chunk] stack, the fold, reconstruct), so that moving work
between ops cannot move it.  HBM-bound by this count."""


def read(r):
    if r.trace is None or "hbm_bytes_per_s" not in r.peaks:
        return None
    spans = r.trace.spans("bench.round")
    s = r.trace.busy_s(spans[0][0], spans[-1][1]) / len(spans)
    if s <= 0:
        return None
    need = r.work["share_sum_bytes_per_round"] / r.peaks["hbm_bytes_per_s"]
    return 100.0 * need / s
