"""Model FLOP/s utilisation of the whole step, in %: the model's training
FLOPs per token (counted from shapes, bench/work/dense_lm_train_flops.py)
times the tokens per second of the traced window, over chips times the
chip's bf16 peak (bench/peaks.json)."""


def read(r):
    if r.trace is None or r.work.get("unit") != "step" \
            or "bf16_flops_per_s" not in r.peaks:
        return None
    spans = r.trace.spans("bench.step")
    t0, t1 = spans[0][0], spans[-1][1]
    rate = r.work["tokens_per_step"] * len(spans) / ((t1 - t0) / 1e9)
    return 100.0 * r.work["flops_per_token"] * rate / (
        r.device["count"] * r.peaks["bf16_flops_per_s"])
