"""Training FLOPs per token of a dense decoder, from its shapes.

6 * N for the forward and backward passes through the N parameters (the
tied embedding counted once, as the output head's product), plus causal
attention: per layer, Q.K^T and P.V take 2 * H * Dh * ctx multiply-adds
each, over an average context of (S + 1) / 2 positions, times 3 for the
backward pass.  Recomputation does not count.
"""

from bench import harness


def flops_per_token(model: dict, seq: int) -> float:
    n = harness.load_work("dense_lm_params").count(model)
    h = model["num_attention_heads"]
    dh = model["hidden_size"] // h
    attn = 6 * model["num_hidden_layers"] * h * dh * (seq + 1)
    return float(6 * n + attn)
