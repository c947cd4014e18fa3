"""Parameters of a dense decoder (Qwen2 layout), counted from its shapes.

Per layer: q/k/v/o projections, the q/k/v biases, the three SiLU-gated
MLP matrices and two RMSNorm scales; then the token embedding (once: the
tied output head shares it) and the final norm.
"""


def count(model: dict) -> int:
    d, f, v = model["hidden_size"], model["intermediate_size"], \
        model["vocab_size"]
    h, hkv = model["num_attention_heads"], model["num_key_value_heads"]
    dh = d // h
    attn = d * h * dh + 2 * d * hkv * dh + h * dh * d + h * dh + 2 * hkv * dh
    layer = attn + 3 * d * f + 2 * d
    head = 0 if model["tie_word_embeddings"] else v * d
    return model["num_hidden_layers"] * layer + v * d + head + d
