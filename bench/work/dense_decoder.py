"""Useful FLOPs of a dense decoder step, from the model's sizes.

A step computes segments ``(tokens, start, logits)``: ``tokens`` new
positions of one sequence starting at position ``start``, and whether
that sequence's logits are taken.  Only real tokens count: two FLOPs per
weight per token for the projections and the MLP, the attention of each
token over its own context (``p + 1`` keys at position ``p``, for
``Q K^T`` and ``P V``), and the LM head only for rows whose logits are
taken.  Padding, idle slots and recomputation do not count.
"""


def flops(cfg: dict, segments) -> float:
    d = cfg["hidden_size"]
    hq, hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    dh = d // hq
    f, v, layers = cfg["intermediate_size"], cfg["vocab_size"], cfg["num_hidden_layers"]
    per_token = 2 * (d * (hq + 2 * hkv) * dh + hq * dh * d + 3 * d * f)
    total = 0.0
    for n, start, logits in segments:
        keys = n * start + n * (n + 1) // 2
        total += layers * (n * per_token + 4 * hq * dh * keys)
        if logits:
            total += 2 * d * v
    return total
