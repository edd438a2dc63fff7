"""Operations and bytes that each measured program needs, from its shapes.

These count what the algorithm needs, not what the compiled program does:
a roofline share built on them cannot pass 100% unless the time leaves out
work.  Sizes come from the configuration file's keys.
"""
from __future__ import annotations

BF16 = 2
F32 = 4


def dense_layer_matmul_params(c: dict) -> int:
    """Weights one dense decoder layer multiplies by: q, k, v, o and the
    gated MLP."""
    d, h, kv, hd, f = (c["hidden_size"], c["num_attention_heads"],
                       c["num_key_value_heads"], c["head_dim"], c["intermediate_size"])
    return d * h * hd + 2 * d * kv * hd + h * hd * d + 3 * d * f


def dense_layer_params(c: dict) -> int:
    """All weights of one layer: the matmuls, q/k/v biases and two norms."""
    kv_bias = (c["num_attention_heads"] + 2 * c["num_key_value_heads"]) * c["head_dim"]
    return (dense_layer_matmul_params(c) + 2 * c["hidden_size"]
            + (kv_bias if c.get("qkv_bias") else 0))


def dense_decode_step(c: dict, batch: int, live: int) -> tuple[float, float]:
    """(operations, bytes) of one decode step: ``batch`` sequences, each
    attending over ``live`` cached positions.

    Operations: 2 per multiply-add of every matmul weight, the output head
    included, plus QK^T and PV over the live positions.  Bytes: every
    weight the step multiplies by, once, as stored (param dtype), the
    embedding rows it looks up when the head is a separate matrix, and the
    live K/V it reads and the one position it writes (compute dtype)."""
    L, d, v = c["num_hidden_layers"], c["hidden_size"], c["vocab_size"]
    h, kv, hd = c["num_attention_heads"], c["num_key_value_heads"], c["head_dim"]
    pbytes = F32 if c["param_dtype"] == "float32" else BF16
    mm = L * dense_layer_matmul_params(c) + d * v
    flops = 2 * batch * mm + L * 4 * batch * h * hd * live
    weights = L * dense_layer_params(c) + d + d * v        # layers, final norm, head
    lookup = 0 if c["tie_word_embeddings"] else batch * d   # embed rows gathered
    kv_bytes = L * 2 * batch * kv * hd * (live + 1) * BF16
    return float(flops), float((weights + lookup) * pbytes + kv_bytes)
