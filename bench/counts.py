"""Operation and byte counts of the model's required work, from the
configuration file's sizes alone (never from the program's plan).

Dense-equivalent: every projection counts ``2 * in * out`` FLOPs per token,
whatever plan implements it (a dense snapshot, a core chain or a kernel);
the embedding is a row gather and counts none.
"""

from __future__ import annotations


def dense_weights(conf: dict) -> int:
    """Weights of every projection and the head (embedding excluded)."""
    d, h, kv, dh, ff = (conf["hidden_size"], conf["num_attention_heads"],
                        conf["num_key_value_heads"], conf["head_dim"],
                        conf["intermediate_size"])
    per_layer = d * h * dh * 2 + d * kv * dh * 2 + 3 * d * ff
    return per_layer * conf["num_hidden_layers"] + d * conf["vocab_size"]


def attention_flops(conf: dict, query_key_pairs: int) -> int:
    """Forward FLOPs of scores and weighted values over all layers, for
    ``query_key_pairs`` (query, key) pairs: ``4 * H * Dh`` each."""
    return (4 * conf["num_attention_heads"] * conf["head_dim"]
            * query_key_pairs * conf["num_hidden_layers"])


def kv_bytes(conf: dict, positions: int, itemsize: int = 2) -> int:
    """Bytes of K and V over all layers for ``positions`` cached positions."""
    return (2 * conf["num_key_value_heads"] * conf["head_dim"] * itemsize
            * positions * conf["num_hidden_layers"])


def itemsize(conf: dict) -> int:
    return {"bfloat16": 2, "float16": 2, "float32": 4}[conf["dtype"]]
