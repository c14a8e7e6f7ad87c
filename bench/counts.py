"""Operation and byte counts of the model's required work, from the
configuration file's sizes alone (never from the program's plan).

Dense-equivalent: every projection counts ``2 * in * out`` FLOPs per token,
whatever plan implements it (a dense snapshot, a core chain or a kernel);
the embedding is a row gather and counts none.

Sizes are read under their published names.  A file that states them adds
to the dense GQA layer: latent attention (``kv_lora_rank``, the
``qk_nope``/``qk_rope``/``v_head_dim`` widths, ``q_lora_rank``), routed and
shared experts (the count under any name ``harness.expert_key`` knows;
``num_experts_per_tok``, ``n_shared_experts``, ``moe_intermediate_size``)
and leading dense layers (``first_k_dense_replace``).  A file's expert
count is the number held here, and ``published`` restates the model's (the
router's width).
"""

from __future__ import annotations

from bench.harness import expert_key


def _is_mla(conf: dict) -> bool:
    return conf.get("kv_lora_rank") is not None


def attention_weights(conf: dict) -> int:
    """Weights of one layer's attention projections."""
    d, h = conf["hidden_size"], conf["num_attention_heads"]
    if not _is_mla(conf):
        kv, dh = conf["num_key_value_heads"], conf["head_dim"]
        return d * h * dh * 2 + d * kv * dh * 2
    nope, rope, v = (conf["qk_nope_head_dim"], conf["qk_rope_head_dim"],
                     conf["v_head_dim"])
    rank, q_rank = conf["kv_lora_rank"], conf.get("q_lora_rank")
    q = (d * h * (nope + rope) if q_rank is None
         else d * q_rank + q_rank * h * (nope + rope))
    kv_a = d * (rank + rope)
    kv_b = rank * h * (nope + v)
    return q + kv_a + kv_b + h * v * d


def experts(conf: dict) -> tuple[int, int] | None:
    """(experts held here, the model's experts), or None for a dense
    model."""
    key = expert_key(conf)
    if key is None or not conf[key]:
        return None
    return conf[key], conf.get("published", {}).get(key, conf[key])


def layer_weights(conf: dict, index: int) -> float:
    """Weights one token passes through in layer ``index``: attention and
    a dense MLP, or attention, the router over every expert, its top-k
    experts' share held here (``top_k * held / published`` experts) and
    the shared experts."""
    d = conf["hidden_size"]
    ex = experts(conf)
    if ex is None or index < conf.get("first_k_dense_replace", 0):
        return attention_weights(conf) + 3 * d * conf["intermediate_size"]
    held, total = ex
    one = 3 * d * conf.get("moe_intermediate_size",
                           conf["intermediate_size"])
    routed = one * conf["num_experts_per_tok"] * held / total
    shared = one * conf.get("n_shared_experts", 0)
    return attention_weights(conf) + d * total + routed + shared


def head_weights(conf: dict) -> int:
    """The output head's weights: every row of the vocabulary held here."""
    return conf["hidden_size"] * conf["vocab_size"]


def dense_weights(conf: dict) -> float:
    """Weights one token passes through: every layer's projections (its
    share of the experts) and the head (embedding excluded)."""
    return sum(layer_weights(conf, i)
               for i in range(conf["num_hidden_layers"])) + head_weights(conf)


def attention_flops(conf: dict, query_key_pairs: int) -> int:
    """Forward FLOPs of scores and weighted values over all layers, for
    ``query_key_pairs`` (query, key) pairs: ``2 * H * (Dqk + Dv)`` each
    (``4 * H * Dh`` where query, key and value share ``head_dim``)."""
    h = conf["num_attention_heads"]
    if _is_mla(conf):
        width = (conf["qk_nope_head_dim"] + conf["qk_rope_head_dim"]
                 + conf["v_head_dim"])
    else:
        width = 2 * conf["head_dim"]
    return 2 * h * width * query_key_pairs * conf["num_hidden_layers"]


def kv_bytes(conf: dict, positions: int, itemsize: int = 2) -> int:
    """Bytes of K and V over all layers for ``positions`` cached positions."""
    return (2 * conf["num_key_value_heads"] * conf["head_dim"] * itemsize
            * positions * conf["num_hidden_layers"])


def itemsize(conf: dict) -> int:
    return {"bfloat16": 2, "float16": 2, "float32": 4}[conf["dtype"]]
