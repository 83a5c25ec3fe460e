"""Operations and bytes of a dense decoder, computed from its shapes.

``conf`` is a configuration file's dict (published key names).  Counts
are what the algorithm needs: one multiply-add is 2 operations, causal
attention counts only the keys a query may see, and nothing recomputed
for memory (rematerialisation) is counted.
"""
from __future__ import annotations


def dims(conf: dict) -> dict:
    d = conf["hidden_size"]
    h = conf["num_attention_heads"]
    return {"d": d, "h": h, "kv": conf["num_key_value_heads"], "hd": d // h,
            "f": conf["intermediate_size"], "v": conf["vocab_size"],
            "layers": conf["num_hidden_layers"]}


def matmul_params(conf: dict) -> int:
    """Weights that take part in a matmul per token (embedding lookup is a
    gather; the untied LM head is a matmul)."""
    m = dims(conf)
    attn = m["d"] * m["h"] * m["hd"] * 2 + m["d"] * m["kv"] * m["hd"] * 2
    mlp = 3 * m["d"] * m["f"]
    return m["layers"] * (attn + mlp) + m["v"] * m["d"]


def attn_flops_per_token(conf: dict, keys: int) -> int:
    """Scores and weighted values of one query over ``keys`` keys, all
    layers: 2 matmuls of (h * hd) per key, 2 operations each."""
    m = dims(conf)
    return m["layers"] * 4 * m["h"] * m["hd"] * keys


def forward_flops_token(conf: dict, keys: int) -> int:
    """Forward operations of one token that attends to ``keys`` keys."""
    return 2 * matmul_params(conf) + attn_flops_per_token(conf, keys)


def forward_flops_sequence(conf: dict, seq: int) -> int:
    """Forward operations of one causal sequence of ``seq`` tokens."""
    m = dims(conf)
    causal_keys = seq * (seq + 1) // 2
    return (2 * matmul_params(conf) * seq
            + m["layers"] * 4 * m["h"] * m["hd"] * causal_keys)


def train_flops_sequence(conf: dict, seq: int) -> int:
    """Forward and backward operations of one training sequence: the
    backward pass takes twice the forward's."""
    return 3 * forward_flops_sequence(conf, seq)
