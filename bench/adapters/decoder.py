"""Adapter of the dense decoder family (``repro.models.transformer``):
the program's ``ModelConfig`` for a configuration file, and the mapping
between the reference's weights (``bench/reference/decoder.py``) and the
program's parameter tree."""
from __future__ import annotations

import dataclasses


def model_config(conf: dict):
    """The program's ``ModelConfig`` for a configuration file: the
    program's entry for the family, with every size of the file."""
    from repro.configs import get_config
    base = get_config(conf["program"]["arch"])
    dtype = {"bfloat16": "bfloat16", "float32": "float32"}[
        conf["torch_dtype"]]
    return dataclasses.replace(
        base, n_layers=conf["num_hidden_layers"],
        d_model=conf["hidden_size"], n_heads=conf["num_attention_heads"],
        n_kv_heads=conf["num_key_value_heads"],
        head_dim=conf["hidden_size"] // conf["num_attention_heads"],
        d_ff=conf["intermediate_size"], vocab_size=conf["vocab_size"],
        rope_theta=float(conf["rope_theta"]), norm_eps=conf["rms_norm_eps"],
        tie_embeddings=conf["tie_word_embeddings"],
        param_dtype=dtype, compute_dtype=dtype)


def params_from(weights):
    """Benchmark weights -> the program's parameter tree.  The program
    scales by (1 + gamma) in its RMSNorm, so gamma = weight - 1."""
    lay = weights["layers"]
    return {
        "embed": weights["embed"],
        "final_norm": weights["final_norm"] - 1.0,
        "lm_head": weights["lm_head"],
        "segments": [{
            "ln1": lay["ln1"] - 1.0, "ln2": lay["ln2"] - 1.0,
            "attn": {"wq": lay["wq"], "wk": lay["wk"], "wv": lay["wv"],
                     "wo": lay["wo"]},
            "mlp": {"wi": lay["w_up"], "wg": lay["w_gate"],
                    "wo": lay["w_down"]},
        }],
    }


_LAYER_NAMES = {"wi": "w_up", "wg": "w_gate", "wo": "w_down"}


def leaf_names(params) -> list:
    """Benchmark names (``weights_from`` naming, "/"-joined) of the
    program tree's leaves, in the program's leaf order."""
    import jax
    out = []
    for path, _ in jax.tree_util.tree_leaves_with_path(params):
        keys = [getattr(p, "key", getattr(p, "idx", None)) for p in path]
        if keys[0] != "segments":
            out.append(keys[0])
        elif keys[2] == "mlp":
            out.append("layers/" + _LAYER_NAMES[keys[3]])
        else:
            out.append("layers/" + keys[-1])
    return out
