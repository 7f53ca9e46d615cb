"""Shapes and parameter count of the dense decoder block every
configuration here runs (MHA + RoPE + RMSNorm + SwiGLU, no biases,
output head tied to the embedding), kept here so that no later PR can
move the yardstick; checked against model.init in tests/benchmark."""

from __future__ import annotations


def model_dims(config: dict) -> dict:
    """The sizes the arithmetic needs, from a configuration file's
    published (Hugging Face) keys."""
    d_model = int(config["hidden_size"])
    n_heads = int(config["num_attention_heads"])
    return {"d_model": d_model, "n_heads": n_heads,
            "d_head": d_model // n_heads,
            "d_ff": int(config["intermediate_size"]),
            "n_layers": int(config["num_hidden_layers"]),
            "vocab": int(config["vocab_size"])}


def param_shapes(dims: dict) -> dict:
    """The parameter tree's shapes, named as the served/trained model
    names them: kernels are [in, out]."""
    d, ff = dims["d_model"], dims["d_ff"]
    features = dims["n_heads"] * dims["d_head"]
    tree = {"embed": {"embedding": (dims["vocab"], d)},
            "final_norm": {"scale": (d,)}}
    for i in range(dims["n_layers"]):
        tree[f"layer_{i}"] = {
            "attn_norm": {"scale": (d,)},
            "mlp_norm": {"scale": (d,)},
            "attn": {"q_proj": {"kernel": (d, features)},
                     "k_proj": {"kernel": (d, features)},
                     "v_proj": {"kernel": (d, features)},
                     "o_proj": {"kernel": (features, d)}},
            "mlp": {"gate_proj": {"kernel": (d, ff)},
                    "up_proj": {"kernel": (d, ff)},
                    "down_proj": {"kernel": (ff, d)}}}
    return tree


def param_count(dims: dict) -> int:
    total = 0

    def walk(node):
        nonlocal total
        if isinstance(node, tuple):
            size = 1
            for n in node:
                size *= n
            total += size
        else:
            for child in node.values():
                walk(child)

    walk(param_shapes(dims))
    return total
