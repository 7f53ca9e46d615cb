"""Model module ``dense_mha``: everything the harness knows about the
dense decoder block (multi-head attention + RoPE + RMSNorm + SwiGLU, no
biases, output head tied to the embedding) that the program's one
``TransformerLM`` runs. A configuration file names it under
``model_module``; the harness loads it by path (spec.load_model) and
goes through its four functions, so that another architecture is
another file here, a reference beside benchmark/reference/plain.py, and
a configuration file that names it.

The tree below IS the program's tree (checked against model.init in
tests/benchmark) and lives here, under ``paths``, so that no later PR
can move the yardstick."""

from __future__ import annotations

from benchmark.reference import plain


def dims(config: dict) -> dict:
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


def param_leaves(dims: dict) -> list:
    """[(path, shape, dtype rule, init rule)] for benchmark/weights.py:
    normal, std 1/sqrt(fan_in), for every kernel (fan-in its rows) and
    1/sqrt(hidden) for the embedding, in the served type; ones for the
    RMSNorm scales, kept float32 as the model declares them."""
    out = []

    def walk(node, path):
        if isinstance(node, tuple):
            if path[-1] == "scale":
                out.append((path, node, "float32", "ones"))
            else:
                fan_in = node[1] if path[-1] == "embedding" else node[0]
                out.append((path, node, "served", ("normal", fan_in)))
        else:
            for name in node:
                walk(node[name], path + (name,))

    walk(param_shapes(dims), ())
    return out


def program_model(config: dict, dims: dict, engine: dict,
                  kv_cache_dtype=None):
    """The model configuration object workloads/serve.build_engine
    takes, from the file's sizes and its ``engine`` section."""
    import jax.numpy as jnp
    from batch_shipyard_tpu.models import transformer as tfm
    return tfm.TransformerConfig(
        vocab_size=dims["vocab"], d_model=dims["d_model"],
        n_layers=dims["n_layers"], n_heads=dims["n_heads"],
        d_head=dims["d_head"], d_ff=dims["d_ff"],
        max_seq_len=engine["max_decode_len"],
        dtype=jnp.bfloat16, param_dtype=jnp.bfloat16,
        rope_theta=float(config["rope_theta"]),
        kv_cache_dtype=kv_cache_dtype)


def teacher_forced_logits(params, tokens, rows, config: dict,
                          dims: dict):
    """The float32 reference's logits at ``rows`` of one teacher-forced
    sequence (benchmark/reference/plain.py) -> [len(rows), vocab]."""
    return plain.teacher_forced_logits(
        params, tokens, rows, n_layers=dims["n_layers"],
        n_heads=dims["n_heads"], eps=float(config["rms_norm_eps"]),
        theta=float(config["rope_theta"]))
