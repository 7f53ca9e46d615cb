"""Model module ``hybrid_ssm_moe``: everything the harness knows about
the hybrid stack of state-space (Mamba-2), attention (grouped-query,
no positional embedding) and routed-expert blocks, one mixer a block,
that the program's ``TransformerLM`` runs from a per-layer list of
kinds (``nemotron_h``). A configuration file names it under
``model_module``; the reference is benchmark/reference/
hybrid_ssm_moe_plain.py.

The configuration is ONE CHIP'S SHARE of a deployment: the file's
``n_routed_experts`` and ``vocab_size`` count what is held here, and
its ``share`` group says of how many (``experts_of``: the router's
width, which is not cut) and from where (``first_expert``). Program
and reference are handed the same share.

The tree below IS the program's tree (checked against model.init in
tests/benchmark) and lives here, under ``paths``, so that no later PR
can move the yardstick."""

from __future__ import annotations

from benchmark.reference import hybrid_ssm_moe_plain as plain

KINDS = {"M": "ssm", "*": "attn", "E": "experts"}


def dims(config: dict) -> dict:
    """The sizes the arithmetic needs, from a configuration file's
    published (Hugging Face) keys and its ``share``."""
    pattern = config["hybrid_override_pattern"]
    n_layers = int(config["num_hidden_layers"])
    if len(pattern) != n_layers or set(pattern) - set(KINDS):
        raise ValueError(f"hybrid_override_pattern {pattern!r} is not "
                         f"{n_layers} blocks of {sorted(KINDS)}")
    share = config["share"]
    out = {
        "d_model": int(config["hidden_size"]),
        "n_layers": n_layers, "pattern": pattern,
        "vocab": int(config["vocab_size"]),
        "eps": float(config["layer_norm_epsilon"]),
        # attention
        "n_heads": int(config["num_attention_heads"]),
        "n_kv_heads": int(config["num_key_value_heads"]),
        "d_head": int(config["head_dim"]),
        # state-space
        "ssm_heads": int(config["mamba_num_heads"]),
        "ssm_head_dim": int(config["mamba_head_dim"]),
        "ssm_groups": int(config["n_groups"]),
        "ssm_state": int(config["ssm_state_size"]),
        "conv_kernel": int(config["conv_kernel"]),
        "chunk": int(config["chunk_size"]),
        # experts: the router's width, the choices, what is held
        "n_router": int(share["experts_of"]),
        "top_k": int(config["num_experts_per_tok"]),
        "experts_held": int(config["n_routed_experts"]),
        "first_expert": int(share["first_expert"]),
        "d_expert": int(config["moe_intermediate_size"]),
        "d_shared": int(config["moe_shared_expert_intermediate_size"]),
        "scale": float(config["routed_scaling_factor"]),
        # the seeded weights' one free number (param_leaves)
        "a_log_std": float(config.get("seeded_weights", {}).get(
            "A_log_std", 0.0)),
    }
    out["d_inner"] = out["ssm_heads"] * out["ssm_head_dim"]
    out["conv_dim"] = out["d_inner"] + 2 * out["ssm_groups"] \
        * out["ssm_state"]
    out["n_kind"] = {kind: pattern.count(letter)
                     for letter, kind in KINDS.items()}
    # for kernels/decode_step.py: parameters by what a decode step has
    # to read of them, and the bytes a slot and a cached token hold
    # (weights, activations and K/V in 2 bytes; the state-space state
    # in 4, the convolution's tail in 2)
    d = out["d_model"]
    out["params"] = {
        "ssm": d * (2 * out["d_inner"] + 2 * out["ssm_groups"]
                    * out["ssm_state"] + out["ssm_heads"])
        + out["d_inner"] * d + out["conv_kernel"] * out["conv_dim"],
        "attn": 2 * d * out["n_heads"] * out["d_head"]
        + 2 * d * out["n_kv_heads"] * out["d_head"],
        "experts_always": d * out["n_router"] + 2 * d * out["d_shared"],
        "expert": 2 * d * out["d_expert"],
        "head": d * out["vocab"]}
    out["slot_state_bytes"] = out["n_kind"]["ssm"] * (
        4 * out["d_inner"] * out["ssm_state"]
        + 2 * (out["conv_kernel"] - 1) * out["conv_dim"])
    out["kv_bytes_per_token"] = out["n_kind"]["attn"] * 2 * 2 \
        * out["n_kv_heads"] * out["d_head"]
    return out


def decision_layers(config: dict, dims: dict) -> list:
    """The E blocks: each chooses top_k of the router's n_router."""
    return [(f"layer_{i}", dims["top_k"], dims["n_router"])
            for i, kind in enumerate(dims["pattern"]) if kind == "E"]


def param_leaves(dims: dict) -> list:
    """[(path, shape, dtype rule, init rule)] for benchmark/weights.py,
    paths as the program names its leaves. Kernels: normal, std
    1/sqrt(fan_in) (fan-in their rows; an expert stack's its middle
    axis; the depthwise convolution's its taps; the embedding's the
    hidden size), in the served type. Norm scales and D: ones;
    conv_bias, dt_bias and e_score_correction_bias: zeros; A_log
    normal with the standard deviation the file states
    (``seeded_weights.A_log_std``: 8 at the published widths), so that
    A = -exp(A_log) spreads over a block's heads from no decay at all
    to forgetting at once and about half of them remember over a whole
    sequence, as a trained model's do; zeros where the file states
    none or 0 (A = -1: the state forgets within a few tokens, and the
    check cannot tell a state kept in bfloat16 from the stated float32:
    PERF.md, PR 31). All float32 as the program declares them."""
    d = dims["d_model"]
    # weights.py's ("normal", fan_in) draws with std 1 / sqrt(fan_in)
    a_log = ("normal", dims["a_log_std"] ** -2) if dims["a_log_std"] \
        else "zeros"
    out = [(("embed", "embedding"), (dims["vocab"], d), "served",
            ("normal", d)),
           (("lm_head", "kernel"), (d, dims["vocab"]), "served",
            ("normal", d)),
           (("final_norm", "scale"), (d,), "float32", "ones")]

    def kernel(path, rows, cols):
        out.append((path + ("kernel",), (rows, cols), "served",
                    ("normal", rows)))

    for i, letter in enumerate(dims["pattern"]):
        layer = f"layer_{i}"
        out.append(((layer, "norm", "scale"), (d,), "float32", "ones"))
        if letter == "M":
            mix = (layer, "ssm")
            kernel(mix + ("in_proj",), d, 2 * dims["d_inner"] + 2
                   * dims["ssm_groups"] * dims["ssm_state"]
                   + dims["ssm_heads"])
            kernel(mix + ("out_proj",), dims["d_inner"], d)
            out += [
                (mix + ("conv_kernel",),
                 (dims["conv_kernel"], dims["conv_dim"]), "served",
                 ("normal", dims["conv_kernel"])),
                (mix + ("conv_bias",), (dims["conv_dim"],), "float32",
                 "zeros"),
                (mix + ("dt_bias",), (dims["ssm_heads"],), "float32",
                 "zeros"),
                (mix + ("A_log",), (dims["ssm_heads"],), "float32",
                 a_log),
                (mix + ("D",), (dims["ssm_heads"],), "float32", "ones"),
                (mix + ("norm_scale",), (dims["d_inner"],), "float32",
                 "ones")]
        elif letter == "*":
            mix = (layer, "attn")
            features = dims["n_heads"] * dims["d_head"]
            kv_features = dims["n_kv_heads"] * dims["d_head"]
            kernel(mix + ("q_proj",), d, features)
            kernel(mix + ("k_proj",), d, kv_features)
            kernel(mix + ("v_proj",), d, kv_features)
            kernel(mix + ("o_proj",), features, d)
        else:
            mix = (layer, "experts")
            held, f = dims["experts_held"], dims["d_expert"]
            out += [
                (mix + ("router_kernel",), (d, dims["n_router"]),
                 "served", ("normal", d)),
                (mix + ("e_score_correction_bias",),
                 (dims["n_router"],), "float32", "zeros"),
                (mix + ("experts_up",), (held, d, f), "served",
                 ("normal", d)),
                (mix + ("experts_down",), (held, f, d), "served",
                 ("normal", f)),
                (mix + ("shared_up",), (d, dims["d_shared"]), "served",
                 ("normal", d)),
                (mix + ("shared_down",), (dims["d_shared"], d),
                 "served", ("normal", dims["d_shared"]))]
    return out


def program_model(config: dict, dims: dict, engine: dict,
                  ssm_state_dtype="float32"):
    """The model configuration object workloads/serve.build_engine
    takes, from the file's sizes and its ``engine`` section.
    ``ssm_state_dtype="bfloat16"`` is the program's own lower-precision
    switch (the state-space state kept in bfloat16 between steps): the
    check's control."""
    import jax.numpy as jnp
    from batch_shipyard_tpu.models import moe, ssm
    from batch_shipyard_tpu.models import transformer as tfm
    return tfm.TransformerConfig(
        vocab_size=dims["vocab"], d_model=dims["d_model"],
        n_layers=dims["n_layers"], n_heads=dims["n_heads"],
        n_kv_heads=dims["n_kv_heads"], d_head=dims["d_head"],
        max_seq_len=engine["max_decode_len"],
        dtype=jnp.bfloat16, param_dtype=jnp.bfloat16,
        use_rope=False, tie_embeddings=False, norm_eps=dims["eps"],
        block_kinds=tuple(KINDS[letter] for letter in dims["pattern"]),
        ssm=ssm.SSMConfig(
            n_heads=dims["ssm_heads"], head_dim=dims["ssm_head_dim"],
            n_groups=dims["ssm_groups"], state_size=dims["ssm_state"],
            conv_kernel=dims["conv_kernel"], chunk=dims["chunk"],
            state_dtype=jnp.dtype(ssm_state_dtype).type),
        experts=moe.RoutedConfig(
            d_model=dims["d_model"], n_experts=dims["n_router"],
            top_k=dims["top_k"], d_expert=dims["d_expert"],
            d_shared=dims["d_shared"], scale=dims["scale"],
            experts_held=dims["experts_held"],
            first_expert=dims["first_expert"]))


def teacher_forced_logits(params, tokens, rows, config: dict,
                          dims: dict, decisions=None):
    """The float32 reference's logits at ``rows`` of one teacher-forced
    sequence (benchmark/reference/hybrid_ssm_moe_plain.py) -> [len(rows),
    vocab]; with ``decisions`` also the slack per position and layer."""
    return plain.teacher_forced_logits(
        params, tokens, rows, pattern=dims["pattern"], eps=dims["eps"],
        ssm={"heads": dims["ssm_heads"], "width": dims["ssm_head_dim"],
             "groups": dims["ssm_groups"], "n_state": dims["ssm_state"]},
        attn={"q_heads": dims["n_heads"],
              "kv_heads": dims["n_kv_heads"]},
        routed={"top_k": dims["top_k"], "scale": dims["scale"],
                "first": dims["first_expert"]},
        decisions=decisions)
