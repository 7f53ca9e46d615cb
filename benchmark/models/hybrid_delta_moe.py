"""Model module ``hybrid_delta_moe``: everything the harness knows
about the hybrid stack of gated delta-rule linear attention (KDA),
gated softmax attention without positions and gated routed experts
(``solar_open2``) that the program's ``TransformerLM`` runs from a
per-layer list of kinds. A configuration file names it under
``model_module``; the reference is benchmark/reference/
hybrid_delta_moe_plain.py.

A PUBLISHED LAYER IS TWO BLOCKS of the program, each one mixer after
one norm: published layer l is block 2l (``attn`` where l is in the
file's ``gqa_layers``, else ``delta``) and block 2l+1 (``experts``;
``first_k_dense_replace`` is 0, so every layer has them).

The configuration is ONE CHIP'S SHARE of a deployment: the file's
``n_routed_experts`` and ``vocab_size`` count what is held here, and
its ``share`` group says of how many (``experts_of``: the router's
width, which is not cut) and from where (``first_expert``). Program
and reference are handed the same share.

The tree below IS the program's tree (checked against model.init in
tests/benchmark) and lives here, under ``paths``, so that no later PR
can move the yardstick."""

from __future__ import annotations

from benchmark.reference import hybrid_delta_moe_plain as plain


def dims(config: dict) -> dict:
    """The sizes the arithmetic needs, from a configuration file's
    published (Hugging Face) keys, its ``share`` and its ``sizes_set``
    (what the published file does not say)."""
    published_layers = int(config["num_hidden_layers"])
    gqa = [int(i) for i in config["gqa_layers"]]
    if int(config["first_k_dense_replace"]) != 0 or any(
            not 0 <= i < published_layers for i in gqa):
        raise ValueError(
            f"gqa_layers {gqa} of {published_layers} layers, "
            f"first_k_dense_replace "
            f"{config['first_k_dense_replace']}: not a stack of "
            f"routed layers throughout")
    kinds = tuple(kind for layer in range(published_layers) for kind in
                  ("attn" if layer in gqa else "delta", "experts"))
    linear, share, set_here = (config["linear_attn_config"],
                               config["share"], config["sizes_set"])
    out = {
        "d_model": int(config["hidden_size"]),
        "n_layers": len(kinds), "kinds": kinds,
        "vocab": int(config["vocab_size"]),
        "eps": float(config["rms_norm_eps"]),
        # gated attention
        "n_heads": int(config["num_attention_heads"]),
        "n_kv_heads": int(config["num_key_value_heads"]),
        "d_head": int(config["head_dim"]),
        # delta rule
        "delta_heads": int(linear["num_heads"]),
        "delta_head_dim": int(linear["head_dim"]),
        "conv_kernel": int(linear["short_conv_kernel_size"]),
        "gate_rank": int(set_here["kda_gate_rank"]),
        "chunk": int(set_here["kda_chunk"]),
        # experts: the router's width, the choices, what is held
        "n_router": int(share["experts_of"]),
        "top_k": int(config["num_experts_per_tok"]),
        "experts_held": int(config["n_routed_experts"]),
        "first_expert": int(share["first_expert"]),
        "d_expert": int(config["moe_intermediate_size"]),
        "d_shared": int(config["n_shared_experts"])
        * int(config["moe_intermediate_size"]),
        "scale": float(config["routed_scaling_factor"]),
        # the seeded weights' one free number (param_leaves)
        "dt_bias_std": float(config.get("seeded_weights", {}).get(
            "dt_bias_std", 0.0)),
    }
    if linear.get("num_kv_heads") not in (None, out["delta_heads"]):
        raise ValueError("the delta mixer has one key/value head a "
                         "query head")
    out["d_inner"] = out["delta_heads"] * out["delta_head_dim"]
    out["n_kind"] = {kind: kinds.count(kind)
                     for kind in ("delta", "attn", "experts")}
    # for kernels/: parameters by what a decode step has to read of
    # them, and the bytes a slot and a cached token hold (weights,
    # activations, K/V and the convolution's tail in 2 bytes; the
    # delta state in 4)
    d, inner, rank = out["d_model"], out["d_inner"], out["gate_rank"]
    features = out["n_heads"] * out["d_head"]
    out["params"] = {
        "delta": 3 * d * inner + inner * d + 2 * (d * rank + rank * inner)
        + d * out["delta_heads"] + out["conv_kernel"] * 3 * inner,
        "attn": 3 * d * features
        + 2 * d * out["n_kv_heads"] * out["d_head"],
        "experts_always": d * out["n_router"] + 3 * d * out["d_shared"],
        "expert": 3 * d * out["d_expert"],
        "head": d * out["vocab"]}
    out["slot_state_bytes"] = out["n_kind"]["delta"] * (
        4 * inner * out["delta_head_dim"]
        + 2 * (out["conv_kernel"] - 1) * 3 * inner)
    out["kv_bytes_per_token"] = out["n_kind"]["attn"] * 2 * 2 \
        * out["n_kv_heads"] * out["d_head"]
    return out


def decision_layers(config: dict, dims: dict) -> list:
    """The experts blocks: each chooses top_k of the router's
    n_router."""
    return [(f"layer_{i}", dims["top_k"], dims["n_router"])
            for i, kind in enumerate(dims["kinds"]) if kind == "experts"]


def param_leaves(dims: dict) -> list:
    """[(path, shape, dtype rule, init rule)] for benchmark/weights.py,
    paths as the program names its leaves. Kernels: normal, std
    1/sqrt(fan_in) (fan-in their rows; an expert stack's its middle
    axis; the depthwise convolution's its taps; the embedding's the
    hidden size), in the served type. Norm scales: ones; A_log and
    e_score_correction_bias: zeros; dt_bias normal with the standard
    deviation the file states (``seeded_weights.dt_bias_std``), zeros
    where it states none or 0. All float32 as the program declares
    them. What that makes of the delta rule's memory: the decay is
    PER CHANNEL, -exp(A_log) = -1 times softplus of a unit-scale
    number plus the channel's dt_bias, so with dt_bias spread over
    several units every head has channels that hardly decay at all
    beside channels that forget at once. In the first the rule
    forgets only along the keys it writes (beta = 2 sigmoid of a
    unit-scale number, around 1): what a sequence wrote a hundred
    tokens ago is still read, and a stale, wrong or ROUNDED state
    shows in the logits. And no head's state is ever ONE token: a
    head that forgets at once in every channel (A_log large) reads
    out beta (k.q) v, which after its norm is v times the SIGN of
    k.q, and bfloat16 flips that sign wherever k.q is near 0 (PERF.md,
    PR 33: such heads raised the sound engine's reading by a third
    on the chip and doubled it at the tests' size)."""
    d = dims["d_model"]
    # weights.py's ("normal", fan_in) draws with std 1 / sqrt(fan_in)
    dt_bias = ("normal", dims["dt_bias_std"] ** -2) \
        if dims["dt_bias_std"] else "zeros"
    out = [(("embed", "embedding"), (dims["vocab"], d), "served",
            ("normal", d)),
           (("lm_head", "kernel"), (d, dims["vocab"]), "served",
            ("normal", d)),
           (("final_norm", "scale"), (d,), "float32", "ones")]

    def kernel(path, rows, cols):
        out.append((path + ("kernel",), (rows, cols), "served",
                    ("normal", rows)))

    inner, rank = dims["d_inner"], dims["gate_rank"]
    for i, kind in enumerate(dims["kinds"]):
        layer = f"layer_{i}"
        out.append(((layer, "norm", "scale"), (d,), "float32", "ones"))
        mix = (layer, kind)
        if kind == "delta":
            kernel(mix + ("qkv_proj",), d, 3 * inner)
            kernel(mix + ("decay_a",), d, rank)
            kernel(mix + ("decay_b",), rank, inner)
            kernel(mix + ("gate_a",), d, rank)
            kernel(mix + ("gate_b",), rank, inner)
            kernel(mix + ("beta_proj",), d, dims["delta_heads"])
            kernel(mix + ("o_proj",), inner, d)
            out += [
                (mix + ("conv_kernel",),
                 (dims["conv_kernel"], 3 * inner), "served",
                 ("normal", dims["conv_kernel"])),
                (mix + ("A_log",), (dims["delta_heads"],), "float32",
                 "zeros"),
                (mix + ("dt_bias",), (inner,), "float32", dt_bias),
                (mix + ("norm_scale",), (dims["delta_head_dim"],),
                 "float32", "ones")]
        elif kind == "attn":
            features = dims["n_heads"] * dims["d_head"]
            kv_features = dims["n_kv_heads"] * dims["d_head"]
            kernel(mix + ("q_proj",), d, features)
            kernel(mix + ("k_proj",), d, kv_features)
            kernel(mix + ("v_proj",), d, kv_features)
            kernel(mix + ("gate_proj",), d, features)
            kernel(mix + ("o_proj",), features, d)
        else:
            held, f, shared = (dims["experts_held"], dims["d_expert"],
                               dims["d_shared"])
            out += [
                (mix + ("router_kernel",), (d, dims["n_router"]),
                 "served", ("normal", d)),
                (mix + ("e_score_correction_bias",),
                 (dims["n_router"],), "float32", "zeros"),
                (mix + ("experts_gate",), (held, d, f), "served",
                 ("normal", d)),
                (mix + ("experts_up",), (held, d, f), "served",
                 ("normal", d)),
                (mix + ("experts_down",), (held, f, d), "served",
                 ("normal", f)),
                (mix + ("shared_gate",), (d, shared), "served",
                 ("normal", d)),
                (mix + ("shared_up",), (d, shared), "served",
                 ("normal", d)),
                (mix + ("shared_down",), (shared, d), "served",
                 ("normal", shared))]
    return out


def program_model(config: dict, dims: dict, engine: dict,
                  delta_state_dtype="float32"):
    """The model configuration object workloads/serve.build_engine
    takes, from the file's sizes and its ``engine`` section.
    ``delta_state_dtype="bfloat16"`` is the program's own
    lower-precision switch (the delta state kept in bfloat16 between
    steps): the check's control."""
    import jax.numpy as jnp
    from batch_shipyard_tpu.models import delta, moe
    from batch_shipyard_tpu.models import transformer as tfm
    return tfm.TransformerConfig(
        vocab_size=dims["vocab"], d_model=dims["d_model"],
        n_layers=dims["n_layers"], n_heads=dims["n_heads"],
        n_kv_heads=dims["n_kv_heads"], d_head=dims["d_head"],
        max_seq_len=engine["max_decode_len"],
        dtype=jnp.bfloat16, param_dtype=jnp.bfloat16,
        use_rope=bool(config["use_rope"]),
        attn_output_gate=bool(config["use_gqa_gate"]),
        tie_embeddings=bool(config["tie_word_embeddings"]),
        norm_eps=dims["eps"], block_kinds=dims["kinds"],
        delta=delta.DeltaConfig(
            n_heads=dims["delta_heads"],
            head_dim=dims["delta_head_dim"],
            conv_kernel=dims["conv_kernel"],
            gate_rank=dims["gate_rank"], chunk=dims["chunk"],
            state_dtype=jnp.dtype(delta_state_dtype).type),
        experts=moe.RoutedConfig(
            d_model=dims["d_model"], n_experts=dims["n_router"],
            top_k=dims["top_k"], d_expert=dims["d_expert"],
            d_shared=dims["d_shared"], scale=dims["scale"],
            experts_held=dims["experts_held"],
            first_expert=dims["first_expert"], gated=True))


def teacher_forced_logits(params, tokens, rows, config: dict,
                          dims: dict, decisions=None):
    """The float32 reference's logits at ``rows`` of one teacher-forced
    sequence (benchmark/reference/hybrid_delta_moe_plain.py) ->
    [len(rows), vocab]; with ``decisions`` also the slack per position
    and layer."""
    return plain.teacher_forced_logits(
        params, tokens, rows, kinds=dims["kinds"], eps=dims["eps"],
        delta={"heads": dims["delta_heads"],
               "width": dims["delta_head_dim"]},
        attn={"q_heads": dims["n_heads"],
              "kv_heads": dims["n_kv_heads"]},
        routed={"top_k": dims["top_k"], "scale": dims["scale"],
                "first": dims["first_expert"]},
        decisions=decisions)
