"""Model module ``window_moe``: everything the harness knows about the
stack of sliding-window and full grouped-query attention layers over
top-k routed ReGLU experts whose router reads the layer's input before
attention (``smallthinker``) that the program's ``TransformerLM`` runs
from a per-layer list of kinds, windows and rotations. A configuration
file names it under ``model_module``; the reference is
benchmark/reference/smallthinker_plain.py.

A PUBLISHED LAYER IS TWO BLOCKS of the program, each one mixer after
one norm: published layer l is block 2l (``attn``: window
``sliding_window_size`` where ``sliding_window_layout[l]`` is 1, else
full; rotated where ``rope_layout[l]`` is 1, else no positions) and
block 2l+1 (``experts``, whose router reads block 2l's normed input).
Every expert is held and the vocabulary is whole: the configuration
is cut over depth alone.

The tree below IS the program's tree (checked against model.init in
tests/benchmark) and lives here, under ``paths``, so that no later PR
can move the yardstick."""

from __future__ import annotations

from benchmark.reference import smallthinker_plain as plain


def dims(config: dict) -> dict:
    """The sizes the arithmetic needs, from a configuration file's
    published (Hugging Face) keys and its ``seeded_weights``."""
    published_layers = int(config["num_hidden_layers"])
    ropes = tuple(int(flag) for flag in config["rope_layout"])
    windowed = tuple(int(flag) for flag in
                     config["sliding_window_layout"])
    if len(ropes) != published_layers or \
            len(windowed) != published_layers:
        raise ValueError(
            f"rope_layout {ropes} / sliding_window_layout {windowed}: "
            f"not one entry for each of {published_layers} layers")
    if not (config["moe_primary_router_apply_softmax"]
            and config["norm_topk_prob"]):
        raise ValueError("the router's weights are a softmax over the "
                         "chosen logits")
    window = int(config["sliding_window_size"])
    out = {
        "d_model": int(config["hidden_size"]),
        "published_layers": published_layers,
        "n_layers": 2 * published_layers,
        "kinds": ("attn", "experts") * published_layers,
        # one entry a published layer
        "windows": tuple(window * flag for flag in windowed),
        "ropes": ropes,
        "window": window,
        "theta": float(config["rope_theta"]),
        "vocab": int(config["vocab_size"]),
        "eps": float(config["rms_norm_eps"]),
        "n_heads": int(config["num_attention_heads"]),
        "n_kv_heads": int(config["num_key_value_heads"]),
        "d_head": int(config["head_dim"]),
        "n_router": int(config["moe_num_primary_experts"]),
        "top_k": int(config["moe_num_active_primary_experts"]),
        "experts_held": int(config["moe_num_primary_experts"]),
        "d_expert": int(config["moe_ffn_hidden_size"]),
        # the seeded weights' one free number (param_leaves)
        "qk_gain": float(config.get("seeded_weights", {}).get(
            "qk_gain", 1.0)),
    }
    out["n_kind"] = {"attn_full": sum(not w for w in out["windows"]),
                     "attn_window": sum(bool(w) for w in out["windows"]),
                     "experts": published_layers}
    # for kernels/: parameters by what a decode step has to read of
    # them, and the bytes a cached token holds in ONE attention layer
    # (K and V rows of Hkv * D in 2 bytes)
    d, features = out["d_model"], out["n_heads"] * out["d_head"]
    kv_features = out["n_kv_heads"] * out["d_head"]
    out["params"] = {
        "attn": 2 * d * features + 2 * d * kv_features,
        "experts_always": d * out["n_router"],
        "expert": 3 * d * out["d_expert"],
        "head": d * out["vocab"]}
    out["kv_bytes_per_token_layer"] = 2 * 2 * kv_features
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
    axis; the embedding's the hidden size), in the served type; norm
    scales: ones, float32. The q and k projections alone are drawn
    ``qk_gain`` times wider (the file's ``seeded_weights.qk_gain``; 1
    where it states none): under unit-variance q and k a score is a
    unit-scale number and the softmax over thousands of keys is nearly
    uniform, so a layer's output is a mean of thousands of values and
    a key more or less (one beyond the window, say) moves nothing
    anyone could read; wider q and k make scores of a few units and an
    attention peaked on a few keys, as a trained model's is."""
    d = dims["d_model"]
    out = [(("embed", "embedding"), (dims["vocab"], d), "served",
            ("normal", d)),
           (("lm_head", "kernel"), (d, dims["vocab"]), "served",
            ("normal", d)),
           (("final_norm", "scale"), (d,), "float32", "ones")]

    def kernel(path, rows, cols, gain=1.0):
        # weights.py's ("normal", fan_in) draws with std 1/sqrt(fan_in)
        out.append((path + ("kernel",), (rows, cols), "served",
                    ("normal", rows / gain ** 2)))

    features = dims["n_heads"] * dims["d_head"]
    kv_features = dims["n_kv_heads"] * dims["d_head"]
    held, f = dims["experts_held"], dims["d_expert"]
    for i, kind in enumerate(dims["kinds"]):
        layer = f"layer_{i}"
        out.append(((layer, "norm", "scale"), (d,), "float32", "ones"))
        mix = (layer, kind)
        if kind == "attn":
            kernel(mix + ("q_proj",), d, features, dims["qk_gain"])
            kernel(mix + ("k_proj",), d, kv_features, dims["qk_gain"])
            kernel(mix + ("v_proj",), d, kv_features)
            kernel(mix + ("o_proj",), features, d)
        else:
            out += [
                (mix + ("router_kernel",), (d, dims["n_router"]),
                 "served", ("normal", d)),
                (mix + ("experts_gate",), (held, d, f), "served",
                 ("normal", d)),
                (mix + ("experts_up",), (held, d, f), "served",
                 ("normal", d)),
                (mix + ("experts_down",), (held, f, d), "served",
                 ("normal", f))]
    return out


def _per_block(values: tuple) -> tuple:
    """One entry a published layer -> one a program block (the
    experts block's is 0 and read by nobody)."""
    return tuple(entry for value in values for entry in (value, 0))


def program_model(config: dict, dims: dict, engine: dict,
                  windows_off: bool = False,
                  attn_softmax_dtype="float32", router_dtype="float32"):
    """The model configuration object workloads/serve.build_engine
    takes, from the file's sizes and its ``engine`` section. The
    grouped paged-decode kernel is asked for by name on a TPU
    ("kernel"); elsewhere the program's XLA gather serves.
    ``windows_off=True`` is the check's control: the SAME program with
    the window taken off the window layers (every layer attends over
    its whole context), which has to fail. ``attn_softmax_dtype`` /
    ``router_dtype`` "bfloat16" are the program's own lower-precision
    switches (TransformerConfig.attn_softmax_dtype: every attention
    layer's scores and running softmax terms kept in bfloat16, decode
    and prefill; RoutedConfig.router_dtype: the router's logits, top-k
    and weights in bfloat16): controls too."""
    import jax
    import jax.numpy as jnp
    from batch_shipyard_tpu.models import moe
    from batch_shipyard_tpu.models import transformer as tfm
    windows = tuple(0 for _ in dims["windows"]) if windows_off \
        else dims["windows"]
    return tfm.TransformerConfig(
        vocab_size=dims["vocab"], d_model=dims["d_model"],
        n_layers=dims["n_layers"], n_heads=dims["n_heads"],
        n_kv_heads=dims["n_kv_heads"], d_head=dims["d_head"],
        max_seq_len=engine["max_decode_len"],
        dtype=jnp.bfloat16, param_dtype=jnp.bfloat16,
        rope_theta=dims["theta"],
        tie_embeddings=bool(config["tie_word_embeddings"]),
        norm_eps=dims["eps"], block_kinds=dims["kinds"],
        layer_windows=_per_block(windows),
        layer_rope=_per_block(tuple(bool(r) for r in dims["ropes"])),
        router_before_mixer=True, prefill_blocks=True,
        attn_softmax_dtype=jnp.dtype(attn_softmax_dtype).type,
        paged_attention_impl="kernel"
        if jax.default_backend() == "tpu" else None,
        experts=moe.RoutedConfig(
            d_model=dims["d_model"], n_experts=dims["n_router"],
            top_k=dims["top_k"], d_expert=dims["d_expert"],
            d_shared=0, experts_held=dims["experts_held"],
            gated=True, gate_act="relu", scoring="softmax",
            router_dtype=jnp.dtype(router_dtype).type))


def teacher_forced_logits(params, tokens, rows, config: dict,
                          dims: dict, decisions=None):
    """The float32 reference's logits at ``rows`` of one teacher-forced
    sequence (benchmark/reference/smallthinker_plain.py) ->
    [len(rows), vocab]; with ``decisions`` also the slack per position
    and layer."""
    return plain.teacher_forced_logits(
        params, tokens, rows, windows=dims["windows"],
        ropes=dims["ropes"], q_heads=dims["n_heads"],
        kv_heads=dims["n_kv_heads"], theta=dims["theta"],
        top_k=dims["top_k"], eps=dims["eps"], decisions=decisions)
