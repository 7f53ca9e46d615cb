"""Model module ``window_moe_mtp``: everything the harness knows about
the stack that opens with a dense gated feed-forward layer, then runs
sliding-window and full grouped-query attention layers with q/k norms
over sigmoid-routed SwiGLU experts beside a shared one, with ONE
multi-token-prediction module behind it (``exaone_moe``), as the
program's ``TransformerLM`` runs it from a per-layer list of kinds,
windows and rotations and ``mtp_modules``. A configuration file names
it under ``model_module``; the reference is
benchmark/reference/kexaone_plain.py.

A PUBLISHED LAYER IS TWO BLOCKS of the program, each one mixer after
one norm: published layer l is block 2l (``attn``: window
``sliding_windows[l]``, 0 = full; rotated where ``layer_types[l]`` is
``sliding_attention``, no positions where it is ``full_attention``)
and block 2l+1 (``mlp`` where ``mlp_layer_types[l]`` is ``dense``,
else ``experts``). The module is the subtree ``mtp`` of the same tree
(embed_norm, hidden_norm, proj, layer_0 = attn, layer_1 = experts,
norm). The configuration holds an expert-parallel SHARE of every
routed block (``share``: experts first_expert .. + num_experts - 1 of
experts_of, vocabulary rows of vocab_rows_of).

The tree below IS the program's tree (checked against model.init in
tests/benchmark) and lives here, under ``paths``, so that no later PR
can move the yardstick."""

from __future__ import annotations

from benchmark.reference import kexaone_plain as plain

MTP = plain.MTP


def dims(config: dict) -> dict:
    """The sizes the arithmetic needs, from a configuration file's
    published (Hugging Face) keys, its ``share`` and its
    ``seeded_weights``."""
    published_layers = int(config["num_hidden_layers"])
    types = tuple(config["layer_types"])
    feeds = tuple(config["mlp_layer_types"])
    windows = tuple(int(w) for w in config["sliding_windows"])
    if not (len(types) == len(feeds) == len(windows)
            == published_layers):
        raise ValueError(
            f"layer_types / mlp_layer_types / sliding_windows: not one "
            f"entry each for each of {published_layers} layers")
    if any((kind == "sliding_attention") != bool(window)
           or kind not in ("sliding_attention", "full_attention")
           for kind, window in zip(types, windows)) or any(
               feed not in ("dense", "sparse") for feed in feeds):
        raise ValueError(f"layer_types {types} / sliding_windows "
                         f"{windows} / mlp_layer_types {feeds}")
    if feeds[:int(config["first_k_dense_replace"])].count("dense") \
            != feeds.count("dense"):
        raise ValueError("the dense layers are the leading "
                         "first_k_dense_replace")
    if config["scoring_func"] != "sigmoid" or not config[
            "norm_topk_prob"] or int(config["n_group"]) != 1 \
            or int(config["topk_group"]) != 1:
        raise ValueError("the router is sigmoid-scored, its weights "
                         "normalised, without a group step")
    if int(config["num_nextn_predict_layers"]) != 1 or tuple(
            config["mtp_layer_types"]) != ("full_attention",) or tuple(
                config["mtp_sliding_windows"]) != (0,):
        raise ValueError("one multi-token-prediction module, its "
                         "layer full attention")
    share = config["share"]
    window = int(config["sliding_window"])
    out = {
        "d_model": int(config["hidden_size"]),
        "published_layers": published_layers,
        "n_layers": 2 * published_layers,
        "kinds": tuple(kind for feed in feeds for kind in (
            "attn", "mlp" if feed == "dense" else "experts")),
        # one entry a published layer
        "windows": windows,
        "ropes": tuple(int(kind == "sliding_attention")
                       for kind in types),
        "dense": tuple(int(feed == "dense") for feed in feeds),
        "window": window,
        "theta": float(config["rope_parameters"]["rope_theta"]),
        "vocab": int(config["vocab_size"]),
        "eps": float(config["rms_norm_eps"]),
        "n_heads": int(config["num_attention_heads"]),
        "n_kv_heads": int(config["num_key_value_heads"]),
        "d_head": int(config["head_dim"]),
        "d_ff": int(config["intermediate_size"]),
        # experts: the router's width, the choices, what is held
        "n_router": int(share["experts_of"]),
        "top_k": int(config["num_experts_per_tok"]),
        "experts_held": int(config["num_experts"]),
        "first_expert": int(share["first_expert"]),
        "d_expert": int(config["moe_intermediate_size"]),
        "d_shared": int(config["num_shared_experts"])
        * int(config["moe_intermediate_size"]),
        "scale": float(config["routed_scaling_factor"]),
        "mtp_modules": int(config["num_nextn_predict_layers"]),
        # the tokens a decode step may write beyond the one it commits
        "drafts": int(config["num_nextn_predict_layers"]),
        # the seeded weights' one free number (param_leaves)
        "qk_gain": float(config.get("seeded_weights", {}).get(
            "qk_gain", 1.0)),
    }
    out["n_kind"] = {
        "attn_full": sum(not w for w in windows),
        "attn_window": sum(bool(w) for w in windows),
        "mlp": sum(out["dense"]),
        "experts": published_layers - sum(out["dense"])}
    # for kernels/: parameters by what a decode step has to read of
    # them, and the bytes a cached token holds in ONE attention layer
    # (K and V rows of Hkv * D in 2 bytes)
    d, features = out["d_model"], out["n_heads"] * out["d_head"]
    kv_features = out["n_kv_heads"] * out["d_head"]
    out["params"] = {
        "attn": 2 * d * features + 2 * d * kv_features,
        "mlp": 3 * d * out["d_ff"],
        "experts_always": d * out["n_router"] + 3 * d * out["d_shared"],
        "expert": 3 * d * out["d_expert"],
        "head": d * out["vocab"],
        "mtp_proj": 2 * d * d}
    out["kv_bytes_per_token_layer"] = 2 * 2 * kv_features
    return out


def decision_layers(config: dict, dims: dict) -> list:
    """The experts blocks, and the module's own (``mtp``): each
    chooses top_k of the router's n_router."""
    names = [f"layer_{i}" for i, kind in enumerate(dims["kinds"])
             if kind == "experts"] + [MTP]
    return [(name, dims["top_k"], dims["n_router"]) for name in names]


def param_leaves(dims: dict) -> list:
    """[(path, shape, dtype rule, init rule)] for benchmark/weights.py,
    paths as the program names its leaves. Kernels: normal, std
    1/sqrt(fan_in) (fan-in their rows; an expert stack's its middle
    axis; the embedding's the hidden size), in the served type; norm
    scales (the blocks', the q/k norms', the module's): ones, float32;
    e_score_correction_bias: zeros. ``qk_gain`` g
    (``seeded_weights.qk_gain``; window_moe.py has why a seeded
    attention wants one): behind a q/k norm a wider q or k projection
    is divided out again, so it is the NORMS' scales that set the
    scores' scale here, and where g is not 1 the q and k norms' scales
    are drawn normal with std g (the one rule of weights.py's closed
    set that is neither 0 nor 1): a score is then a sum over the
    head's channels of q_c k_c a_c b_c / sqrt(d_head), of standard
    deviation g * g where scales of ones give 1."""
    d = dims["d_model"]
    out = [(("embed", "embedding"), (dims["vocab"], d), "served",
            ("normal", d)),
           (("lm_head", "kernel"), (d, dims["vocab"]), "served",
            ("normal", d)),
           (("final_norm", "scale"), (d,), "float32", "ones")]

    def kernel(path, rows, cols):
        out.append((path + ("kernel",), (rows, cols), "served",
                    ("normal", rows)))

    def scale(path, width, gain=1.0):
        # weights.py's ("normal", fan_in) draws with std 1/sqrt(fan_in)
        out.append((path + ("scale",), (width,), "float32",
                    "ones" if gain == 1.0
                    else ("normal", 1.0 / gain ** 2)))

    features = dims["n_heads"] * dims["d_head"]
    kv_features = dims["n_kv_heads"] * dims["d_head"]
    held, f, shared = (dims["experts_held"], dims["d_expert"],
                       dims["d_shared"])

    def block(layer: tuple, kind: str):
        scale(layer + ("norm",), d)
        mix = layer + (kind,)
        if kind == "attn":
            kernel(mix + ("q_proj",), d, features)
            kernel(mix + ("k_proj",), d, kv_features)
            kernel(mix + ("v_proj",), d, kv_features)
            kernel(mix + ("o_proj",), features, d)
            scale(mix + ("q_norm",), dims["d_head"], dims["qk_gain"])
            scale(mix + ("k_norm",), dims["d_head"], dims["qk_gain"])
        elif kind == "mlp":
            kernel(mix + ("gate_proj",), d, dims["d_ff"])
            kernel(mix + ("up_proj",), d, dims["d_ff"])
            kernel(mix + ("down_proj",), dims["d_ff"], d)
        else:
            out.extend([
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
                 ("normal", shared))])

    for i, kind in enumerate(dims["kinds"]):
        block((f"layer_{i}",), kind)
    for name in ("embed_norm", "hidden_norm", "norm"):
        scale((MTP, name), d)
    kernel((MTP, "proj"), 2 * d, d)
    block((MTP, "layer_0"), "attn")
    block((MTP, "layer_1"), "experts")
    return out


def _per_block(values: tuple) -> tuple:
    """One entry a published layer -> one a program block (the
    feed-forward block's is 0 and read by nobody)."""
    return tuple(entry for value in values for entry in (value, 0))


def program_model(config: dict, dims: dict, engine: dict,
                  windows_off=False,
                  attn_softmax_dtype="float32", router_dtype="float32",
                  mtp_modules=None):
    """The model configuration object workloads/serve.build_engine
    takes, from the file's sizes and its ``engine`` section. The
    module is a FIELD of it (``mtp_modules``): an engine whose model
    has one drafts by itself, and is handed no option for it. The
    grouped paged-decode kernel is asked for by name on a TPU
    ("kernel"); elsewhere the program's XLA gather serves.
    ``windows_off`` is the check's control: the SAME program with the
    window taken off its window layers (True) or off the published
    layers listed (a layer without a window keeps whole contexts in
    the pool, and the chip has room for one more such layer beside
    the configuration's own, not for four), which has to fail.
    ``attn_softmax_dtype`` / ``router_dtype`` "bfloat16" are the
    program's own lower-precision switches
    (TransformerConfig.attn_softmax_dtype, RoutedConfig.router_dtype):
    controls too. ``mtp_modules=0`` leaves the module out (the plain
    step read beside the cell's; no control)."""
    import jax
    import jax.numpy as jnp
    from batch_shipyard_tpu.models import moe
    from batch_shipyard_tpu.models import transformer as tfm
    off = range(len(dims["windows"])) if windows_off is True \
        else tuple(windows_off or ())
    windows = tuple(0 if layer in off else window
                    for layer, window in enumerate(dims["windows"]))
    return tfm.TransformerConfig(
        vocab_size=dims["vocab"], d_model=dims["d_model"],
        n_layers=dims["n_layers"], n_heads=dims["n_heads"],
        n_kv_heads=dims["n_kv_heads"], d_head=dims["d_head"],
        d_ff=dims["d_ff"], max_seq_len=engine["max_decode_len"],
        dtype=jnp.bfloat16, param_dtype=jnp.bfloat16,
        rope_theta=dims["theta"],
        tie_embeddings=bool(config["tie_word_embeddings"]),
        norm_eps=dims["eps"], block_kinds=dims["kinds"],
        layer_windows=_per_block(windows),
        layer_rope=_per_block(tuple(bool(r) for r in dims["ropes"])),
        qk_norm=True, prefill_blocks=True,
        mtp_modules=dims["mtp_modules"] if mtp_modules is None
        else int(mtp_modules),
        mtp_rope=False,
        attn_softmax_dtype=jnp.dtype(attn_softmax_dtype).type,
        paged_attention_impl="kernel"
        if jax.default_backend() == "tpu" else None,
        experts=moe.RoutedConfig(
            d_model=dims["d_model"], n_experts=dims["n_router"],
            top_k=dims["top_k"], d_expert=dims["d_expert"],
            d_shared=dims["d_shared"], scale=dims["scale"],
            experts_held=dims["experts_held"],
            first_expert=dims["first_expert"], gated=True,
            scoring="sigmoid",
            router_dtype=jnp.dtype(router_dtype).type))


def teacher_forced_logits(params, tokens, rows, config: dict,
                          dims: dict, decisions=None, mtp_rows=None):
    """The float32 reference's logits at ``rows`` of one teacher-forced
    sequence (benchmark/reference/kexaone_plain.py) ->
    [len(rows), vocab]; with ``decisions`` also the slack per position
    and layer (the module's under "mtp"); with ``mtp_rows`` also the
    module's logits at those positions."""
    return plain.teacher_forced_logits(
        params, tokens, rows, windows=dims["windows"],
        ropes=dims["ropes"], dense=dims["dense"],
        q_heads=dims["n_heads"], kv_heads=dims["n_kv_heads"],
        theta=dims["theta"], top_k=dims["top_k"], scale=dims["scale"],
        first=dims["first_expert"], eps=dims["eps"],
        decisions=decisions, mtp_rows=mtp_rows)
