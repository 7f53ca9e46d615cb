"""Model module ``latent_moe_mtp``: everything the harness knows about
the stack of multi-head LATENT attention layers (a low-rank query, one
compressed K/V vector and one rotary key a token for all heads) under
sandwich norms, leading dense gated feed-forward layers, then
sigmoid-routed SwiGLU experts beside a shared one, with ONE
multi-token-prediction module behind it (``pangu_ultra_moe``), as the
program's ``TransformerLM`` runs it from ``block_kinds``, ``latent``,
``sandwich_norm`` and ``mtp_modules``. A configuration file names it
under ``model_module``; the reference is
benchmark/reference/openpangu_plain.py.

A PUBLISHED LAYER IS TWO BLOCKS of the program, each one mixer between
two norms (``norm`` before it, ``post_norm`` on its output): published
layer l is block 2l (``attn``: latent attention) and block 2l+1
(``mlp`` for the leading ``first_k_dense_replace`` layers, else
``experts``). The module is the subtree ``mtp`` of the same tree
(embed_norm, hidden_norm, proj, layer_0 = attn, layer_1 = experts,
norm). The configuration holds an expert-parallel SHARE of every
routed block (``share``: experts first_expert .. + n_routed_experts - 1
of experts_of, vocabulary rows of vocab_rows_of).

The tree below IS the program's tree (checked against model.init in
tests/benchmark) and lives here, under ``paths``, so that no later PR
can move the yardstick."""

from __future__ import annotations

from benchmark.reference import openpangu_plain as plain

MTP = plain.MTP


def dims(config: dict) -> dict:
    """The sizes the arithmetic needs, from a configuration file's
    published (Hugging Face) keys and its ``share``."""
    published_layers = int(config["num_hidden_layers"])
    leading = int(config["first_k_dense_replace"])
    if not 0 <= leading <= published_layers:
        raise ValueError(f"first_k_dense_replace {leading} of "
                         f"{published_layers} layers")
    if not config["norm_topk_prob"] or not config["sandwich_norm"] \
            or config["attention_bias"]:
        raise ValueError("the router's weights are normalised, the "
                         "norms a sandwich, attention has no bias")
    if int(config["num_nextn_predict_layers"]) != 1:
        raise ValueError("one multi-token-prediction module")
    if int(config["num_key_value_heads"]) != int(
            config["num_attention_heads"]):
        raise ValueError("latent attention: every head has keys and "
                         "values of its own")
    share = config["share"]
    dense = tuple(int(l < leading) for l in range(published_layers))
    out = {
        "d_model": int(config["hidden_size"]),
        "published_layers": published_layers,
        "n_layers": 2 * published_layers,
        "kinds": tuple(kind for is_dense in dense for kind in (
            "attn", "mlp" if is_dense else "experts")),
        "dense": dense,                   # one entry a published layer
        "theta": float(config["rope_theta"]),
        "vocab": int(config["vocab_size"]),
        "eps": float(config["rms_norm_eps"]),
        "n_heads": int(config["num_attention_heads"]),
        "q_rank": int(config["q_lora_rank"]),
        "kv_rank": int(config["kv_lora_rank"]),
        "nope": int(config["qk_nope_head_dim"]),
        "rope": int(config["qk_rope_head_dim"]),
        "v_dim": int(config["v_head_dim"]),
        "d_ff": int(config["intermediate_size"]),
        # experts: the router's width, the choices, what is held
        "n_router": int(share["experts_of"]),
        "top_k": int(config["num_experts_per_tok"]),
        "experts_held": int(config["n_routed_experts"]),
        "first_expert": int(share["first_expert"]),
        "d_expert": int(config["moe_intermediate_size"]),
        "d_shared": int(config["n_shared_experts"])
        * int(config["moe_intermediate_size"]),
        "scale": float(config["routed_scaling_factor"]),
        "mtp_modules": int(config["num_nextn_predict_layers"]),
        # the tokens a decode step may write beyond the one it commits
        "drafts": int(config["num_nextn_predict_layers"]),
    }
    out["n_kind"] = {
        "attn_full": published_layers, "attn_window": 0,
        "mlp": sum(dense), "experts": published_layers - sum(dense)}
    # for kernels/: parameters by what a decode step has to read of
    # them, the lanes of a cached token's row in ONE attention layer
    # that hold the model's numbers, and the lanes the pool stores a
    # row in (whole lane tiles of 128: the rest is zeros)
    d, heads = out["d_model"], out["n_heads"]
    out["params"] = {
        "attn": d * out["q_rank"]
        + out["q_rank"] * heads * (out["nope"] + out["rope"])
        + d * (out["kv_rank"] + out["rope"])
        + out["kv_rank"] * heads * (out["nope"] + out["v_dim"])
        + heads * out["v_dim"] * d,
        "mlp": 3 * d * out["d_ff"],
        "experts_always": d * out["n_router"] + 3 * d * out["d_shared"],
        "expert": 3 * d * out["d_expert"],
        "head": d * out["vocab"],
        "mtp_proj": 2 * d * d}
    out["row_lanes"] = out["kv_rank"] + out["rope"]
    out["row_lanes_stored"] = -(-out["row_lanes"] // 128) * 128
    out["kv_bytes_per_token_layer"] = 2 * out["row_lanes"]
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
    scales (each block's two, the query's and the compressed vector's,
    the module's): ones, float32; e_score_correction_bias: zeros (the
    published file has no such key: the leaf is the program's)."""
    d = dims["d_model"]
    out = [(("embed", "embedding"), (dims["vocab"], d), "served",
            ("normal", d)),
           (("lm_head", "kernel"), (d, dims["vocab"]), "served",
            ("normal", d)),
           (("final_norm", "scale"), (d,), "float32", "ones")]

    def kernel(path, rows, cols):
        out.append((path + ("kernel",), (rows, cols), "served",
                    ("normal", rows)))

    def scale(path, width):
        out.append((path + ("scale",), (width,), "float32", "ones"))

    heads = dims["n_heads"]
    held, f, shared = (dims["experts_held"], dims["d_expert"],
                       dims["d_shared"])

    def block(layer: tuple, kind: str):
        scale(layer + ("norm",), d)
        scale(layer + ("post_norm",), d)
        mix = layer + (kind,)
        if kind == "attn":
            kernel(mix + ("q_down",), d, dims["q_rank"])
            scale(mix + ("q_norm",), dims["q_rank"])
            kernel(mix + ("q_up",), dims["q_rank"],
                   heads * (dims["nope"] + dims["rope"]))
            kernel(mix + ("kv_down",), d, dims["kv_rank"] + dims["rope"])
            scale(mix + ("kv_norm",), dims["kv_rank"])
            out.append((mix + ("kv_up",), (
                dims["kv_rank"], heads * (dims["nope"] + dims["v_dim"])),
                "served", ("normal", dims["kv_rank"])))
            kernel(mix + ("o_proj",), heads * dims["v_dim"], d)
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


def decode_rope_left_out(latent):
    """``latent`` (the program's LatentKV) as the check's structural
    control reads it: the SAME sizes, and a paged decode call whose
    scores lack the rotary key's term (the absorbed query row's rotary
    lanes zeroed; the prefill's expanded form is left as it is). The
    fault is built HERE, not in the served model, which has no switch
    for it: the first call wraps LatentAttention._absorbed_paged once,
    and the wrapper acts only where a model's ``latent`` is of the
    marker class this returns, so a sound model in the same process
    runs what it ran."""
    import dataclasses
    import jax.numpy as jnp
    from batch_shipyard_tpu.models import transformer as tfm
    marker = getattr(tfm.LatentAttention, "rope_left_out", None)
    if marker is None:
        class marker(tfm.LatentKV):
            pass
        served = tfm.LatentAttention._absorbed_paged

        def absorbed_paged(self, q_nope, q_rope, *rest):
            if isinstance(self.config.latent, marker):
                q_rope = jnp.zeros_like(q_rope)
            return served(self, q_nope, q_rope, *rest)

        tfm.LatentAttention._absorbed_paged = absorbed_paged
        tfm.LatentAttention.rope_left_out = marker
    return marker(**dataclasses.asdict(latent))


def program_model(config: dict, dims: dict, engine: dict,
                  decode_rope=True, attn_softmax_dtype="float32"):
    """The model configuration object workloads/serve.build_engine
    takes, from the file's sizes and its ``engine`` section. The
    module is a FIELD of it (``mtp_modules``): an engine whose model
    has one drafts by itself, and is handed no option for it; so are
    the latent attention (``latent``) and the sandwich. The latent
    paged-decode kernel is asked for by name on a TPU ("kernel");
    elsewhere the program's XLA gather serves.
    ``decode_rope`` False is the check's structural control
    (decode_rope_left_out above), which has to fail.
    ``attn_softmax_dtype`` "bfloat16" is the program's own
    lower-precision switch: a control too."""
    import jax
    import jax.numpy as jnp
    from batch_shipyard_tpu.models import moe
    from batch_shipyard_tpu.models import transformer as tfm
    latent = tfm.LatentKV(
        q_rank=dims["q_rank"], kv_rank=dims["kv_rank"],
        nope_dim=dims["nope"], rope_dim=dims["rope"],
        v_dim=dims["v_dim"])
    return tfm.TransformerConfig(
        vocab_size=dims["vocab"], d_model=dims["d_model"],
        n_layers=dims["n_layers"], n_heads=dims["n_heads"],
        d_head=dims["nope"] + dims["rope"],
        d_ff=dims["d_ff"], max_seq_len=engine["max_decode_len"],
        dtype=jnp.bfloat16, param_dtype=jnp.bfloat16,
        rope_theta=dims["theta"],
        tie_embeddings=bool(config["tie_word_embeddings"]),
        norm_eps=dims["eps"], block_kinds=dims["kinds"],
        prefill_blocks=True, sandwich_norm=True,
        latent=latent if decode_rope else decode_rope_left_out(latent),
        mtp_modules=dims["mtp_modules"],
        attn_softmax_dtype=jnp.dtype(attn_softmax_dtype).type,
        paged_attention_impl="kernel"
        if jax.default_backend() == "tpu" else None,
        experts=moe.RoutedConfig(
            d_model=dims["d_model"], n_experts=dims["n_router"],
            top_k=dims["top_k"], d_expert=dims["d_expert"],
            d_shared=dims["d_shared"], scale=dims["scale"],
            experts_held=dims["experts_held"],
            first_expert=dims["first_expert"], gated=True,
            scoring="sigmoid"))


def teacher_forced_logits(params, tokens, rows, config: dict,
                          dims: dict, decisions=None, mtp_rows=None):
    """The float32 reference's logits at ``rows`` of one teacher-forced
    sequence (benchmark/reference/openpangu_plain.py) ->
    [len(rows), vocab]; with ``decisions`` also the slack per position
    and layer (the module's under "mtp"); with ``mtp_rows`` also the
    module's logits at those positions."""
    return plain.teacher_forced_logits(
        params, tokens, rows, dense=dims["dense"],
        heads=dims["n_heads"], kv_rank=dims["kv_rank"],
        nope=dims["nope"], rope_dim=dims["rope"], theta=dims["theta"],
        top_k=dims["top_k"], scale=dims["scale"],
        first=dims["first_expert"], eps=dims["eps"],
        decisions=decisions, mtp_rows=mtp_rows)
