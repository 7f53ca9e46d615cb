"""Model module ``moe_block_diffusion``: everything the harness knows
about a stack of grouped-query attention layers with q/k norms under a
BLOCK-causal mask over softmax-routed SwiGLU experts (no shared one)
that generates by diffusion over blocks (``sdar_moe``), as the
program's ``TransformerLM`` runs it from a per-layer list of kinds and
``TransformerConfig.block_diffusion``. A configuration file names it
under ``model_module``; the reference is
benchmark/reference/sdar_plain.py.

A PUBLISHED LAYER IS TWO BLOCKS of the program, each one mixer after
one norm: published layer l is block 2l (``attn``) and block 2l+1
(``experts``). Block length, denoising steps, the remasking rule, its
threshold and the mask token are the file's ``generation`` section
(assumed sizes: the published file gives none of them).

WHAT IS JUDGED, AND ON WHAT. A token of a block is conditioned on the
tokens of its own block that were unmasked before it, so the engine's
record (``ContinuousBatcher.take_decisions``) holds, beside the routed
layers' choices of the passes that wrote each position's K/V, those of
every denoise pass and the pass that unmasked each position, each over
the same positions, and ``decision_layers`` declares all of them:

  layer_<i>            k of n: a prefill's or a commit pass's choices
  layer_<i>.pass<s>    k of n: denoise pass s of the position's block
  unmask               1 of steps + 1: the pass that unmasked the
                       position (``steps``: given, never masked)

``request_readings`` is the whole judgement of one request, handed
every token the program conditioned on: benchmark/drivers/
serve_closed_blocks.py decides ``correct`` by it.
``teacher_forced_logits`` is the same reference behind the harness's
autoregressive call (benchmark/check.py::serve_gaps: ``prompt +
served[:-1]``, row r read for token r + 1), which cannot hand over the
request's last token or the ones dropped behind it: exact for every
token whose block lies whole inside the sequence, an approximation in
the last block (stated there); tests/benchmark/test_bench_reference.py
reads it at a small size.

The tree below IS the program's tree (checked against model.init in
tests/benchmark) and lives here, under ``paths``, so that no later PR
can move the yardstick."""

from __future__ import annotations

import math

import numpy as np

from benchmark.reference import sdar_plain as plain

UNMASK = plain.UNMASK
RULES = ("low_confidence_static", "low_confidence_dynamic")


def dims(config: dict) -> dict:
    """The sizes the arithmetic needs, from a configuration file's
    published (Hugging Face) keys and its ``generation`` section."""
    published_layers = int(config["num_hidden_layers"])
    if int(config["decoder_sparse_step"]) != 1 or list(
            config["mlp_only_layers"]) or not config["norm_topk_prob"]:
        raise ValueError("every layer is sparse, its top-k weights "
                         "normalised")
    if config["use_sliding_window"] or config["rope_scaling"] \
            or config["attention_bias"]:
        raise ValueError("full attention, unscaled rotation, no bias")
    generation = config["generation"]
    block, steps = (int(generation["block_length"]),
                    int(generation["denoising_steps"]))
    if block < 1 or block & (block - 1) or block % steps \
            or generation["remasking"] not in RULES:
        raise ValueError(f"generation {generation!r}: a block of a "
                         f"power of two, steps that divide it, a rule "
                         f"of {RULES}")
    out = {
        "d_model": int(config["hidden_size"]),
        "published_layers": published_layers,
        "n_layers": 2 * published_layers,
        "kinds": ("attn", "experts") * published_layers,
        "theta": float(config["rope_theta"]),
        "vocab": int(config["vocab_size"]),
        "eps": float(config["rms_norm_eps"]),
        "n_heads": int(config["num_attention_heads"]),
        "n_kv_heads": int(config["num_key_value_heads"]),
        "d_head": int(config["head_dim"]),
        "n_router": int(config["num_experts"]),
        "top_k": int(config["num_experts_per_tok"]),
        "experts_held": int(config["num_experts"]),
        "d_expert": int(config["moe_intermediate_size"]),
        # generation by diffusion over blocks
        "block": block,
        "steps": steps,
        "remask": generation["remasking"],
        "threshold": float(generation["confidence_threshold"]),
        "mask_id": int(generation["mask_token_id"]),
    }
    if not 0 <= out["mask_id"] < out["vocab"]:
        raise ValueError("the mask token is a row of the embedding")
    out["n_kind"] = {"attn_full": published_layers, "attn_window": 0,
                     "experts": published_layers}
    # for kernels/: parameters by what a step has to read of them, and
    # the bytes a cached token holds in ONE attention layer (K and V
    # rows of Hkv * D in 2 bytes)
    d, features = out["d_model"], out["n_heads"] * out["d_head"]
    kv_features = out["n_kv_heads"] * out["d_head"]
    out["params"] = {
        "attn": 2 * d * features + 2 * d * kv_features,
        "experts_always": d * out["n_router"],
        "expert": 3 * d * out["d_expert"],
        "head": d * out["vocab"]}
    out["kv_bytes_per_token_layer"] = 2 * 2 * kv_features
    return out


def _routed(dims: dict) -> list:
    return [f"layer_{i}" for i, kind in enumerate(dims["kinds"])
            if kind == "experts"]


def decision_layers(config: dict, dims: dict) -> list:
    """What the engine's record holds a position (the module's
    docstring): each routed layer's choices of the pass that wrote the
    position, of each denoise pass, and the pass that unmasked it."""
    choice = (dims["top_k"], dims["n_router"])
    return [(name, *choice) for name in _routed(dims)] + [
        (plain.pass_name(name, s), *choice)
        for s in range(dims["steps"]) for name in _routed(dims)] + [
            (UNMASK, 1, dims["steps"] + 1)]


def param_leaves(dims: dict) -> list:
    """[(path, shape, dtype rule, init rule)] for benchmark/weights.py,
    paths as the program names its leaves. Kernels: normal, std
    1/sqrt(fan_in) (fan-in their rows; an expert stack's its middle
    axis; the embedding's the hidden size), in the served type; norm
    scales, the q and k norms' too: ones, float32 (unit-scale scores;
    PERF.md section 6, PR 46, has the wider scores that were tried and
    taken back)."""
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

    features = dims["n_heads"] * dims["d_head"]
    kv_features = dims["n_kv_heads"] * dims["d_head"]
    held, f = dims["experts_held"], dims["d_expert"]
    for i, kind in enumerate(dims["kinds"]):
        layer = f"layer_{i}"
        scale((layer, "norm"), d)
        mix = (layer, kind)
        if kind == "attn":
            kernel(mix + ("q_proj",), d, features)
            kernel(mix + ("k_proj",), d, kv_features)
            kernel(mix + ("v_proj",), d, kv_features)
            kernel(mix + ("o_proj",), features, d)
            scale(mix + ("q_norm",), dims["d_head"])
            scale(mix + ("k_norm",), dims["d_head"])
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


def program_lacks():
    """None, or what the program beside this benchmark lacks to run
    this model at all (the parent of the PR that brought this file):
    asked by the cell's driver before any weight is made."""
    from batch_shipyard_tpu.models import transformer as tfm
    if hasattr(tfm, "BlockDiffusion"):
        return None
    return ("the program beside this benchmark has no generation by "
            "diffusion over blocks (models/transformer.py: "
            "TransformerConfig.block_diffusion)")


def program_model(config: dict, dims: dict, engine: dict,
                  causal_inside_block: bool = False,
                  attn_softmax_dtype="float32"):
    """The model configuration object workloads/serve.build_engine
    takes, from the file's sizes and its ``engine`` section. Generation
    by diffusion over blocks is a FIELD of it (``block_diffusion``): an
    engine whose model has one denoises and commits blocks by itself,
    and is handed no option for it. The grouped paged-decode kernel is
    asked for by name on a TPU ("kernel"); elsewhere the program's XLA
    gather serves. ``causal_inside_block=True`` is the check's control:
    the SAME program with the plain causal mask kept inside a block
    (BlockDiffusion.bidirectional False: prefill and block pass), which
    has to fail. ``attn_softmax_dtype`` "bfloat16" is the program's
    own lower-precision switch: a control too."""
    import jax
    import jax.numpy as jnp
    from batch_shipyard_tpu.models import moe
    from batch_shipyard_tpu.models import transformer as tfm
    return tfm.TransformerConfig(
        vocab_size=dims["vocab"], d_model=dims["d_model"],
        n_layers=dims["n_layers"], n_heads=dims["n_heads"],
        n_kv_heads=dims["n_kv_heads"], d_head=dims["d_head"],
        max_seq_len=engine["max_decode_len"],
        dtype=jnp.bfloat16, param_dtype=jnp.bfloat16,
        rope_theta=dims["theta"],
        tie_embeddings=bool(config["tie_word_embeddings"]),
        norm_eps=dims["eps"], block_kinds=dims["kinds"],
        qk_norm=True, prefill_blocks=True,
        attn_softmax_dtype=jnp.dtype(attn_softmax_dtype).type,
        paged_attention_impl="kernel"
        if jax.default_backend() == "tpu" else None,
        block_diffusion=tfm.BlockDiffusion(
            block=dims["block"], steps=dims["steps"],
            remask=dims["remask"], threshold=dims["threshold"],
            mask_id=dims["mask_id"],
            bidirectional=not causal_inside_block),
        experts=moe.RoutedConfig(
            d_model=dims["d_model"], n_experts=dims["n_router"],
            top_k=dims["top_k"], d_expert=dims["d_expert"],
            d_shared=0, experts_held=dims["experts_held"],
            gated=True, scoring="softmax"))


def _sizes(dims: dict) -> dict:
    return dict(layers=dims["published_layers"], block=dims["block"],
                q_heads=dims["n_heads"], kv_heads=dims["n_kv_heads"],
                theta=dims["theta"], top_k=dims["top_k"],
                eps=dims["eps"])


def _forward(params, clean, start: int, unmasked_at, dims: dict,
             handed=None):
    """The reference over one request's extended sequence
    (sdar_plain.extended) -> (hidden [R, d], {name: slack} by the
    record's names: a routed layer's over the clean positions [T], its
    pass s's over the span [T - start]). ``handed``: the record's
    routed entries cut to the same frames, {layer: int32 [T, k],
    layer.pass<s>: int32 [T - start, k]} (a row of -1: the reference's
    own choice), or None."""
    total, steps = len(clean), dims["steps"]
    routed = _routed(dims)
    decisions = None if handed is None else {
        name: np.concatenate([handed[name]] + [
            handed[plain.pass_name(name, s)] for s in range(steps)])
        for name in routed}
    hidden, slacks = plain.stack_hidden(
        params, *plain.extended(clean, start, unmasked_at, steps,
                                dims["mask_id"]),
        decisions=decisions, **_sizes(dims))
    out = {}
    for name in routed:
        out[name] = slacks[name][:total]
        for s in range(steps):
            lo = plain.extended_row(start, s, total, start)
            out[plain.pass_name(name, s)] = \
                slacks[name][lo:lo + total - start]
    return hidden, out


def unmask_slacks(confidence, unmasked_at, dims: dict) -> dict:
    """Every unmask choice judged: for each block and each denoise
    pass s it took, M the positions masked before the pass (s <=
    unmasked_at < steps) and U those it unmasked (unmasked_at == s),
    from the reference's log-confidences ``confidence`` [steps, span]
    (read at M alone) -> {(block's first span position, s): slack},
    the reference's |U|-th best confidence in M less the lowest among
    U: 0 where the sets agree, never below. Under the dynamic rule
    also how far a handed position lies below the threshold where more
    than the least were handed, and how far a position left masked
    lies above it."""
    at = np.asarray(unmasked_at)
    block, steps = dims["block"], dims["steps"]
    least = block // steps
    bar = math.log(dims["threshold"]) \
        if dims["remask"] == "low_confidence_dynamic" else None
    out = {}
    for lo in range(0, len(at), block):
        mine = at[lo:lo + block]
        for s in range(steps):
            took = mine == s
            if not took.any():
                continue
            masked = (mine >= s) & (mine < steps)
            scores = confidence[s, lo:lo + block]
            ranked = np.sort(scores[masked])[::-1]
            slack = ranked[took.sum() - 1] - scores[took].min()
            if bar is not None:
                if took.sum() > least:
                    slack = max(slack, bar - scores[took].min())
                left = masked & ~took
                if left.any():
                    slack = max(slack, scores[left].max() - bar)
            out[(lo, s)] = float(max(slack, 0.0))
    return out


def _masked_readings(params, hidden, clean, at, start: int,
                     dims: dict):
    """The head at every (span position, pass) at which the position
    was still masked, ``at`` [n] the passes of the span positions
    start .. start + n - 1 of the extended sequence whose rows
    ``hidden`` holds -> (span [m], copy [m]: the pairs; the head's
    readings at them, "at" the logit of the position's own clean
    token; the log-confidences as [steps, n], nan elsewhere)."""
    steps = dims["steps"]
    span, copy = np.nonzero(
        (np.arange(steps)[None, :] <= at[:, None])
        & (at[:, None] < steps))
    rows = np.asarray(
        [plain.extended_row(start + i, s, len(clean), start)
         for i, s in zip(span, copy)], np.int64)
    read = plain.head_readings(params, hidden[rows],
                               clean[start + span], dims["eps"])
    confidence = np.full((steps, len(at)), np.nan)
    confidence[copy, span] = read["confidence"]
    return span, copy, read, confidence


def request_readings(params, prompt: list, served: list, record: dict,
                     config: dict, dims: dict) -> dict:
    """One finished request judged whole by the reference, which is
    handed every token the program conditioned on (``record``: the
    engine's, ContinuousBatcher.take_decisions: "tokens" holds the
    committed blocks from "start" on, the dropped ones behind the last
    served included). ->

      gaps, best    one a served token: how far its logit lies below
                    the reference's best AT ITS OWN POSITION in the
                    pass that unmasked it, and that best
      slack         {record name: [m]} one a recorded position and
                    routed entry (the passes that wrote it, each
                    denoise pass)
      unmask_slack  one a denoise pass of a block (unmask_slacks)
      positions, positions_unrecorded

    or None for a record that is not the declared shape or does not
    hold the served tokens."""
    steps, block = dims["steps"], dims["block"]
    try:
        first, start = int(record["first"]), int(record["start"])
        span_tokens = np.asarray(record["tokens"], np.int32)
        layers = {name: np.asarray(record["layers"][name])
                  for name, _k, _n in decision_layers(config, dims)}
    except (KeyError, TypeError, ValueError):
        return None
    total = start + len(span_tokens)
    given = len(prompt) - start
    rows = total - first
    if not (0 <= first <= start <= len(prompt) and start % block == 0
            and len(span_tokens) % block == 0 and 0 <= given < block
            and len(prompt) + len(served) <= total
            and span_tokens[:given].tolist() == prompt[start:]
            and span_tokens[given:given + len(served)].tolist()
            == list(served)) or any(
                value.shape != (rows, k)
                or not np.issubdtype(value.dtype, np.integer)
                for (_name, k, _n), value in zip(
                    decision_layers(config, dims), layers.values())):
        return None
    at = layers[UNMASK][start - first:, 0].astype(np.int32)
    # a pass out of range is no pass: the position reads as given
    at = np.where((at < 0) | (at > steps), steps, at)
    clean = np.concatenate([np.asarray(prompt[:start], np.int32),
                            span_tokens])
    handed = {}
    for name in _routed(dims):
        handed[name] = np.full((total, dims["top_k"]), -1, np.int32)
        handed[name][first:] = layers[name]
        for s in range(steps):
            handed[plain.pass_name(name, s)] = layers[
                plain.pass_name(name, s)][start - first:].astype(np.int32)
    # (what no top-k could have chosen: benchmark/check.py's rule)
    from benchmark import check
    refused = {name: check._malformed(rows_, dims["n_router"])
               for name, rows_ in handed.items()}
    for name, bad in refused.items():
        handed[name][bad] = -1
    hidden, slacks = _forward(params, clean, start, at, dims, handed)
    # a position's confidence in every pass that still found it
    # masked, and in its own pass its gap
    span, copy, read, confidence = _masked_readings(
        params, hidden, clean, at, start, dims)
    own = copy == at[span]
    judged = own & (span >= given) & (span < given + len(served))
    out = {"gaps": (read["best"] - read["at"])[judged].tolist(),
           "best": read["best"][judged].tolist(),
           "unmask_slack": list(unmask_slacks(confidence, at,
                                              dims).values()),
           "slack": {}, "positions": total,
           "positions_unrecorded": first}
    # (a served position that was never masked, as a corrupted record
    # may say, has no pass to be judged in: counted as a gap of inf)
    out["gaps"] += [math.inf] * (len(served) - int(judged.sum()))
    for name, slack in slacks.items():
        slack = np.array(slack, np.float64)
        slack[refused[name]] = np.inf
        out["slack"][name] = slack[first:].tolist() \
            if name in _routed(dims) else slack.tolist()
    return out


def teacher_forced_logits(params, tokens, rows, config: dict,
                          dims: dict, decisions=None):
    """The reference behind the harness's AUTOREGRESSIVE call
    (benchmark/check.py::serve_gaps): ``tokens`` [padded] holds prompt
    + served[:-1] and then padding, and row r is read for the token at
    position r + 1, so this gives for each of ``rows`` the logits AT
    POSITION r + 1 in the denoise pass that unmasked it
    (``decisions["unmask"]``; the clean sequence's where that position
    was given or has no record) -> [len(rows), vocab]; with
    ``decisions`` also {name: slack [padded]}, the unmask choices'
    under "unmask" at the positions each pass unmasked.

    The sequence ends one token short of the request (and of what was
    dropped behind it), so of the LAST block the reference knows less
    than the program did: the positions it is not handed read as MASK
    in every pass, and the one beyond the sequence is taken as
    unmasked by the pass after the last that the known positions of
    its block name. A token of that block that was unmasked after one
    the sequence lacks is judged on a block that differs by that
    token; every earlier block is exact. benchmark/drivers/
    serve_closed_blocks.py, which decides the cell's ``correct``,
    hands over every token (request_readings)."""
    import jax.numpy as jnp
    block, steps = dims["block"], dims["steps"]
    rows = np.asarray(rows)
    padded = int(tokens.shape[0])
    length = int(rows.max()) + 1        # the sequence's own tokens
    total = (length // block + 1) * block
    known = np.asarray(tokens, np.int32)[:length]
    unknown = steps - 1                 # masked in every copy
    at = np.full((total,), steps, np.int32)
    at[length:] = unknown
    if decisions is not None:
        handed_at = np.asarray(decisions[UNMASK])[:length, 0]
        at[:length] = np.where((handed_at < 0) | (handed_at > steps),
                               steps, handed_at)
    generated = np.nonzero(at[:length] < steps)[0]
    start = int(generated[0]) // block * block if len(generated) \
        else length // block * block
    # the position beyond the sequence: the pass after the last its
    # block's known positions name
    last = at[total - block:length]
    last = last[last < steps]
    at[length] = min(steps - 1, int(last.max()) + 1 if len(last) else 0)
    clean = np.concatenate([known, np.zeros((total - length,), np.int32)])
    handed = None
    if decisions is not None:
        handed = {}
        for name in _routed(dims):
            for key, lo in [(name, 0)] + [
                    (plain.pass_name(name, s), start)
                    for s in range(steps)]:
                value = np.full((total - lo, dims["top_k"]), -1, np.int32)
                value[:length - lo] = np.asarray(
                    decisions[key])[lo:length]
                handed[key] = value
    hidden, slacks = _forward(params, clean, start, at[start:], dims,
                              handed)
    judged = np.minimum(rows + 1, total - 1)
    copy = np.where((judged >= start) & (at[judged] < steps),
                    at[judged], plain.CLEAN)
    ext = np.asarray([plain.extended_row(int(p), int(s), total, start)
                      for p, s in zip(judged, copy)], np.int64)
    logits = plain.head_logits(
        hidden[jnp.asarray(ext)], params["final_norm"],
        params["lm_head"]["kernel"], dims["eps"])
    if decisions is None:
        return logits
    out = {}
    for name, slack in slacks.items():
        lo = 0 if name in _routed(dims) else start
        full = np.zeros((padded,), np.float64)
        full[lo:length] = slack[:length - lo]
        out[name] = full
    # the unmask choices, each pass's slack at the positions it took:
    # of the blocks the sequence holds whole alone
    whole = (length - start) // block * block
    _span, _copy, _read, confidence = _masked_readings(
        params, hidden, clean, at[start:start + whole], start, dims)
    choice = np.zeros((padded,), np.float64)
    for (lo, s), slack in unmask_slacks(
            confidence, at[start:start + whole], dims).items():
        took = at[start + lo:start + lo + block] == s
        choice[start + lo:start + lo + block][took] = slack
    out[UNMASK] = choice
    return logits, out
