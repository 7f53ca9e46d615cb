"""Autoregressive inference: KV-cache decode + sampling.

The framework's serving-side counterpart to the training path
(ROADMAP item; the reference had no inference story at all). Design:

  - prefill: ONE jitted full-sequence forward over the prompt writing
    all KV-cache rows in a single MXU-batched pass (the multi-token
    insert path of transformer._decode_attend) — prefill cost is one
    forward, not T_prompt sequential micro-steps;
  - decode: one token per step through the transformer's decode mode
    (flax 'cache' collection holding per-layer K/V + write index),
    inside a single jitted lax.scan — no per-token Python dispatch;
  - sampling: greedy, temperature, and top-k, driven by a jax PRNG key.

Works on CPU/TPU and under dp sharding (batch dim); cache lives on
device for the whole generation.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp

from batch_shipyard_tpu.models import delta, ssm
from batch_shipyard_tpu.models import transformer as tfm


@dataclasses.dataclass(frozen=True)
class SamplingConfig:
    temperature: float = 0.0   # 0 => greedy
    top_k: int = 0             # 0 => full distribution


def decode_config(config: tfm.TransformerConfig,
                  max_decode_len: int) -> tfm.TransformerConfig:
    return dataclasses.replace(
        config, decode=True, max_decode_len=max_decode_len,
        attention_fn=None, remat=False)


def init_cache(model: tfm.TransformerLM, params, batch_size: int):
    """Materialize an empty KV cache pytree for the decode model.

    model.init runs a forward pass, which WRITES the dummy token into
    slot 0 and bumps the index — zero everything so the cache starts
    truly empty."""
    variables = model.init(
        jax.random.PRNGKey(0),
        jnp.zeros((batch_size, 1), jnp.int32),
        positions=jnp.zeros((1,), jnp.int32))
    return jax.tree_util.tree_map(jnp.zeros_like, variables["cache"])


@functools.partial(jax.jit, static_argnames=("model", "batch_size"))
def empty_cache(model: tfm.TransformerLM, batch_size: int):
    """init_cache as ONE compiled program (the model static): the same
    zeros, without model.init's hundreds of eager operations, each of
    which compiles by itself the first time a process meets its shape
    (twelve seconds for a stack of ten blocks on a CPU, and a second
    set of parameters and of the cache on the device before they are
    thrown away). What a serving engine builds its cache with; the
    prefill programs, which are traced already, keep init_cache."""
    return init_cache(model, None, batch_size)


def _sample(logits, key, sampling: SamplingConfig):
    """logits: [B, vocab] fp32 -> token ids [B]."""
    if sampling.temperature <= 0.0:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    logits = logits / sampling.temperature
    if sampling.top_k > 0:
        top_vals, _ = jax.lax.top_k(logits, sampling.top_k)
        cutoff = top_vals[:, -1][:, None]
        logits = jnp.where(logits < cutoff, -1e30, logits)
    return jax.random.categorical(key, logits, axis=-1).astype(
        jnp.int32)


@functools.partial(jax.jit, static_argnames=(
    "model", "num_tokens", "sampling"))
def generate(model: tfm.TransformerLM, params, cache, prompt,
             num_tokens: int, key,
             sampling: SamplingConfig = SamplingConfig()):
    """Generate num_tokens continuations of prompt [B, T_prompt].

    Returns (tokens [B, T_prompt + num_tokens], cache). The whole
    prefill + decode runs inside one jit; per-token work is a lax.scan
    step feeding the KV cache.
    """
    batch, prompt_len = prompt.shape

    def step(carry, _):
        cache, token, pos, key = carry
        logits, mutated = model.apply(
            {"params": params, "cache": cache}, token,
            positions=pos[None], mutable=["cache"])
        key, sample_key = jax.random.split(key)
        next_token = _sample(logits[:, 0].astype(jnp.float32),
                             sample_key, sampling)
        return ((mutated["cache"], next_token[:, None], pos + 1, key),
                next_token)

    # Prefill: ONE full-sequence forward through the multi-token
    # cache-insert path (transformer._decode_attend seq > 1) — all
    # prompt K/V land in the cache in a single MXU-batched pass
    # instead of a T_prompt-step scan. Only the last position's
    # logits are needed, so return_hidden + a [B, d] x [d, vocab]
    # matmul avoids materializing [B, T, vocab] fp32 logits.
    hidden, mutated = model.apply(
        {"params": params, "cache": cache}, prompt,
        return_hidden=True, mutable=["cache"])
    cache = mutated["cache"]
    pos = jnp.int32(prompt_len)
    last_logits = tfm.output_logits(model.config, params, hidden[:, -1])
    key, sample_key = jax.random.split(key)
    first = _sample(last_logits, sample_key, sampling)
    (cache, _tok, _pos, _key), generated = jax.lax.scan(
        step, (cache, first[:, None], pos, key), None,
        length=num_tokens - 1)
    tokens = jnp.concatenate(
        [prompt, first[:, None],
         jnp.moveaxis(generated, 0, 1)], axis=1)
    return tokens, cache


def _rewind_cache(cache, steps):
    """Roll every layer's write index back by ``steps`` (scalar or
    [B]). Entries beyond the index are masked by _decode_attend and
    overwritten by the next insert, so the index IS the cache state —
    rewinding it un-commits speculated tokens in O(1). The paged
    cache's per-slot write cursor is its "length" leaf; rewinding it
    un-commits the same way (pages stay allocated, the next insert
    overwrites)."""
    return _map_cursors(lambda leaf: leaf - steps, cache)


def _park_idle_cursors(cache, active):
    """Set the write cursor of every slot that holds no request
    (``active`` [B] false) to 0, in every layer. A decode kernel skips
    key blocks by the cursor (ops/paged_attention.py,
    ops/decode_attention.py), and an idle slot stays in the full-batch
    step: left alone its cursor keeps the last request's length and
    grows by one a step, and the kernel attends over that many rows of
    the scratch page for nothing. Parked, the next step writes the
    slot's one garbage row at offset 0; a dense cache's kernel then
    sees length 1, a paged pool's length 0 (the step's same mask
    reaches its attention call as ``live``:
    transformer.Attention._decode_attend_paged), which costs it
    nothing. Admission's prefill sets the cursor to the prompt's
    length before the slot is read again. Per-slot state without a
    cursor key is left alone."""
    return _map_cursors(lambda leaf: jnp.where(active, leaf, 0), cache)


# Cache leaves that are a layer's fixed-size state per slot: a slot
# row, no cursor, no pages. Every stateful mixer declares its own
# (transformer.STATEFUL_KINDS).
SLOT_STATE_LEAVES = ssm.STATE_LEAVES + delta.STATE_LEAVES


def slot_state_bytes(cache) -> int:
    """Bytes of per-slot state ONE slot holds, over all layers."""
    leaves = jax.tree_util.tree_flatten_with_path(cache)[0]
    return sum(leaf.nbytes // leaf.shape[0] for path, leaf in leaves
               if getattr(path[-1], "key", None) in SLOT_STATE_LEAVES)


# Cache leaves whose FIRST axis is the pool's page ids: K and V pages
# (and an int8 pool's scales) of an attention layer, the one leaf of
# latent rows of a latent layer (transformer.LatentAttention).
POOL_LEAVES = ("k_pages", "v_pages", "k_page_scales", "v_page_scales",
               "kv_pages")


def pool_page_bytes(cache) -> int:
    """Bytes ONE page id names over all pooled layers, read off the
    leaves as they are (their row's width and type, whatever kind of
    attention wrote them: Hkv * D twice, a latent row once), the
    scratch page counted as a page. A layer's ring and a per-slot
    state are of no page and are not counted."""
    leaves = jax.tree_util.tree_flatten_with_path(cache)[0]
    return sum(leaf.nbytes // leaf.shape[0] for path, leaf in leaves
               if getattr(path[-1], "key", None) in POOL_LEAVES)


def _map_cursors(fn, cache):
    """Apply ``fn`` to every layer's per-slot write cursor: the dense
    cache's "index" leaf, the paged cache's "length" leaf."""
    def fix(path, leaf):
        if path and getattr(path[-1], "key", None) in ("index",
                                                       "length"):
            return fn(leaf)
        return leaf
    return jax.tree_util.tree_map_with_path(fix, cache)


@functools.partial(jax.jit, static_argnames=(
    "target_model", "draft_model", "num_tokens", "gamma"))
def speculative_generate(target_model: tfm.TransformerLM,
                         target_params,
                         draft_model: tfm.TransformerLM,
                         draft_params,
                         prompt, num_tokens: int, gamma: int = 4):
    """Speculative decoding (Leviathan et al.): a cheap DRAFT model
    proposes ``gamma`` tokens autoregressively; the TARGET model
    scores the whole block in ONE MXU-batched forward through the
    multi-token cache-insert path and commits the longest validated
    prefix plus one target token. Greedy acceptance: outputs are
    BIT-IDENTICAL to target-only greedy decoding (the equivalence the
    tests pin), while the target runs a forward every ~(accepted+1)
    tokens instead of every token — the serving latency lever when
    the target is much larger than the draft.

    Batched: acceptance is synchronized to the batch MINIMUM each
    round. That is still exact per slot — a slot that could have
    accepted more receives the same tokens via the target's
    correction logits — it only costs throughput, never correctness
    (and keeps every shape static for jit).

    prompt: [B, P] int32 (P >= 1). Returns (tokens [B, P+num_tokens],
    stats dict: rounds, proposed, accepted — acceptance rate =
    accepted / proposed).

    Cache bookkeeping invariant: each model's cache holds every
    committed token EXCEPT the newest (``y``); each round feeds
    [y, d_1..d_gamma], so both caches advance gamma+1 and rewind by
    gamma - accepted (see _rewind_cache).
    """
    batch, prompt_len = prompt.shape
    cap = num_tokens + gamma + 1

    t_cache = init_cache(target_model, target_params, batch)
    d_cache = init_cache(draft_model, draft_params, batch)
    if prompt_len > 1:
        # Prefill both caches with prompt[:-1]; the last prompt token
        # is the first pending y.
        _, mut = target_model.apply(
            {"params": target_params, "cache": t_cache},
            prompt[:, :-1], return_hidden=True, mutable=["cache"])
        t_cache = mut["cache"]
        _, mut = draft_model.apply(
            {"params": draft_params, "cache": d_cache},
            prompt[:, :-1], return_hidden=True, mutable=["cache"])
        d_cache = mut["cache"]
    y0 = prompt[:, -1]

    t_embed = target_params["embed"]["embedding"]
    d_embed = draft_params["embed"]["embedding"]

    def draft_step(carry, _):
        cache, token, pos = carry
        hidden, mut = draft_model.apply(
            {"params": draft_params, "cache": cache}, token[:, None],
            return_hidden=True, positions=pos[None],
            mutable=["cache"])
        logits = jnp.dot(hidden[:, 0].astype(jnp.float32),
                         d_embed.astype(jnp.float32).T)
        nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return (mut["cache"], nxt, pos + 1), nxt

    def round_body(state):
        t_cache, d_cache, out, n_done, y, rounds, proposed, accepted \
            = state
        pos_y = prompt_len + n_done - 1
        # Draft proposes d_1..d_gamma (the final extra step only
        # inserts d_gamma's K/V so the draft cache can keep pace when
        # everything is accepted).
        (d_cache, _, _), drafts = jax.lax.scan(
            draft_step, (d_cache, y, pos_y), None, length=gamma + 1)
        d_tok = jnp.moveaxis(drafts, 0, 1)[:, :gamma]      # [B, g]
        # Target scores [y, d_1..d_gamma] in one forward.
        x_blk = jnp.concatenate([y[:, None], d_tok], axis=1)
        positions = pos_y + jnp.arange(gamma + 1, dtype=jnp.int32)
        hidden, mut = target_model.apply(
            {"params": target_params, "cache": t_cache}, x_blk,
            return_hidden=True, positions=positions,
            mutable=["cache"])
        t_cache = mut["cache"]
        logits = jnp.einsum("bsd,vd->bsv",
                            hidden.astype(jnp.float32),
                            t_embed.astype(jnp.float32))
        t_tok = jnp.argmax(logits, axis=-1).astype(
            jnp.int32)                                      # [B, g+1]
        # Longest validated prefix, synchronized to the batch min.
        match = (d_tok == t_tok[:, :gamma])
        a_slot = jnp.sum(jnp.cumprod(
            match.astype(jnp.int32), axis=1), axis=1)       # [B]
        a = jnp.min(a_slot)
        # Commit d_1..d_a plus the target's token at position a
        # (correction when a < gamma, bonus when a == gamma — same
        # formula either way).
        js = jnp.arange(gamma + 1, dtype=jnp.int32)
        d_pad = jnp.concatenate(
            [d_tok, jnp.zeros((batch, 1), jnp.int32)], axis=1)
        block = jnp.where(js[None, :] < a, d_pad, t_tok)
        out = jax.lax.dynamic_update_slice(out, block, (0, n_done))
        rewind = gamma - a
        return (_rewind_cache(t_cache, rewind),
                _rewind_cache(d_cache, rewind),
                out, n_done + a + 1, block[:, a],
                rounds + 1, proposed + gamma, accepted + a)

    def cond(state):
        return state[3] < num_tokens

    out0 = jnp.zeros((batch, cap), jnp.int32)
    (t_cache, d_cache, out, n_done, _y, rounds, proposed, accepted
     ) = jax.lax.while_loop(
        cond, round_body,
        (t_cache, d_cache, out0, jnp.int32(0), y0,
         jnp.int32(0), jnp.int32(0), jnp.int32(0)))
    tokens = jnp.concatenate([prompt, out[:, :num_tokens]], axis=1)
    stats = {"rounds": rounds, "proposed": proposed,
             "accepted": accepted}
    return tokens, stats


def make_speculative_decoder(target_config: tfm.TransformerConfig,
                             target_params,
                             draft_config: tfm.TransformerConfig,
                             draft_params, max_decode_len: int,
                             gamma: int = 4):
    """(run, target_model, draft_model) bound to decode-mode models.
    run(prompt, num_tokens) -> (tokens, stats)."""
    for name, cfg in (("target", target_config),
                      ("draft", draft_config)):
        if getattr(cfg, "kv_page_size", None):
            raise ValueError(
                f"speculative decoding needs the dense KV cache "
                f"(multi-token verify + O(1) index rewind); {name} "
                f"config sets kv_page_size={cfg.kv_page_size} — "
                f"clear it for the speculative path")
    t_model = tfm.TransformerLM(
        decode_config(target_config, max_decode_len))
    d_model = tfm.TransformerLM(
        decode_config(draft_config, max_decode_len))

    def run(prompt, num_tokens: int):
        return speculative_generate(
            t_model, target_params, d_model, draft_params, prompt,
            num_tokens, gamma=gamma)

    return run, t_model, d_model


def make_decoder(config: tfm.TransformerConfig, params,
                 max_decode_len: int):
    """Convenience: (generate_fn, model) bound to a decode-mode model
    sharing training params."""
    dconfig = decode_config(config, max_decode_len)
    model = tfm.TransformerLM(dconfig)

    def run(prompt, num_tokens, key,
            sampling: SamplingConfig = SamplingConfig()):
        cache = empty_cache(model, prompt.shape[0])
        return generate(model, params, cache, prompt, num_tokens, key,
                        sampling)

    return run, model
