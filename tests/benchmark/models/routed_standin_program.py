"""The routed stand-in's PROGRAM (tests/benchmark only): the layers of
reference/routed_standin_plain.py written again in bfloat16 as a
serving program computes them, one position a step with the last two
rows of each layer's convolution input as its state, greedy. It yields
its tokens AND the experts it chose, which is what an engine's
``take_decisions`` hands the check. It shares no code with the
reference.

``options`` are the faults and the lower-precision path the tests
switch on: ``weigh="softmax"`` (the weights of the chosen experts by
a softmax of the router's outputs, where the published equations
normalise their sigmoids: another equation), ``reroute_share`` (that
share of positions' last expert goes to a random one, and the program
computes with it), ``state_bits=8 | 4`` (the convolution state kept
as integers of that many bits with one scale a row: the stand-in's
lower-precision path, as the int8 KV cache is the program's)."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

BF16 = jnp.bfloat16


def _norm(x, scale, eps):
    x32 = x.astype(jnp.float32)
    return (x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, -1, keepdims=True)
                                + eps) * scale).astype(BF16)


def _integer_round_trip(rows, bits: int):
    top = 2.0 ** (bits - 1) - 1
    scale = jnp.max(jnp.abs(rows.astype(jnp.float32)), -1,
                    keepdims=True) / top + 1e-12
    return (jnp.round(rows.astype(jnp.float32) / scale)
            * scale).astype(BF16)


@functools.partial(jax.jit, static_argnames=(
    "n_layers", "top_k", "eps", "weigh", "reroute_share", "state_bits"))
def step(params, tokens, state, key, *, n_layers, top_k, eps,
         weigh="sigmoid", reroute_share=0.0, state_bits=None):
    """tokens [B] at one position each; state {layer: [B, 2, d]} ->
    (greedy next tokens [B], new state, choices [n_layers, B, k])."""
    embedding = params["embed"]["embedding"]
    x = embedding[tokens]
    new_state, choices = {}, []
    for i in range(n_layers):
        name = f"layer_{i}"
        w = params[name]
        h = _norm(x, w["mix_norm"]["scale"], eps)
        kernel = w["mix"]["kernel"]
        mixed = (kernel[0] * state[name][:, 0] + kernel[1]
                 * state[name][:, 1] + kernel[2] * h)
        kept = jnp.stack([state[name][:, 1], h], axis=1)
        new_state[name] = (_integer_round_trip(kept, state_bits)
                           if state_bits else kept)
        x = x + mixed @ w["mix"]["out"]
        h = _norm(x, w["moe_norm"]["scale"], eps)
        routed = h @ w["router"]["kernel"]
        scores = jax.nn.sigmoid(routed)
        _best, chosen = jax.lax.top_k(
            scores + w["router"]["bias"].astype(BF16), top_k)
        if reroute_share:
            roll, where = jax.random.split(jax.random.fold_in(key, i))
            other = jax.random.randint(roll, chosen[:, -1].shape, 0,
                                       scores.shape[-1])
            hit = jax.random.uniform(where, other.shape) < reroute_share
            hit = hit & ~jnp.any(chosen == other[:, None], axis=-1)
            chosen = chosen.at[:, -1].set(
                jnp.where(hit, other, chosen[:, -1]))
        if weigh == "softmax":
            weights = jax.nn.softmax(
                jnp.take_along_axis(routed, chosen, axis=-1), axis=-1)
        else:
            picked = jnp.take_along_axis(scores, chosen, axis=-1)
            weights = picked / (jnp.sum(picked, -1, keepdims=True)
                                + 1e-6)
        experts = w["experts"]
        gate = jnp.einsum("bd,bkdf->bkf", h, experts["gate"][chosen])
        up = jnp.einsum("bd,bkdf->bkf", h, experts["up"][chosen])
        down = jnp.einsum("bkf,bkfd->bkd", jax.nn.silu(gate) * up,
                          experts["down"][chosen])
        x = x + jnp.sum(weights[..., None] * down, axis=1)
        choices.append(chosen)
    logits = _norm(x, params["final_norm"]["scale"], eps) @ embedding.T
    return (jnp.argmax(logits, axis=-1).astype(jnp.int32), new_state,
            jnp.stack(choices))


def empty_state(params, n_layers: int, batch: int) -> dict:
    width = params["embed"]["embedding"].shape[1]
    return {f"layer_{i}": jnp.zeros((batch, 2, width), BF16)
            for i in range(n_layers)}


def generate(params, prompts, new_tokens: int, *, n_layers, top_k, eps,
             seed: int = 0, **options):
    """prompts [B, P] -> (served tokens [B, new_tokens], {layer name:
    choices [B, P + new_tokens - 1, k]}): every position through
    ``step``, the prompt's tokens forced, then the program's own."""
    prompt_len = prompts.shape[1]

    def body(carry, at):
        state, last = carry
        tokens = jnp.where(at < prompt_len,
                           prompts[:, jnp.minimum(at, prompt_len - 1)],
                           last)
        out, state, chosen = step(
            params, tokens, state, jax.random.fold_in(
                jax.random.PRNGKey(seed), at),
            n_layers=n_layers, top_k=top_k, eps=eps, **options)
        return (state, out), (out, chosen)

    start = (empty_state(params, n_layers, prompts.shape[0]),
             prompts[:, 0])
    _end, (tokens, chosen) = jax.lax.scan(
        body, start, jnp.arange(prompt_len + new_tokens - 1))
    served = tokens[prompt_len - 1:].T
    return served, {f"layer_{i}": jnp.transpose(chosen[:, i], (1, 0, 2))
                    for i in range(n_layers)}
