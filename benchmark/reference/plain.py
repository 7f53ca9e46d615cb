"""The plain reference of the dense decoder block (multi-head
attention with rotary positions in the half-split layout, RMSNorm
before attention and before the MLP, SwiGLU, no biases): the reference
of the configurations whose ``model_module`` is ``dense_mha``
(benchmark/models/dense_mha.py calls it); another architecture brings
a file of its own beside this one. Written from the block's equations
in straightforward jax.numpy, float32, matmuls at precision "highest".
No kernels, no cache, no batching, and nothing imported from
batch_shipyard_tpu.

Departure from the published Baichuan-7B, followed here because the
program makes it: the output head is the TRANSPOSED EMBEDDING (tied),
where the published model has a separate lm_head.

It is handed the benchmark's own seeded weights (benchmark/weights.py)
and upcasts them a layer at a time, so that it fits beside them."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


def matmul(a, b):
    """a [..., k] @ b [k, n] in float32."""
    return jnp.matmul(a.astype(jnp.float32), b.astype(jnp.float32),
                      precision=HIGHEST)


def rmsnorm(x, scale, eps: float):
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(
        jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def rope(x, positions, theta: float):
    """x [T, H, D], positions [T]: rotate the two halves of D."""
    depth = x.shape[-1]
    freqs = jnp.exp(-jnp.log(theta) * jnp.arange(
        0, depth, 2, dtype=jnp.float32) / depth)
    angles = positions.astype(jnp.float32)[:, None] * freqs
    cos = jnp.cos(angles)[:, None, :]
    sin = jnp.sin(angles)[:, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)


def attention(q, k, v):
    """Causal softmax attention. q, k, v [T, H, D] -> [T, H, D]."""
    t = q.shape[0]
    scores = jnp.einsum("qhd,khd->hqk", q, k, precision=HIGHEST)
    scores = scores / jnp.sqrt(jnp.float32(q.shape[-1]))
    causal = jnp.tril(jnp.ones((t, t), jnp.bool_))
    scores = jnp.where(causal, scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("hqk,khd->qhd", probs, v, precision=HIGHEST)


@functools.partial(jax.jit, static_argnames=("n_heads", "eps", "theta"))
def block(x, w, positions, n_heads: int, eps: float, theta: float):
    """One decoder block. x [T, d] float32; w the block's weights as
    the parameter tree names them."""
    t = x.shape[0]
    h = rmsnorm(x, w["attn_norm"]["scale"], eps)
    att = w["attn"]
    q = matmul(h, att["q_proj"]["kernel"]).reshape(t, n_heads, -1)
    k = matmul(h, att["k_proj"]["kernel"]).reshape(t, n_heads, -1)
    v = matmul(h, att["v_proj"]["kernel"]).reshape(t, n_heads, -1)
    out = attention(rope(q, positions, theta),
                    rope(k, positions, theta), v).reshape(t, -1)
    x = x + matmul(out, att["o_proj"]["kernel"])
    h = rmsnorm(x, w["mlp_norm"]["scale"], eps)
    mlp = w["mlp"]
    gate = matmul(h, mlp["gate_proj"]["kernel"])
    up = matmul(h, mlp["up_proj"]["kernel"])
    return x + matmul(jax.nn.silu(gate) * up,
                      mlp["down_proj"]["kernel"])


@functools.partial(jax.jit, static_argnames=("eps",))
def head_logits(hidden, final_norm, embedding, eps: float):
    """Final norm and the tied head: hidden [R, d] -> [R, vocab]."""
    return matmul(rmsnorm(hidden, final_norm["scale"], eps),
                  embedding.T)


def teacher_forced_logits(params, tokens, rows, *, n_layers: int,
                          n_heads: int, eps: float, theta: float):
    """One full forward over ``tokens`` [T] (no cache), a layer at a
    time; the logits of the positions in ``rows`` -> [len(rows),
    vocab] float32. T and len(rows) should come from few buckets: each
    distinct pair compiles once."""
    positions = jnp.arange(tokens.shape[0], dtype=jnp.int32)
    embedding = params["embed"]["embedding"]
    x = embedding[tokens].astype(jnp.float32)
    for i in range(n_layers):
        x = block(x, params[f"layer_{i}"], positions, n_heads, eps,
                  theta)
    return head_logits(x[rows], params["final_norm"], embedding, eps)
