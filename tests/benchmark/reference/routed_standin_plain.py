"""The plain reference of the ROUTED STAND-IN (tests/benchmark only:
no configuration of BENCHMARK.json names it): a stack of layers, each
``x += out(causal_depthwise_conv1d(RMSNorm(x), kernel 3))`` and then
``x += experts(RMSNorm(x))`` with sigmoid-scored top-k routed SwiGLU
experts: ``s = sigmoid(router(h))``, the k experts of largest
``s + bias``, weights ``s_i / (sum of the k + 1e-6)``; final RMSNorm,
head tied to the embedding. Straightforward jax.numpy, float32, matmuls
at precision "highest"; nothing imported from the program or from the
stand-in's bfloat16 program.

It is the model of what a routed configuration's reference does for
benchmark/check.py: handed ``decisions`` ({layer name: int32 [T, k]},
a row of -1 where there is no record), it computes the experts it is
handed, weighs them by ITS OWN scores, and returns beside the logits
one slack per position and layer: its own k-th best selection score
less the lowest selection score among the handed ones (0 when the
sets are equal, never below)."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


def matmul(a, b):
    return jnp.matmul(a.astype(jnp.float32), b.astype(jnp.float32),
                      precision=HIGHEST)


def rmsnorm(x, scale, eps: float):
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(
        jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def causal_conv3(h, kernel):
    """h [T, d], kernel [3, d] float32: y[t] = k0 h[t-2] + k1 h[t-1] +
    k2 h[t], rows before the start read as zero."""
    kernel = kernel.astype(jnp.float32)
    zeros = jnp.zeros_like(h[:1])
    back1 = jnp.concatenate([zeros, h[:-1]])
    back2 = jnp.concatenate([zeros, zeros, h[:-2]])
    return kernel[0] * back2 + kernel[1] * back1 + kernel[2] * h


def routed_experts(h, w, handed, top_k: int):
    """h [T, d]; handed int32 [T, k] (-1: this row takes the
    reference's own choice) -> (the layer's output [T, d], slack [T])."""
    scores = jax.nn.sigmoid(matmul(h, w["router"]["kernel"]))
    select = scores + w["router"]["bias"].astype(jnp.float32)
    own_select, own = jax.lax.top_k(select, top_k)
    use = jnp.where(handed[:, :1] >= 0, handed, own)
    slack = own_select[:, -1] - jnp.min(
        jnp.take_along_axis(select, use, axis=-1), axis=-1)
    weights = jnp.take_along_axis(scores, use, axis=-1)
    weights = weights / (jnp.sum(weights, axis=-1, keepdims=True)
                         + 1e-6)
    out = jnp.zeros_like(h)
    experts = w["experts"]
    for j in range(top_k):      # one gathered expert per position
        gate = jnp.einsum("td,tdf->tf", h, experts["gate"][use[:, j]]
                          .astype(jnp.float32), precision=HIGHEST)
        up = jnp.einsum("td,tdf->tf", h, experts["up"][use[:, j]]
                        .astype(jnp.float32), precision=HIGHEST)
        down = jnp.einsum(
            "tf,tfd->td", jax.nn.silu(gate) * up,
            experts["down"][use[:, j]].astype(jnp.float32),
            precision=HIGHEST)
        out = out + weights[:, j:j + 1] * down
    return out, slack


@functools.partial(jax.jit, static_argnames=("top_k", "eps"))
def block(x, w, handed, top_k: int, eps: float):
    h = rmsnorm(x, w["mix_norm"]["scale"], eps)
    x = x + matmul(causal_conv3(h, w["mix"]["kernel"]),
                   w["mix"]["out"])
    h = rmsnorm(x, w["moe_norm"]["scale"], eps)
    out, slack = routed_experts(h, w, handed, top_k)
    return x + out, slack


@functools.partial(jax.jit, static_argnames=("eps",))
def head_logits(hidden, final_norm, embedding, eps: float):
    return matmul(rmsnorm(hidden, final_norm["scale"], eps),
                  embedding.T)


def teacher_forced_logits(params, tokens, rows, *, n_layers: int,
                          top_k: int, eps: float, decisions=None):
    """One full forward over ``tokens`` [T], a layer at a time; the
    logits at ``rows`` -> [len(rows), vocab] float32, and with
    ``decisions`` also {layer name: slack [T]}."""
    embedding = params["embed"]["embedding"]
    x = embedding[tokens].astype(jnp.float32)
    own = jnp.full((tokens.shape[0], top_k), -1, jnp.int32)
    slacks = {}
    for i in range(n_layers):
        name = f"layer_{i}"
        x, slacks[name] = block(
            x, params[name], own if decisions is None
            else decisions[name], top_k, eps)
    logits = head_logits(x[rows], params["final_norm"], embedding, eps)
    return logits if decisions is None else (logits, slacks)
