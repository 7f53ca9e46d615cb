"""The plain reference of the hybrid stack of gated delta-rule linear
attention (KDA), gated softmax attention without positions and gated
routed experts (``solar_open2``): the reference of the configurations
whose ``model_module`` is ``hybrid_delta_moe``
(benchmark/models/hybrid_delta_moe.py calls it). Written from the
published description of the layers in straightforward jax.numpy,
float32, matmuls at precision "highest"; the delta rule is a
SEQUENTIAL recurrence, one token after another, as it is written
below. No kernels, no chunking, no cache, no batching, and nothing
imported from batch_shipyard_tpu.

A published layer is two blocks here, each ONE mixer after ONE norm,
``x <- x + Mixer(RMSNorm(x))`` (learned scale, float32): the layer's
token mixer, then its experts. The mixer by the block's kind:

  attn     gated attention. q_proj -> Hq heads, k_proj / v_proj -> Hkv
           heads of D; causal softmax attention, scale D**-0.5, Hq /
           Hkv query heads to each K/V head, NO positional embedding;
           out = o_proj(attn * sigmoid(gate_proj(x))), the gate
           elementwise over the Hq * D outputs. No biases.
  delta    KDA, H heads of width D. qkv_proj -> q | k | v side by
           side [3 * H*D]; each passes a causal depthwise convolution
           (kernel K, no bias) and silu; q^ = q / |q| * D**-0.5,
           k^ = k / |k| (per head, x * rsqrt(sum x^2 + 1e-6));
           g = -exp(A_log) * softplus(decay_b(decay_a(x)) + dt_bias)
           in R^D a head (A_log one scalar a head, the low-rank decay
           gate d -> r -> H*D); beta = 2 * sigmoid(beta_proj(x)) a
           head; per head, from S_0 = 0,
             S_t = (I - beta_t k^_t k^_t^T) Diag(exp(g_t)) S_{t-1}
                   + beta_t k^_t v_t^T,        o_t = S_t^T q^_t;
           out = o_proj(rmsnorm_head(o_t) * sigmoid(gate_b(gate_a(x))))
           (RMS norm over each head's D channels, one scale [D]).
  experts  s = sigmoid(x W_r) over ALL n router outputs (float32); the
           k largest of s + e_score_correction_bias; weights s_i /
           (sum + 1e-20) * scale; Expert(x) = down(silu(gate x) * up
           x), no bias; output sum_i w_i Expert_i(x) + Shared(x), the
           shared expert of the same form.

Then the final norm and an UNTIED lm_head.

THE CHIP'S SHARE. It is handed the share of the weights that the
configuration holds: ``experts_gate`` / ``experts_up`` /
``experts_down`` are the experts ``first`` .. ``first + E - 1`` of the
router's n, and embedding and head the held vocabulary rows. The
router keeps its n outputs and its k choices; a choice that falls on
an expert not held adds nothing (in the deployment another chip adds
it), here as in the program, and that partial sum goes on to the next
block. The shared expert and the mixers are whole.

Handed ``decisions`` ({layer name: int32 [T, k]}, a row of -1: no
record) it computes the experts it is handed, weighs them by ITS OWN
scores, and returns beside the logits one slack per position and
layer: its own k-th best selection score (s + bias) less the lowest
selection score among the handed ones: 0 when the sets are equal,
never below.

It is handed the benchmark's own seeded weights and upcasts them a
layer (an expert) at a time, so that it fits beside them."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
F32 = jnp.float32


def matmul(a, b):
    """a [..., k] @ b [k, n] in float32."""
    return jnp.matmul(a.astype(F32), b.astype(F32), precision=HIGHEST)


def rmsnorm(x, scale, eps: float):
    x = x.astype(F32)
    return x * jax.lax.rsqrt(
        jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def unit(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True)
                             + 1e-6)


def causal_conv(x, kernel):
    """x [T, C]; kernel [K, C], tap K-1 on the current row; rows
    before the start read as zero."""
    taps = kernel.shape[0]
    kernel = kernel.astype(F32)
    padded = jnp.concatenate(
        [jnp.zeros((taps - 1, x.shape[1]), F32), x.astype(F32)])
    return sum(kernel[k] * padded[k:k + x.shape[0]] for k in range(taps))


def delta_rule(q, k, v, g, beta):
    """The recurrence as written, token by token. q, k (unit), v, g
    [T, H, D]; beta [T, H] -> (o [T, H, D], the last state [H, D, D])."""
    def token(state, row):
        q_t, k_t, v_t, g_t, beta_t = row
        decayed = jnp.exp(g_t)[:, :, None] * state   # Diag(exp g) S
        seen = jnp.einsum("hk,hkv->hv", k_t, decayed, precision=HIGHEST)
        kb = beta_t[:, None] * k_t                   # beta k^
        state = (decayed - kb[:, :, None] * seen[:, None, :]
                 + kb[:, :, None] * v_t[:, None, :])
        return state, jnp.einsum("hkv,hk->hv", state, q_t,
                                 precision=HIGHEST)

    heads, width = q.shape[1:]
    last, o = jax.lax.scan(token, jnp.zeros((heads, width, width), F32),
                           (q, k, v, g, beta))
    return o, last


def kda(h, w, *, heads: int, width: int, eps: float):
    """The delta mixer on normed h [T, d] -> [T, d]."""
    t = h.shape[0]
    mixed = jax.nn.silu(causal_conv(
        matmul(h, w["qkv_proj"]["kernel"]), w["conv_kernel"]))
    q, k, v = (part.reshape(t, heads, width)
               for part in jnp.split(mixed, 3, axis=-1))
    q, k = unit(q) * width ** -0.5, unit(k)
    g = -jnp.exp(w["A_log"].astype(F32))[:, None] * jax.nn.softplus(
        matmul(matmul(h, w["decay_a"]["kernel"]), w["decay_b"]["kernel"])
        + w["dt_bias"].astype(F32)).reshape(t, heads, width)
    beta = 2.0 * jax.nn.sigmoid(matmul(h, w["beta_proj"]["kernel"]))
    o, _last = delta_rule(q, k, v, g, beta)
    o = o * jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True) + eps)
    o = (o * w["norm_scale"].astype(F32)).reshape(t, heads * width)
    gate = jax.nn.sigmoid(matmul(matmul(
        h, w["gate_a"]["kernel"]), w["gate_b"]["kernel"]))
    return matmul(o * gate, w["o_proj"]["kernel"])


def attention(h, w, *, q_heads: int, kv_heads: int):
    """The gated attention mixer on normed h [T, d] -> [T, d]: no
    positions."""
    t = h.shape[0]
    q = matmul(h, w["q_proj"]["kernel"]).reshape(t, q_heads, -1)
    k = matmul(h, w["k_proj"]["kernel"]).reshape(t, kv_heads, -1)
    v = matmul(h, w["v_proj"]["kernel"]).reshape(t, kv_heads, -1)
    k = jnp.repeat(k, q_heads // kv_heads, axis=1)
    v = jnp.repeat(v, q_heads // kv_heads, axis=1)
    scores = jnp.einsum("qhd,khd->hqk", q, k, precision=HIGHEST)
    scores = scores / jnp.sqrt(F32(q.shape[-1]))
    causal = jnp.tril(jnp.ones((t, t), jnp.bool_))
    probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
    out = jnp.einsum("hqk,khd->qhd", probs, v, precision=HIGHEST)
    gate = jax.nn.sigmoid(matmul(h, w["gate_proj"]["kernel"]))
    return matmul(out.reshape(t, -1) * gate, w["o_proj"]["kernel"])


def swiglu(h, gate, up, down):
    return matmul(jax.nn.silu(matmul(h, gate)) * matmul(h, up), down)


def route(h, w, handed, top_k: int, scale: float):
    """-> (the experts used [T, k], their weights [T, k], slack [T]).
    handed int32 [T, k]: a row of -1 takes the reference's own."""
    scores = jax.nn.sigmoid(matmul(h, w["router_kernel"]))
    select = scores + w["e_score_correction_bias"].astype(F32)
    own_select, own = jax.lax.top_k(select, top_k)
    use = jnp.where(handed[:, :1] >= 0, handed, own)
    slack = own_select[:, -1] - jnp.min(
        jnp.take_along_axis(select, use, axis=-1), axis=-1)
    picked = jnp.take_along_axis(scores, use, axis=-1)
    weights = picked / (jnp.sum(picked, axis=-1, keepdims=True)
                        + 1e-20) * scale
    return use, weights, slack


def routed_part(h, w, use, weights, first: int):
    """sum_i w_i Expert_i(h) over the used experts that are HELD
    (first .. first + E - 1), one held expert after another, each over
    every row and weighed 0 where it was not used."""
    def one(total, expert):
        index, gate, up, down = expert
        weight = jnp.sum(jnp.where(use == index, weights, 0.0), axis=-1)
        return total + weight[:, None] * swiglu(h, gate, up, down), None

    held = w["experts_up"].shape[0]
    total, _ = jax.lax.scan(
        one, jnp.zeros_like(h),
        (first + jnp.arange(held), w["experts_gate"], w["experts_up"],
         w["experts_down"]))
    return total


def experts(h, w, handed, *, top_k: int, scale: float, first: int):
    """The experts mixer on normed h [T, d] -> ([T, d], slack [T])."""
    use, weights, slack = route(h, w, handed, top_k, scale)
    shared = swiglu(h, w["shared_gate"], w["shared_up"],
                    w["shared_down"])
    return routed_part(h, w, use, weights, first) + shared, slack


@functools.partial(jax.jit, static_argnames=("heads", "width", "eps"))
def delta_block(x, w, **sizes):
    return x + kda(rmsnorm(x, w["norm"]["scale"], sizes["eps"]),
                   w["delta"], **sizes)


@functools.partial(jax.jit, static_argnames=(
    "q_heads", "kv_heads", "eps"))
def attention_block(x, w, *, eps: float, **sizes):
    return x + attention(rmsnorm(x, w["norm"]["scale"], eps), w["attn"],
                         **sizes)


@functools.partial(jax.jit, static_argnames=(
    "top_k", "scale", "first", "eps"))
def experts_block(x, w, handed, *, eps: float, **sizes):
    out, slack = experts(rmsnorm(x, w["norm"]["scale"], eps),
                         w["experts"], handed, **sizes)
    return x + out, slack


@functools.partial(jax.jit, static_argnames=("eps",))
def head_logits(hidden, final_norm, lm_head, eps: float):
    """Final norm and the untied head: hidden [R, d] -> [R, vocab]."""
    return matmul(rmsnorm(hidden, final_norm["scale"], eps), lm_head)


def teacher_forced_logits(params, tokens, rows, *, kinds: tuple,
                          delta: dict, attn: dict, routed: dict,
                          eps: float, decisions=None):
    """One full forward over ``tokens`` [T] (no cache), a block at a
    time by ``kinds`` (one of attn, delta, experts a block); the
    logits of the positions in ``rows`` -> [len(rows), vocab] float32,
    and with ``decisions`` also {layer name: slack [T]} for the
    experts blocks. ``delta`` / ``attn`` / ``routed`` are the mixers'
    sizes (the keyword arguments of kda / attention / experts)."""
    x = params["embed"]["embedding"][tokens].astype(F32)
    own = jnp.full((tokens.shape[0], routed["top_k"]), -1, jnp.int32)
    slacks = {}
    for i, kind in enumerate(kinds):
        name = f"layer_{i}"
        if kind == "delta":
            x = delta_block(x, params[name], eps=eps, **delta)
        elif kind == "attn":
            x = attention_block(x, params[name], eps=eps, **attn)
        elif kind == "experts":
            x, slacks[name] = experts_block(
                x, params[name], own if decisions is None
                else decisions[name], eps=eps, **routed)
        else:
            raise ValueError(f"no block of kind {kind!r}")
    logits = head_logits(x[rows], params["final_norm"],
                         params["lm_head"]["kernel"], eps)
    return logits if decisions is None else (logits, slacks)
