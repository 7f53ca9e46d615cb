"""The plain reference of the hybrid state-space / attention /
routed-expert stack (``nemotron_h``): the reference of the
configurations whose ``model_module`` is ``hybrid_ssm_moe``
(benchmark/models/hybrid_ssm_moe.py calls it). Written from the
published block equations in straightforward jax.numpy, float32,
matmuls at precision "highest"; the state-space layer is a SEQUENTIAL
recurrence, one token after another. No kernels, no chunking, no
cache, no batching, and nothing imported from batch_shipyard_tpu.

Every block is ONE mixer after ONE norm, ``x <- x + Mixer(RMSNorm(x))``
(learned scale, float32), the mixer by the block's letter:

  M  Mamba-2. in_proj -> z [H*P], xBC [H*P + 2*G*N], dt [H];
     xBC <- silu(causal depthwise conv, kernel K, + bias); split x
     [H, P], B [G, N], C [G, N], head h reading group h // (H/G);
     dt <- softplus(dt + dt_bias) (no clamp), A = -exp(A_log);
     S_t = exp(dt_t A) S_{t-1} + dt_t x_t (x) B_t, y_t = S_t C_t +
     D x_t; y <- y * silu(z), RMS-normalised over each group of
     H*P / G channels, times a scale; out_proj. No biases but the
     convolution's.
  *  attention. q_proj -> Hq heads, k_proj / v_proj -> Hkv heads of
     D; causal softmax attention, scale 1/sqrt(D), Hq / Hkv query
     heads to each K/V head; o_proj. NO positional embedding.
  E  experts. s = sigmoid(x W_r) over ALL n router outputs (float32);
     the k largest of s + e_score_correction_bias; weights s_i / sum
     * scale; Expert(x) = down(relu(up x)^2), no gate, no bias; output
     sum_i w_i Expert_i(x) + Shared(x).

Then the final norm and an UNTIED lm_head.

THE CHIP'S SHARE. It is handed the share of the weights that the
configuration holds: ``experts_up`` / ``experts_down`` are the experts
``first`` .. ``first + E - 1`` of the router's n, and embedding and
head the held vocabulary rows. The router keeps its n outputs and its
k choices; a choice that falls on an expert not held adds nothing (in
the deployment another chip adds it), here as in the program, and that
partial sum goes on to the next block. The shared expert is whole.

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


def relu2(x):
    return jnp.square(jax.nn.relu(x))


def causal_conv(x, kernel, bias):
    """x [T, C]; kernel [K, C], tap K-1 on the current row; rows
    before the start read as zero."""
    taps = kernel.shape[0]
    kernel = kernel.astype(F32)
    padded = jnp.concatenate(
        [jnp.zeros((taps - 1, x.shape[1]), F32), x.astype(F32)])
    return bias.astype(F32) + sum(
        kernel[k] * padded[k:k + x.shape[0]] for k in range(taps))


def mamba(h, w, *, heads: int, width: int, groups: int, n_state: int,
          eps: float):
    """The M mixer on normed h [T, d] -> [T, d]."""
    t = h.shape[0]
    d_inner = heads * width
    projected = matmul(h, w["in_proj"]["kernel"])
    z = projected[:, :d_inner]
    xbc = projected[:, d_inner:d_inner + d_inner + 2 * groups * n_state]
    dt = projected[:, -heads:]
    xbc = jax.nn.silu(causal_conv(xbc, w["conv_kernel"], w["conv_bias"]))
    x = xbc[:, :d_inner].reshape(t, heads, width)
    per = heads // groups
    b = jnp.repeat(xbc[:, d_inner:d_inner + groups * n_state].reshape(
        t, groups, n_state), per, axis=1)               # [T, H, N]
    c = jnp.repeat(xbc[:, d_inner + groups * n_state:].reshape(
        t, groups, n_state), per, axis=1)
    dt = jax.nn.softplus(dt + w["dt_bias"].astype(F32))  # [T, H]
    a = -jnp.exp(w["A_log"].astype(F32))                 # [H]

    def token(state, row):
        x_t, b_t, c_t, dt_t = row
        state = (jnp.exp(dt_t * a)[:, None, None] * state
                 + (dt_t[:, None] * x_t)[:, :, None] * b_t[:, None, :])
        return state, jnp.einsum("hpn,hn->hp", state, c_t,
                                 precision=HIGHEST)

    _last, y = jax.lax.scan(
        token, jnp.zeros((heads, width, n_state), F32), (x, b, c, dt))
    y = y + w["D"].astype(F32)[:, None] * x
    y = y.reshape(t, d_inner) * jax.nn.silu(z)
    y = y.reshape(t, groups, d_inner // groups)
    y = y * jax.lax.rsqrt(jnp.mean(y * y, axis=-1, keepdims=True) + eps)
    y = y.reshape(t, d_inner) * w["norm_scale"].astype(F32)
    return matmul(y, w["out_proj"]["kernel"])


def attention(h, w, *, q_heads: int, kv_heads: int):
    """The * mixer on normed h [T, d] -> [T, d]: no positions."""
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
    return matmul(out.reshape(t, -1), w["o_proj"]["kernel"])


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
        index, up, down = expert
        weight = jnp.sum(jnp.where(use == index, weights, 0.0), axis=-1)
        return total + weight[:, None] * matmul(
            relu2(matmul(h, up)), down), None

    held = w["experts_up"].shape[0]
    total, _ = jax.lax.scan(
        one, jnp.zeros_like(h),
        (first + jnp.arange(held), w["experts_up"], w["experts_down"]))
    return total


def experts(h, w, handed, *, top_k: int, scale: float, first: int):
    """The E mixer on normed h [T, d] -> ([T, d], slack [T])."""
    use, weights, slack = route(h, w, handed, top_k, scale)
    shared = matmul(relu2(matmul(h, w["shared_up"])), w["shared_down"])
    return routed_part(h, w, use, weights, first) + shared, slack


@functools.partial(jax.jit, static_argnames=(
    "heads", "width", "groups", "n_state", "eps"))
def mamba_block(x, w, **sizes):
    return x + mamba(rmsnorm(x, w["norm"]["scale"], sizes["eps"]),
                     w["ssm"], **sizes)


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


def teacher_forced_logits(params, tokens, rows, *, pattern: str,
                          ssm: dict, attn: dict, routed: dict,
                          eps: float, decisions=None):
    """One full forward over ``tokens`` [T] (no cache), a block at a
    time by ``pattern`` (one letter a block: M, *, E); the logits of
    the positions in ``rows`` -> [len(rows), vocab] float32, and with
    ``decisions`` also {layer name: slack [T]} for the E blocks.
    ``ssm`` / ``attn`` / ``routed`` are the mixers' sizes (the keyword
    arguments of mamba / attention / experts)."""
    x = params["embed"]["embedding"][tokens].astype(F32)
    own = jnp.full((tokens.shape[0], routed["top_k"]), -1, jnp.int32)
    slacks = {}
    for i, kind in enumerate(pattern):
        name = f"layer_{i}"
        if kind == "M":
            x = mamba_block(x, params[name], eps=eps, **ssm)
        elif kind == "*":
            x = attention_block(x, params[name], eps=eps, **attn)
        elif kind == "E":
            x, slacks[name] = experts_block(
                x, params[name], own if decisions is None
                else decisions[name], eps=eps, **routed)
        else:
            raise ValueError(f"no block of kind {kind!r}")
    logits = head_logits(x[rows], params["final_norm"],
                         params["lm_head"]["kernel"], eps)
    return logits if decisions is None else (logits, slacks)
