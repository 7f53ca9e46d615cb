"""Gated delta-rule mixer with a decay per channel (KDA): a
linear-attention layer whose per-sequence memory is a FIXED-SIZE
matrix a head, rewritten along one key direction a token instead of
growing by a row.

Per token t and head (H heads, keys and values of width D):

    q, k, v = silu(causal_depthwise_conv(x W_qkv, kernel K))
    q^ = q / |q| * D**-0.5,  k^ = k / |k|          (eps 1e-6 inside)
    g  = -exp(A_log) * softplus(W_f2 (W_f1 x) + dt_bias)   in R^D
    beta = 2 * sigmoid(w_b x)        (eigenvalue 1 - beta in (-1, 1))
    S_t = (I - beta k^ k^T) Diag(exp(g)) S_{t-1} + beta k^ v^T
    o_t = S_t^T q^                                  S in R^{D x D}
    out = W_o (rmsnorm_head(o_t) * sigmoid(W_g2 (W_g1 x)))

Where models/ssm.py's recurrence is diagonal (a scalar decay a head,
so a cumulative sum runs it over many tokens), this one multiplies the
state by a matrix a token. One token is a rank-1 read-modify-write of
the state (``delta_step``). Many tokens run in chunks (``delta_scan``,
the WY form): inside a chunk the pseudo-values u_t = beta_t (v_t -
S_{t-1}^T Diag(exp(g_t)) k^_t) obey a unit lower-triangular system,

    (I + diag(beta) strict_lower(A)) U = diag(beta) (V - K+ S_0),
    A[t, i] = sum_c k^_t[c] k^_i[c] exp(G_t[c] - G_i[c])

(G the running sum of g inside the chunk, K+_t = exp(G_t) k^_t, S_0
the state before the chunk), solved once for the right-hand sides V
and K+ together; then S_t = exp(G_t) S_0 + sum_{i<=t} exp(G_t - G_i)
k^_i u_i^T gives every row's read-out and the chunk's end state, and
a lax.scan carries the state from chunk to chunk.

THE DECAYS ARE TAKEN AS MASKED PAIRWISE DIFFERENCES, G_t - G_i for
i <= t, never as exp(G_t) * exp(-G_i): under a strong decay (A_log of
a few units, hundreds of nats a chunk) exp(-G_i) overflows float32
while every difference that is used is <= 0. The price is a
[chunk, chunk, D] product a head on the vector unit where a matmul
would do; sub-chunks with a reference point each would buy the matmul
back and are not written.

Serving keeps, per slot and layer, the state S ([H, D, D], float32
unless DeltaConfig.state_dtype says otherwise) and ONE convolution
tail for q, k and v side by side (the last K-1 rows of x W_qkv), in
the flax "cache" collection beside the attention layers' K/V: a slot
row and NO cursor, as models/ssm.py's. Hence ``valid_len``: past the
sequence's own tokens beta = 0 and g = 0, so a prefill padded to its
compile bucket leaves state and tail at the prompt's last own token.

Plain JAX throughout; everything that touches the state is float32,
its matmuls at precision "highest" (on a TPU the default would round
their operands to bfloat16, which is the rounding the state's own
dtype is there to avoid).
"""

from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
from flax import linen as nn

from batch_shipyard_tpu.models.ssm import causal_conv

HIGHEST = jax.lax.Precision.HIGHEST


@dataclasses.dataclass(frozen=True)
class DeltaConfig:
    n_heads: int = 64
    head_dim: int = 128          # of keys and of values
    conv_kernel: int = 4
    gate_rank: int = 128         # inner width of the two low-rank gates
    chunk: int = 64
    # What the per-slot state S is KEPT in between calls (it is always
    # advanced in float32). bfloat16 halves a decode step's state
    # traffic and rounds the state once a token.
    state_dtype: Any = jnp.float32

    @property
    def d_inner(self) -> int:
        return self.n_heads * self.head_dim


# The cache leaves a mixer keeps per slot: a slot row, no cursor, no
# pages (models/inference.slot_state_bytes, serving._seat_state).
STATE_LEAVES = ("delta_state", "qkv_tail")


def unit(x, eps: float = 1e-6):
    """x / |x| over the last axis, float32."""
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + eps)


def delta_step(q, k, v, g, beta, state):
    """One token. q, k (unit), v, g [B, H, D] float32; beta [B, H];
    state [B, H, D(k), D(v)] float32 -> (o [B, H, D], new state)."""
    decayed = state * jnp.exp(g)[..., None]
    seen = jnp.einsum("bhkv,bhk->bhv", decayed, k, precision=HIGHEST)
    u = beta[..., None] * (v - seen)
    state = decayed + k[..., None] * u[..., None, :]
    return jnp.einsum("bhkv,bhk->bhv", state, q, precision=HIGHEST), state


def _chunk(state, rows):
    """One chunk of C tokens from the state before it. q, k, v, g
    [B, C, H, D]; beta [B, C, H]; state [B, H, D, D]."""
    q, k, v, g, beta = rows
    length = q.shape[1]
    cum = jnp.cumsum(g, axis=1)                        # G_t, <= 0
    # exp(G_t - G_i) for i <= t, 0 above the diagonal: [B, t, i, H, D]
    span = cum[:, :, None] - cum[:, None, :]
    lower = jnp.tril(jnp.ones((length, length), jnp.bool_))
    decay = jnp.exp(jnp.where(lower[:, :, None, None], span, -jnp.inf))
    keyed = k[:, None, :] * decay                      # k^_i exp(G_t - G_i)
    a = jnp.sum(k[:, :, None] * keyed, axis=-1)        # [B, t, i, H]
    b = jnp.sum(q[:, :, None] * keyed, axis=-1)
    a, b = (jnp.moveaxis(m, 3, 1) for m in (a, b))     # [B, H, t, i]
    by_head = [jnp.moveaxis(t, 2, 1) for t in (q, k, v, cum)]
    qh, kh, vh, cumh = by_head                         # [B, H, C, D]
    betah = jnp.moveaxis(beta, 2, 1)[..., None]        # [B, H, C, 1]
    from_start = jnp.exp(cumh)
    system = jnp.eye(length) + betah * jnp.tril(a, -1)
    solved = jax.scipy.linalg.solve_triangular(
        system, betah * jnp.concatenate([vh, kh * from_start], axis=-1),
        lower=True, unit_diagonal=True)
    width = v.shape[-1]
    u = solved[..., :width] - jnp.einsum(
        "bhck,bhkv->bhcv", solved[..., width:], state, precision=HIGHEST)
    o = jnp.einsum("bhck,bhkv->bhcv", qh * from_start, state,
                   precision=HIGHEST) \
        + jnp.einsum("bhti,bhiv->bhtv", b, u, precision=HIGHEST)
    to_end = jnp.exp(cumh[:, :, -1:] - cumh)
    state = state * from_start[:, :, -1, :, None] + jnp.einsum(
        "bhck,bhcv->bhkv", kh * to_end, u, precision=HIGHEST)
    return state, jnp.moveaxis(o, 1, 2)


def delta_scan(q, k, v, g, beta, state, chunk: int):
    """The recurrence over L tokens at once. q, k (unit), v, g
    [B, L, H, D] float32, g <= 0; beta [B, L, H], with beta = g = 0 on
    rows that must not touch the state; state [B, H, D, D] float32,
    the state before the first row. -> (o [B, L, H, D] float32, the
    state after the last row)."""
    batch, length, heads, width = q.shape
    size = min(chunk, length)
    n = -(-length // size)
    if n * size != length:
        # whole chunks: rows with beta = g = 0 leave the state alone
        pad = [(0, 0), (0, n * size - length)]
        q, k, v, g = (jnp.pad(t, pad + [(0, 0), (0, 0)])
                      for t in (q, k, v, g))
        beta = jnp.pad(beta, pad + [(0, 0)])

    def chunks(t):
        return jnp.moveaxis(
            t.reshape(batch, n, size, *t.shape[2:]), 1, 0)

    state, o = jax.lax.scan(
        _chunk, state, tuple(chunks(t) for t in (q, k, v, g, beta)))
    o = jnp.moveaxis(o, 0, 1).reshape(batch, n * size, heads, width)
    return o[:, :length], state


class DeltaMixer(nn.Module):
    config: Any                 # transformer.TransformerConfig

    @nn.compact
    def __call__(self, x, valid_len=None):
        cfg, delta = self.config, self.config.delta
        batch, length, _ = x.shape
        heads, width, d_inner = delta.n_heads, delta.head_dim, \
            delta.d_inner
        taps = delta.conv_kernel
        f32 = jnp.float32

        def dense(features, name):
            return nn.Dense(features, use_bias=False, dtype=cfg.dtype,
                            param_dtype=cfg.param_dtype, name=name)

        conv_kernel = self.param(
            "conv_kernel", nn.initializers.normal(taps ** -0.5),
            (taps, 3 * d_inner), cfg.param_dtype)
        a_log = self.param("A_log", nn.initializers.zeros, (heads,), f32)
        dt_bias = self.param("dt_bias", nn.initializers.zeros,
                             (d_inner,), f32)
        norm_scale = self.param("norm_scale", nn.initializers.ones,
                                (width,), f32)

        qkv = dense(3 * d_inner, "qkv_proj")(x)
        decay_in = dense(d_inner, "decay_b")(
            dense(delta.gate_rank, "decay_a")(x))
        gate = dense(d_inner, "gate_b")(dense(delta.gate_rank,
                                              "gate_a")(x))
        beta = 2.0 * jax.nn.sigmoid(dense(heads, "beta_proj")(x)
                                    .astype(f32))            # [B, L, H]
        g = -jnp.exp(a_log)[:, None] * jax.nn.softplus(
            decay_in.astype(f32) + dt_bias).reshape(
                batch, length, heads, width)

        if cfg.decode:
            kept_state = self.variable(
                "cache", STATE_LEAVES[0], jnp.zeros,
                (batch, heads, width, width), delta.state_dtype)
            kept_tail = self.variable(
                "cache", STATE_LEAVES[1], jnp.zeros,
                (batch, taps - 1, 3 * d_inner), cfg.dtype)
            state, tail = kept_state.value.astype(f32), kept_tail.value
        else:
            state = jnp.zeros((batch, heads, width, width), f32)
            tail = jnp.zeros((batch, taps - 1, 3 * d_inner), cfg.dtype)

        mixed, rows = causal_conv(qkv, tail, conv_kernel,
                                  jnp.zeros((), f32))   # no bias
        q, k, v = (t.reshape(batch, length, heads, width) for t in
                   jnp.split(mixed, 3, axis=-1))
        q, k, v = unit(q) * width ** -0.5, unit(k), v.astype(f32)

        if length == 1:
            o, state = delta_step(q[:, 0], k[:, 0], v[:, 0], g[:, 0],
                                  beta[:, 0], state)
            o, new_tail = o[:, None], rows[:, 1:]
        else:
            own = length if valid_len is None else jnp.clip(
                valid_len, 0, length)
            if valid_len is not None:
                # bucket padding: no decay and no write past the
                # sequence's own tokens, so the state stays that of
                # its last one
                is_own = (jnp.arange(length) < own)[None, :, None]
                beta = jnp.where(is_own, beta, 0.0)
                g = jnp.where(is_own[..., None], g, 0.0)
            o, state = delta_scan(q, k, v, g, beta, state, delta.chunk)
            # the K-1 rows before position ``own``
            new_tail = jax.lax.dynamic_slice_in_dim(
                rows, own, taps - 1, axis=1)
        if cfg.decode:
            kept_state.value = state.astype(delta.state_dtype)
            kept_tail.value = new_tail.astype(cfg.dtype)

        # RMS-normalised over each head's channels, then the scale
        o = o * jax.lax.rsqrt(
            jnp.mean(o * o, axis=-1, keepdims=True) + cfg.norm_eps)
        o = (o * norm_scale).reshape(batch, length, d_inner) \
            * jax.nn.sigmoid(gate.astype(f32))
        return dense(cfg.d_model, "o_proj")(o.astype(cfg.dtype))
