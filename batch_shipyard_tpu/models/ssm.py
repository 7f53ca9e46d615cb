"""Mamba-2 mixer: a state-space layer whose per-sequence memory is a
FIXED-SIZE state, not rows that grow with the sequence.

Per head h of H (head width P, state width N, head h reading group
h // (H / G) of G shared B/C projections):

    xBC <- silu(causal_depthwise_conv(xBC, kernel K) + bias)
    dt  <- softplus(dt + dt_bias),  A = -exp(A_log)
    S_t  = exp(dt_t A) S_{t-1} + dt_t x_t (x) B_t        S in R^{P x N}
    y_t  = S_t C_t + D x_t
    out  = out_proj(group_rmsnorm(y * silu(z)) * scale)

Serving keeps, per slot and layer, the state S ([H, P, N], float32
unless SSMConfig.state_dtype says otherwise) and the convolution's
tail (the last K-1 rows of xBC before the convolution) in the flax
"cache" collection, beside the attention layers' K/V. They have a
slot row and NO cursor: nothing masks a state on read, so whatever
must not be in it may never be written into it. Hence ``valid_len``:
a prefill padded to its compile bucket freezes state and tail at the
sequence's last own token (dt = 0 there: no decay, no input).

Plain JAX throughout. A multi-token call is the chunked ("state-space
duality") scan: quadratic inside chunks of SSMConfig.chunk tokens, a
short recurrence over the chunks' end states between them; a
one-token call is one step of the recurrence.
"""

from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
from flax import linen as nn


@dataclasses.dataclass(frozen=True)
class SSMConfig:
    n_heads: int = 64
    head_dim: int = 64
    n_groups: int = 8
    state_size: int = 128
    conv_kernel: int = 4
    chunk: int = 128
    # What the per-slot state S is KEPT in between calls (it is always
    # advanced in float32). bfloat16 halves a decode step's state
    # traffic and rounds the state once a token.
    state_dtype: Any = jnp.float32

    @property
    def d_inner(self) -> int:
        return self.n_heads * self.head_dim

    @property
    def conv_dim(self) -> int:
        return self.d_inner + 2 * self.n_groups * self.state_size


# The cache leaves a mixer keeps per slot: a slot row, no cursor, no
# pages (models/inference.slot_state_bytes, serving._seat_state).
STATE_LEAVES = ("ssm_state", "conv_tail")


def causal_conv(xbc, tail, kernel, bias):
    """Depthwise causal convolution then silu. xbc [B, L, C]; tail
    [B, K-1, C], the K-1 rows before it; kernel [K, C], tap K-1 on the
    current row. -> (float32 [B, L, C], the rows [tail; xbc] whose
    last K-1 before any position are that position's tail)."""
    taps, length = kernel.shape[0], xbc.shape[1]
    rows = jnp.concatenate([tail.astype(xbc.dtype), xbc], axis=1)
    wide = rows.astype(jnp.float32)
    kernel = kernel.astype(jnp.float32)
    out = bias.astype(jnp.float32) + sum(
        wide[:, k:k + length] * kernel[k] for k in range(taps))
    return jax.nn.silu(out), rows


def ssd_scan(x, dt, a, b, c, state, chunk: int):
    """The recurrence over L tokens at once. x [B, L, H, P]; dt
    [B, L, H] float32, 0 on rows that must not advance the state; a
    [H] float32 (negative); b, c [B, L, G, N]; state [B, H, P, N]
    float32, the state before the first row. -> (y [B, L, H, P]
    float32 without the D term, the state after the last row)."""
    batch, length, heads, width = x.shape
    groups, n_state = b.shape[2], b.shape[3]
    per = heads // groups
    q = min(chunk, length)
    n = -(-length // q)
    if n * q != length:
        # whole chunks: rows with dt = 0 neither decay nor feed
        pad = [(0, 0), (0, n * q - length)]
        x, b, c = (jnp.pad(t, pad + [(0, 0), (0, 0)]) for t in (x, b, c))
        dt = jnp.pad(dt, pad + [(0, 0)])
    f32 = jnp.float32
    xc = x.astype(f32).reshape(batch, n, q, groups, per, width)
    dtc = dt.reshape(batch, n, q, groups, per)
    bc = b.astype(f32).reshape(batch, n, q, groups, n_state)
    cc = c.astype(f32).reshape(batch, n, q, groups, n_state)
    # log-decay up to and including each row, inside its chunk
    cum = jnp.cumsum(dtc * a.reshape(groups, per), axis=2)
    # inside a chunk: row i reads row j <= i through C_i.B_j, decayed
    # from j to i
    cb = jnp.einsum("bcigs,bcjgs->bcijg", cc, bc)
    span = cum[:, :, :, None] - cum[:, :, None, :]   # [B, n, i, j, G, R]
    causal = jnp.tril(jnp.ones((q, q), jnp.bool_))[:, :, None, None]
    weight = jnp.exp(jnp.where(causal, span, -jnp.inf)) \
        * cb[..., None] * dtc[:, :, None]
    y = jnp.einsum("bcijgr,bcjgrp->bcigrp", weight, xc)
    # each chunk's own contribution to the state at its end
    to_end = jnp.exp(cum[:, :, -1:] - cum) * dtc
    ends = jnp.einsum("bcjgrp,bcjgs->bcgrps", xc * to_end[..., None], bc)
    through = jnp.exp(cum[:, :, -1])                 # [B, n, G, R]

    def carry(before, chunk_in):
        end, decay = chunk_in
        return before * decay[..., None, None] + end, before

    last, before = jax.lax.scan(
        carry, state.astype(f32).reshape(batch, groups, per, width,
                                         n_state),
        (jnp.moveaxis(ends, 1, 0), jnp.moveaxis(through, 1, 0)))
    before = jnp.moveaxis(before, 0, 1)              # [B, n, G, R, P, N]
    # what the state before the chunk adds to each of its rows
    y = y + jnp.einsum("bcigs,bcgrps->bcigrp", cc, before) \
        * jnp.exp(cum)[..., None]
    return (y.reshape(batch, n * q, heads, width)[:, :length],
            last.reshape(batch, heads, width, n_state))


def ssd_step(x, dt, a, b, c, state):
    """One token. x [B, H, P]; dt [B, H]; a [H]; b, c [B, G, N];
    state [B, H, P, N] float32 -> (y [B, H, P] float32, new state)."""
    batch, heads, width = x.shape
    groups, n_state = b.shape[1], b.shape[2]
    per = heads // groups
    f32 = jnp.float32
    state = state.reshape(batch, groups, per, width, n_state)
    dt = dt.reshape(batch, groups, per)
    decay = jnp.exp(dt * a.reshape(groups, per))
    fed = (x.astype(f32).reshape(batch, groups, per, width)
           * dt[..., None])
    state = state * decay[..., None, None] + \
        fed[..., None] * b.astype(f32)[:, :, None, None, :]
    y = jnp.einsum("bgrps,bgs->bgrp", state, c.astype(f32))
    return (y.reshape(batch, heads, width),
            state.reshape(batch, heads, width, n_state))


class Mamba2Mixer(nn.Module):
    config: Any                 # transformer.TransformerConfig

    @nn.compact
    def __call__(self, x, valid_len=None):
        cfg, ssm = self.config, self.config.ssm
        batch, length, _ = x.shape
        heads, width = ssm.n_heads, ssm.head_dim
        groups, n_state = ssm.n_groups, ssm.state_size
        d_inner, conv_dim = ssm.d_inner, ssm.conv_dim
        taps = ssm.conv_kernel
        f32 = jnp.float32

        def dense(features, name):
            return nn.Dense(features, use_bias=False, dtype=cfg.dtype,
                            param_dtype=cfg.param_dtype, name=name)

        conv_kernel = self.param(
            "conv_kernel", nn.initializers.normal(taps ** -0.5),
            (taps, conv_dim), cfg.param_dtype)
        conv_bias = self.param("conv_bias", nn.initializers.zeros,
                               (conv_dim,), f32)
        dt_bias = self.param("dt_bias", nn.initializers.zeros,
                             (heads,), f32)
        a_log = self.param("A_log", nn.initializers.zeros, (heads,), f32)
        skip = self.param("D", nn.initializers.ones, (heads,), f32)
        norm_scale = self.param("norm_scale", nn.initializers.ones,
                                (d_inner,), f32)

        projected = dense(2 * d_inner + 2 * groups * n_state + heads,
                          "in_proj")(x)
        z, xbc, dt = jnp.split(
            projected, [d_inner, d_inner + conv_dim], axis=-1)
        dt = jax.nn.softplus(dt.astype(f32) + dt_bias)
        a = -jnp.exp(a_log)

        if cfg.decode:
            kept_state = self.variable(
                "cache", STATE_LEAVES[0], jnp.zeros,
                (batch, heads, width, n_state), ssm.state_dtype)
            kept_tail = self.variable(
                "cache", STATE_LEAVES[1], jnp.zeros,
                (batch, taps - 1, conv_dim), cfg.dtype)
            state, tail = kept_state.value.astype(f32), kept_tail.value
        else:
            state = jnp.zeros((batch, heads, width, n_state), f32)
            tail = jnp.zeros((batch, taps - 1, conv_dim), cfg.dtype)

        mixed, rows = causal_conv(xbc, tail, conv_kernel, conv_bias)
        mixed = mixed.astype(cfg.dtype)
        xs, b, c = jnp.split(
            mixed, [d_inner, d_inner + groups * n_state], axis=-1)
        xs = xs.reshape(batch, length, heads, width)
        b = b.reshape(batch, length, groups, n_state)
        c = c.reshape(batch, length, groups, n_state)

        if length == 1:
            y, state = ssd_step(xs[:, 0], dt[:, 0], a, b[:, 0], c[:, 0],
                                state)
            y, new_tail = y[:, None], rows[:, 1:]
        else:
            own = length if valid_len is None else jnp.clip(
                valid_len, 0, length)
            if valid_len is not None:
                # bucket padding: no decay and no input past the
                # sequence's own tokens, so the state stays that of
                # its last one
                dt = jnp.where(
                    (jnp.arange(length) < own)[None, :, None], dt, 0.0)
            y, state = ssd_scan(xs, dt, a, b, c, state, ssm.chunk)
            # the K-1 rows before position ``own``
            new_tail = jax.lax.dynamic_slice_in_dim(
                rows, own, taps - 1, axis=1)
        if cfg.decode:
            kept_state.value = state.astype(ssm.state_dtype)
            kept_tail.value = new_tail.astype(cfg.dtype)

        y = y + skip[:, None] * xs.astype(f32)
        y = y.reshape(batch, length, d_inner) * jax.nn.silu(z.astype(f32))
        # RMS-normalised over each group's channels, then the scale
        y = y.reshape(batch, length, groups, d_inner // groups)
        y = y * jax.lax.rsqrt(
            jnp.mean(y * y, axis=-1, keepdims=True) + cfg.norm_eps)
        y = y.reshape(batch, length, d_inner) * norm_scale
        return dense(cfg.d_model, "out_proj")(y.astype(cfg.dtype))
