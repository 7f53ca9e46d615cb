"""The plain reference of the stack of sliding-window and full
grouped-query attention layers over top-k routed ReGLU experts whose
router reads the layer's input BEFORE attention (``smallthinker``):
the reference of the configurations whose ``model_module`` is
``window_moe`` (benchmark/models/window_moe.py calls it). Written from
the published configuration's keys in straightforward jax.numpy,
float32, matmuls at precision "highest". No kernels, no cache, no
batching, and nothing imported from batch_shipyard_tpu.

A PUBLISHED LAYER l, h the residual stream [T, d], no bias anywhere:

  a    = RMSNorm_1(h)                       learned scale, float32
  r    = a W_r                    [T, n]    the router reads a
  idx  = the k largest of r;  w = softmax(r[idx])   over the k chosen
  q, k, v = a W_q, a W_k, a W_v   Hq query heads over Hkv K/V heads of D
  rope[l]:  q, k = RoPE(q), RoPE(k) at theta (rotate-half: the head's
            first and second half are the pairs); else NO positional
            embedding at all
  key j is visible to query i iff j <= i and
            (window[l] == 0 or j > i - window[l])
  h'   = h + softmax(q k^T / sqrt(D) + mask) v W_o
  m    = RMSNorm_2(h')
  h''  = h' + sum_{e in idx} w_e W_down,e (relu(W_gate,e m) * (W_up,e m))

then the final norm and an UNTIED lm_head. There is no shared expert.

The program runs a published layer as TWO blocks, ``attn`` then
``experts``, each one mixer after one norm; the weights arrive in its
tree (layer_{2l}/norm + attn, layer_{2l+1}/norm + experts) and are
read here a published layer at a time.

Attention runs in ROW BLOCKS of queries, each against the keys it can
see at all, so that a request of 13 k tokens fits; the experts are a
plain loop over the experts held, each over every row and weighed 0
where the row did not choose it.

Handed ``decisions`` ({layer name: int32 [T, k]}, a row of -1: no
record) it computes the experts it is handed, weighs them by ITS OWN
softmax over their logits, and returns beside the logits one slack per
position and layer: its own k-th best logit less the lowest logit
among the handed ones: 0 when the sets are equal, never below.

It is handed the benchmark's own seeded weights and upcasts them a
layer (an expert) at a time, so that it fits beside them."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
F32 = jnp.float32
ROW_BLOCK = 512        # queries an attention block
LENGTH_BUCKET = 4096   # long sequences are padded to a multiple of this


def matmul(a, b):
    """a [..., k] @ b [k, n] in float32."""
    return jnp.matmul(a.astype(F32), b.astype(F32), precision=HIGHEST)


def rmsnorm(x, scale, eps: float):
    x = x.astype(F32)
    return x * jax.lax.rsqrt(
        jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def rope(x, theta: float):
    """x [T, H, D] at positions 0 .. T-1: the pairs are (x_i,
    x_{i + D/2}), each rotated by position * theta^(-2i / D)."""
    t, _heads, depth = x.shape
    freqs = jnp.exp(-jnp.log(F32(theta))
                    * jnp.arange(0, depth, 2, dtype=F32) / depth)
    angles = jnp.arange(t, dtype=F32)[:, None] * freqs[None, :]
    cos, sin = jnp.cos(angles)[:, None, :], jnp.sin(angles)[:, None, :]
    x1, x2 = x[..., :depth // 2], x[..., depth // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                           axis=-1)


def attention(a, w, *, q_heads: int, kv_heads: int, window: int,
              use_rope: bool, theta: float):
    """The attention mixer on normed a [T, d] -> [T, d], T a whole
    number of ROW_BLOCKs: one row block of queries after another, each
    against the keys it can see at all (a window layer: the window +
    ROW_BLOCK rows that end with the block; a full layer: every row),
    masked by position."""
    t = a.shape[0]
    q = matmul(a, w["q_proj"]["kernel"]).reshape(t, q_heads, -1)
    k = matmul(a, w["k_proj"]["kernel"]).reshape(t, kv_heads, -1)
    v = matmul(a, w["v_proj"]["kernel"]).reshape(t, kv_heads, -1)
    if use_rope:
        q, k = rope(q, theta), rope(k, theta)
    group = q_heads // kv_heads
    depth = q.shape[-1]
    span = min(t, window + ROW_BLOCK) if window else t

    def block(lo):
        first = jnp.clip(lo + ROW_BLOCK - span, 0, t - span)
        keys = jax.lax.dynamic_slice_in_dim(k, first, span)
        values = jax.lax.dynamic_slice_in_dim(v, first, span)
        rows = jax.lax.dynamic_slice_in_dim(q, lo, ROW_BLOCK).reshape(
            ROW_BLOCK, kv_heads, group, depth)
        scores = jnp.einsum("qhgd,khd->hgqk", rows, keys,
                            precision=HIGHEST) / jnp.sqrt(F32(depth))
        i = lo + jnp.arange(ROW_BLOCK)[:, None]
        j = first + jnp.arange(span)[None, :]
        visible = j <= i
        if window:
            visible &= j > i - window
        probs = jax.nn.softmax(
            jnp.where(visible, scores, -jnp.inf), axis=-1)
        return jnp.einsum("hgqk,khd->qhgd", probs, values,
                          precision=HIGHEST).reshape(ROW_BLOCK, -1)

    # (a sequence of one block without the loop around it)
    out = block(0) if t == ROW_BLOCK else jax.lax.map(
        block, jnp.arange(0, t, ROW_BLOCK))
    return matmul(out.reshape(t, -1), w["o_proj"]["kernel"])


def route(a, router_kernel, handed, top_k: int):
    """-> (the experts used [T, k], their weights [T, k], slack [T]).
    handed int32 [T, k]: a row of -1 takes the reference's own."""
    logits = matmul(a, router_kernel)
    own_best, own = jax.lax.top_k(logits, top_k)
    use = jnp.where(handed[:, :1] >= 0, handed, own)
    picked = jnp.take_along_axis(logits, use, axis=-1)
    slack = own_best[:, -1] - jnp.min(picked, axis=-1)
    return use, jax.nn.softmax(picked, axis=-1), slack


def reglu(m, gate, up, down):
    return matmul(jax.nn.relu(matmul(m, gate)) * matmul(m, up), down)


def routed(m, w, use, weights):
    """sum_i w_i Expert_i(m) over the used experts, one expert after
    another, each over every row and weighed 0 where it was not
    used."""
    def one(total, expert):
        index, gate, up, down = expert
        weight = jnp.sum(jnp.where(use == index, weights, 0.0), axis=-1)
        return total + weight[:, None] * reglu(m, gate, up, down), None

    held = w["experts_up"].shape[0]
    total, _ = jax.lax.scan(
        one, jnp.zeros_like(m),
        (jnp.arange(held), w["experts_gate"], w["experts_up"],
         w["experts_down"]))
    return total


@functools.partial(jax.jit, static_argnames=(
    "q_heads", "kv_heads", "window", "use_rope", "theta", "top_k",
    "eps"))
def layer(h, mixer, feed, handed, *, eps: float, top_k: int, **sizes):
    """One published layer: ``mixer`` the program's attn block
    ({"norm", "attn"}), ``feed`` its experts block ({"norm",
    "experts"}). -> (h'', slack [T])."""
    a = rmsnorm(h, mixer["norm"]["scale"], eps)
    use, weights, slack = route(a, feed["experts"]["router_kernel"],
                                handed, top_k)
    h = h + attention(a, mixer["attn"], **sizes)
    m = rmsnorm(h, feed["norm"]["scale"], eps)
    return h + routed(m, feed["experts"], use, weights), slack


@functools.partial(jax.jit, static_argnames=("eps",))
def head_logits(hidden, final_norm, lm_head, eps: float):
    """Final norm and the untied head: hidden [R, d] -> [R, vocab]."""
    return matmul(rmsnorm(hidden, final_norm["scale"], eps), lm_head)


def teacher_forced_logits(params, tokens, rows, *, windows: tuple,
                          ropes: tuple, q_heads: int, kv_heads: int,
                          theta: float, top_k: int, eps: float,
                          decisions=None):
    """One full forward over ``tokens`` [T] (no cache), a published
    layer at a time (``windows`` / ``ropes``: one entry a published
    layer); the logits of the positions in ``rows`` -> [len(rows),
    vocab] float32, and with ``decisions`` also {layer name: slack
    [T]}, named as the program names its experts blocks (layer_{2l+1}).
    The sequence is padded at its end to a power of two from
    ROW_BLOCK up, and beyond LENGTH_BUCKET to a whole number of those
    (attention is causal and everything else is a function of the row
    alone, so no position that is read sees the padding): a layer
    compiles once a bucket, not once a length."""
    length = tokens.shape[0]
    padded = -(-length // LENGTH_BUCKET) * LENGTH_BUCKET
    while padded // 2 >= max(length, ROW_BLOCK):
        padded //= 2
    tokens = jnp.pad(tokens, (0, padded - length))
    h = params["embed"]["embedding"][tokens].astype(F32)
    own = jnp.full((padded, top_k), -1, jnp.int32)
    slacks = {}
    for l, (window, use_rope) in enumerate(zip(windows, ropes)):
        name = f"layer_{2 * l + 1}"
        handed = own if decisions is None else jnp.pad(
            decisions[name], ((0, padded - length), (0, 0)),
            constant_values=-1)
        h, slack = layer(
            h, params[f"layer_{2 * l}"], params[name], handed, eps=eps,
            top_k=top_k, q_heads=q_heads, kv_heads=kv_heads,
            window=int(window), use_rope=bool(use_rope),
            theta=float(theta))
        slacks[name] = slack[:length]
    logits = head_logits(h[rows], params["final_norm"],
                         params["lm_head"]["kernel"], eps)
    return logits if decisions is None else (logits, slacks)
