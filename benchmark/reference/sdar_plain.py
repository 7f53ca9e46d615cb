"""The plain reference of a stack of grouped-query attention layers
with q/k norms under a BLOCK-causal mask over softmax-routed SwiGLU
experts, generated from by diffusion over blocks (``sdar_moe``): the
reference of the configurations whose ``model_module`` is
``moe_block_diffusion`` (benchmark/models/moe_block_diffusion.py calls
it). Written from the published configuration's keys in
straightforward jax.numpy, float32, matmuls at precision "highest". No
kernels, no cache, no batching, and nothing imported from
batch_shipyard_tpu.

A PUBLISHED LAYER, h the residual stream [T, d], no bias anywhere, B
the block length:

  a    = RMSNorm_1(h)                        learned scale, float32
  q, k, v = a W_q, a W_k, a W_v   Hq query heads over Hkv K/V heads of D
  q, k = RMSNorm_q(q), RMSNorm_k(k)   over the D of each head, one
            learned scale of D a layer each
  q, k = RoPE(q), RoPE(k) at theta by ABSOLUTE position (rotate-half:
            the head's first and second half are the pairs)
  key j is visible to query i iff j // B <= i // B
  h'   = h + softmax(q k^T / sqrt(D) + mask) v W_o
  m    = RMSNorm_2(h')
  r    = m W_r  [T, n] float32;  idx = the k largest of r;
  w    = softmax over those k;   h'' = h' + sum_{e in idx} w_e Expert_e(m)
         Expert_e(m) = W_down_e (silu(W_gate_e m) * (W_up_e m))

then the final norm and an UNTIED lm_head. The logit at position i
scores the token AT position i (no shift). No shared expert.

GENERATION. The prompt's whole blocks are clean; from ``start`` (a
block's first position) on, a block opens with the prompt's remaining
tokens given and MASK elsewhere, and each DENOISE PASS runs the block
(MASK at the positions still masked) against the clean blocks before
it, takes every position's best token and its confidence (that token's
softmax probability), and unmasks some of the masked (the program's
rule; the reference judges what it is handed). A block without a mask
is clean, and the next opens.

ONE FORWARD judges a whole request: the CLEAN sequence [T] followed by
``steps`` NOISY copies of the span [start, T), copy s holding at each
position its token if it was unmasked BEFORE pass s (or given) and
MASK else: block b of copy s is what denoise pass s of block b read.
Under one explicit mask (``visible``): a clean row sees the clean rows
of its own block and of those before it; a noisy row sees the clean
rows of the blocks before its own and the rows of its own block IN ITS
OWN COPY; each row is rotated by its own position in the sequence. A
block that took fewer than s + 1 passes is clean in copy s and reads
what its clean rows read.

The program runs a published layer as TWO blocks, a token mixer then a
feed-forward, each one mixer after one norm; the weights arrive in its
tree (layer_{2l}/norm + attn, layer_{2l+1}/norm + experts) and are read
here a published layer at a time.

Attention runs in ROW BLOCKS of queries against every row (the mask
says which); the experts are a plain loop over the experts, each over
every row and weighed 0 where the row did not choose it.

Handed ``decisions`` ({layer name: int32 [R, k]} over the extended
rows, a row of -1: no record) it computes the experts it is handed,
weighs them by ITS OWN logits, and returns one slack per row and layer:
its own k-th best logit less the lowest logit among the handed ones, 0
when the sets are equal, never below.

DEPARTURES from the family's published ``generate`` loop, which runs a
block's passes one after another with a K/V cache: none in the
arithmetic; the passes of all blocks are laid side by side in one
sequence under the mask above, which is what block diffusion's
training forward does. It is handed the benchmark's own seeded
weights and upcasts them a layer (an expert) at a time, so that it
fits beside them."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
F32 = jnp.float32
ROW_BLOCK = 256        # queries an attention block
ROWS_BUCKET = 2048     # long extended sequences: a multiple of this
HEAD_ROWS = 256        # rows a call of the head
UNMASK = "unmask"      # the record's name of each position's pass
CLEAN = -1             # the copy index of a clean row


def pass_name(layer: str, s: int) -> str:
    """The record's name of a routed layer's choices in denoise pass
    s."""
    return f"{layer}.pass{s}"


def matmul(a, b):
    """a [..., k] @ b [k, n] in float32."""
    return jnp.matmul(a.astype(F32), b.astype(F32), precision=HIGHEST)


def rmsnorm(x, scale, eps: float):
    x = x.astype(F32)
    return x * jax.lax.rsqrt(
        jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def rope(x, positions, theta: float):
    """x [R, H, D] at ``positions`` [R]: the pairs are (x_i,
    x_{i + D/2}), each rotated by position * theta^(-2i / D)."""
    depth = x.shape[-1]
    freqs = jnp.exp(-jnp.log(F32(theta))
                    * jnp.arange(0, depth, 2, dtype=F32) / depth)
    angles = positions.astype(F32)[:, None] * freqs[None, :]
    cos, sin = jnp.cos(angles)[:, None, :], jnp.sin(angles)[:, None, :]
    x1, x2 = x[..., :depth // 2], x[..., depth // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                           axis=-1)


def visible(q_pos, q_copy, k_pos, k_copy, block: int):
    """[Rq, Rk] True where the key is visible to the query: the one
    explicit mask. A clean key: to a clean query of its block or a
    later one, to a noisy query of a LATER block. A noisy key: to the
    queries of its own block in its own copy."""
    q_blk, k_blk = q_pos[:, None] // block, k_pos[None, :] // block
    q_copy, k_copy = q_copy[:, None], k_copy[None, :]
    return jnp.where(
        k_copy == CLEAN,
        jnp.where(q_copy == CLEAN, k_blk <= q_blk, k_blk < q_blk),
        (k_copy == q_copy) & (k_blk == q_blk))


def attention(a, w, positions, copies, *, q_heads: int, kv_heads: int,
              theta: float, eps: float, block: int):
    """The attention mixer on normed a [R, d] -> [R, d], R a whole
    number of ROW_BLOCKs (or fewer rows than one): q and k normed a
    head and rotated by the row's own position, then one row block of
    queries after another against every row, under ``visible``."""
    rows = a.shape[0]
    q = matmul(a, w["q_proj"]["kernel"]).reshape(rows, q_heads, -1)
    k = matmul(a, w["k_proj"]["kernel"]).reshape(rows, kv_heads, -1)
    v = matmul(a, w["v_proj"]["kernel"]).reshape(rows, kv_heads, -1)
    q = rope(rmsnorm(q, w["q_norm"]["scale"], eps), positions, theta)
    k = rope(rmsnorm(k, w["k_norm"]["scale"], eps), positions, theta)
    group = q_heads // kv_heads
    depth = q.shape[-1]
    step = min(ROW_BLOCK, rows)

    def one(lo):
        queries = jax.lax.dynamic_slice_in_dim(q, lo, step).reshape(
            step, kv_heads, group, depth)
        scores = jnp.einsum("qhgd,khd->hgqk", queries, k,
                            precision=HIGHEST) / jnp.sqrt(F32(depth))
        mask = visible(
            jax.lax.dynamic_slice_in_dim(positions, lo, step),
            jax.lax.dynamic_slice_in_dim(copies, lo, step),
            positions, copies, block)
        probs = jax.nn.softmax(
            jnp.where(mask, scores, -jnp.inf), axis=-1)
        return jnp.einsum("hgqk,khd->qhgd", probs, v,
                          precision=HIGHEST).reshape(step, -1)

    out = one(0) if rows == step else jax.lax.map(
        one, jnp.arange(0, rows, step))
    return matmul(out.reshape(rows, -1), w["o_proj"]["kernel"])


def swiglu(m, gate, up, down):
    return matmul(jax.nn.silu(matmul(m, gate)) * matmul(m, up), down)


def route(m, w, handed, top_k: int):
    """-> (the experts used [R, k], their weights [R, k], slack [R]).
    handed int32 [R, k]: a row of -1 takes the reference's own."""
    logits = matmul(m, w["router_kernel"])
    own_best, own = jax.lax.top_k(logits, top_k)
    use = jnp.where(handed[:, :1] >= 0, handed, own)
    picked = jnp.take_along_axis(logits, use, axis=-1)
    slack = own_best[:, -1] - jnp.min(picked, axis=-1)
    return use, jax.nn.softmax(picked, axis=-1), slack


def experts(m, w, handed, *, top_k: int):
    """The sparse feed-forward on normed m [R, d] -> ([R, d], slack
    [R]): one expert after another, each over every row and weighed 0
    where the row did not use it."""
    use, weights, slack = route(m, w, handed, top_k)

    def one(total, expert):
        index, gate, up, down = expert
        weight = jnp.sum(jnp.where(use == index, weights, 0.0), axis=-1)
        return total + weight[:, None] * swiglu(m, gate, up, down), None

    held = w["experts_up"].shape[0]
    total, _ = jax.lax.scan(
        one, jnp.zeros_like(m),
        (jnp.arange(held), w["experts_gate"], w["experts_up"],
         w["experts_down"]))
    return total, slack


@functools.partial(jax.jit, static_argnames=(
    "q_heads", "kv_heads", "theta", "eps", "block", "top_k"))
def layer(h, mixer, feed, handed, positions, copies, *, top_k: int,
          **sizes):
    """One published layer: ``mixer`` the program's attn block
    ({"norm", "attn"}), ``feed`` its experts block ({"norm",
    "experts"}). -> (h'', slack [R])."""
    eps = sizes["eps"]
    h = h + attention(rmsnorm(h, mixer["norm"]["scale"], eps),
                      mixer["attn"], positions, copies, **sizes)
    out, slack = experts(rmsnorm(h, feed["norm"]["scale"], eps),
                         feed["experts"], handed, top_k=top_k)
    return h + out, slack


@functools.partial(jax.jit, static_argnames=("eps",))
def head_logits(hidden, norm, lm_head, eps: float):
    """A final norm and the untied head: hidden [R, d] -> [R, vocab]."""
    return matmul(rmsnorm(hidden, norm["scale"], eps), lm_head)


@functools.partial(jax.jit, static_argnames=("eps",))
def _head_readings(hidden, norm, lm_head, picked, eps: float):
    logits = head_logits(hidden, norm, lm_head, eps)
    best = jnp.max(logits, axis=-1)
    return (best, jnp.argmax(logits, axis=-1),
            best - jax.nn.logsumexp(logits, axis=-1),
            jnp.take_along_axis(logits, picked[:, None], axis=-1)[:, 0])


def head_readings(params, hidden, picked, eps: float) -> dict:
    """Of the rows ``hidden`` [n, d] (before the final norm), HEAD_ROWS
    at a time so that no more logits than that are ever held: "best"
    (the largest logit), "token" (its index), "confidence" (the
    logarithm of that token's softmax probability) and "at" (the logit
    of ``picked`` [n]), each a numpy [n]."""
    n = hidden.shape[0]
    out = {"best": [], "token": [], "confidence": [], "at": []}
    picked = np.asarray(picked, np.int32)
    for lo in range(0, n, HEAD_ROWS):
        rows = min(HEAD_ROWS, n - lo)
        pad = HEAD_ROWS - rows
        part = _head_readings(
            jnp.pad(hidden[lo:lo + rows], ((0, pad), (0, 0))),
            params["final_norm"], params["lm_head"]["kernel"],
            jnp.asarray(np.pad(picked[lo:lo + rows], (0, pad))), eps)
        for name, values in zip(out, part):
            out[name].append(np.asarray(values)[:rows])
    return {name: np.concatenate(parts) if parts else np.zeros((0,))
            for name, parts in out.items()}


def extended(clean, start: int, unmasked_at, steps: int, mask_id: int):
    """The one sequence a request is judged on, as numpy: (tokens,
    positions, copies), each [T + steps * (T - start)]: the clean
    sequence ``clean`` [T], then copy s = 0 .. steps-1 of its span
    [start, T), holding at span position i its token where
    ``unmasked_at`` [T - start] is below s (unmasked before pass s) or
    at least ``steps`` (given: never masked), and ``mask_id`` else."""
    clean = np.asarray(clean, np.int32)
    at = np.asarray(unmasked_at, np.int32)
    total = len(clean)
    span = np.arange(start, total, dtype=np.int32)
    tokens, positions, copies = [clean], [np.arange(
        total, dtype=np.int32)], [np.full((total,), CLEAN, np.int32)]
    for s in range(steps):
        tokens.append(np.where((at < s) | (at >= steps), clean[start:],
                               np.int32(mask_id)))
        positions.append(span)
        copies.append(np.full((len(span),), s, np.int32))
    return (np.concatenate(tokens), np.concatenate(positions),
            np.concatenate(copies))


def extended_row(position: int, copy: int, total: int, start: int) -> int:
    """The row of ``extended``'s sequence that holds ``position`` in
    copy ``copy`` (CLEAN: the clean sequence)."""
    if copy == CLEAN:
        return position
    return total + copy * (total - start) + position - start


def _padded(rows: int) -> int:
    """A power of two from ROW_BLOCK up, and beyond ROWS_BUCKET a whole
    number of those: few shapes to compile."""
    if rows > ROWS_BUCKET:
        return -(-rows // ROWS_BUCKET) * ROWS_BUCKET
    padded = ROW_BLOCK
    while padded < rows:
        padded *= 2
    return padded


def stack_hidden(params, tokens, positions, copies, *, layers: int,
                 block: int, q_heads: int, kv_heads: int, theta: float,
                 top_k: int, eps: float, decisions=None):
    """The stack over an extended sequence (``extended``'s three
    arrays, [R]) -> (the hidden states [R, d] BEFORE the final norm,
    {layer name: slack [R]}). ``decisions``: {layer name: int32 [R, k]}
    over the same rows (a row of -1: the reference's own choice). The
    rows are padded at the end to ``_padded``: a padding row is a clean
    row of a block of its own behind every other, which nothing sees."""
    rows = len(tokens)
    padded = _padded(rows)
    pad = padded - rows
    last = int(np.max(positions)) // block + 1
    tokens = jnp.asarray(np.pad(tokens, (0, pad)))
    positions = jnp.asarray(np.concatenate(
        [positions, (last + np.arange(pad, dtype=np.int32)) * block]))
    copies = jnp.asarray(np.pad(copies, (0, pad),
                                constant_values=CLEAN))
    own = jnp.full((padded, top_k), -1, jnp.int32)
    h = params["embed"]["embedding"][tokens].astype(F32)
    slacks = {}
    for l in range(layers):
        name = f"layer_{2 * l + 1}"
        handed = own if decisions is None else jnp.asarray(np.pad(
            np.asarray(decisions[name], np.int32), ((0, pad), (0, 0)),
            constant_values=-1))
        h, slack = layer(
            h, params[f"layer_{2 * l}"], params[name], handed,
            positions, copies, q_heads=q_heads, kv_heads=kv_heads,
            theta=float(theta), eps=eps, block=block, top_k=top_k)
        slacks[name] = np.asarray(slack)[:rows]
    return h[:rows], slacks
