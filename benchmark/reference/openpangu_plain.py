"""The plain reference of a stack of multi-head LATENT attention layers
(a low-rank query, one compressed K/V vector and one rotary key a
token for all heads) under SANDWICH norms, leading dense gated
feed-forward layers, then sigmoid-routed SwiGLU experts beside a shared
one, with ONE multi-token-prediction module behind it
(``pangu_ultra_moe``): the reference of the configurations whose
``model_module`` is ``latent_moe_mtp`` (benchmark/models/
latent_moe_mtp.py calls it). Written from the published configuration's
keys in straightforward jax.numpy, float32, matmuls at precision
"highest". The EXPANDED form of the attention only: no absorption, no
kernels, no cache, no paging, no batching, and nothing imported from
batch_shipyard_tpu.

A PUBLISHED LAYER, h the residual stream [T, d], H heads, no bias
anywhere, every norm an RMSNorm with a learned scale (eps rms_norm_eps):

  a      = norm_in(h)
  c_q    = norm_q(a W_dq)                         [q_lora_rank]
  q_h    = [q_h^N ; q_h^R] = (c_q W_uq)_h         [nope + rope] a head
  [c;kR] = a W_dkv;  c = norm_kv(c)               [kv_lora_rank + rope]
  q_h^R, kR rotated at position i (rotate-half pairs over the rope
           lanes, theta rope_theta, no scaling)
  [k_h^N ; v_h] = (c W_ukv)_h                     [nope + v] a head
  s_hij  = (q_h^N . k_hj^N + q_h^R . kR_j) / sqrt(nope + rope), j <= i
  u      = concat_h(sum_j softmax_j(s_hij) v_hj) W_o
  h'     = h + norm_post_attn(u)      SANDWICH: the sublayer's OUTPUT is
                                      normed before it is added
  m      = norm_pre_mlp(h');  f = FFN(m);  h'' = h' + norm_post_mlp(f)
  FFN, a dense layer:  W_down (silu(W_gate m) * (W_up m))
  FFN, a sparse layer: s = sigmoid(m W_r) [T, n] in float32; idx = the
           k largest of s + b (b zero: no correction bias is published);
           w_e = scale * s_e / (sum_{idx} s + 1e-20)
           f = sum_{e in idx, e HELD} w_e Expert_e(m) + Shared(m),
           each a SwiGLU

then the final norm and an UNTIED lm_head. Of the n experts the weights
hold ``first`` .. ``first`` + E - 1 (an expert-parallel share); a choice
among the others is computed and added nowhere.

THE MULTI-TOKEN-PREDICTION MODULE (the wiring DeepSeek-V3 published for
``num_nextn_predict_layers``), h_L the stack's last hidden state BEFORE
the final norm, t_{i+1} the token after position i:

  x_i  = [norm_e(Emb(t_{i+1})) ; norm_h(h_L,i)] W_proj       (2d -> d)
  x'_i = one sparse layer as above on x (latent attention over the x
         positions, rotated; its own norms, router and held experts)
  logits_i = norm_m(x'_i) W_head          a prediction of t_{i+2}

Embedding and head are the stack's.

DEPARTURES from the published description, each the configuration
file's too: the routing has no group step and no correction bias (the
published file has neither key; ``e_score_correction_bias`` is a leaf
of zeros so that the program's tree is the siblings'); the sandwich's
four norms a layer are read as above (Pangu Ultra, arXiv:2504.07866);
the weights hold one chip's share of the experts and of the vocabulary.

The program runs a published layer as TWO blocks, a token mixer then a
feed-forward, each one mixer between two norms; the weights arrive in
its tree (layer_{2l}/norm + attn + post_norm, layer_{2l+1}/norm +
mlp | experts + post_norm; the module under "mtp": embed_norm,
hidden_norm, proj, layer_0, layer_1, norm) and are read here a
published layer at a time.

Attention runs in ROW BLOCKS of queries against all keys, masked by
position; keys and values of all heads are expanded once a layer
([T, H, nope + rope] and [T, H, v]: a 12 k-token request at the
published widths holds 1.2 GB and 0.8 GB of them in float32; the
queries are multiplied out a row block at a time). The
experts are a plain loop over the experts held, each over every row
and weighed 0 where the row did not choose it.

Handed ``decisions`` ({layer name: int32 [T, k]}, a row of -1: no
record) it computes the experts it is handed, weighs them by ITS OWN
scores, and returns beside the logits one slack per position and
layer: its own k-th best selection score less the lowest selection
score among the handed ones: 0 when the sets are equal, never below.
The module's routed layer is judged under the name "mtp", at the
positions whose next token the sequence holds.

It is handed the benchmark's own seeded weights and upcasts them a
layer (an expert) at a time, so that it fits beside them."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
F32 = jnp.float32
ROW_BLOCK = 128        # queries an attention block
LENGTH_BUCKET = 4096   # long sequences are padded to a multiple of this
MTP = "mtp"            # the module's subtree, and its decision layer


def matmul(a, b):
    """a [..., k] @ b [k, n] in float32."""
    return jnp.matmul(a.astype(F32), b.astype(F32), precision=HIGHEST)


def rmsnorm(x, scale, eps: float):
    x = x.astype(F32)
    return x * jax.lax.rsqrt(
        jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def rope(x, theta: float, first=0):
    """x [T, H, D] at positions first .. first + T-1: the pairs are
    (x_i, x_{i + D/2}), each rotated by position * theta^(-2i / D)."""
    t, _heads, depth = x.shape
    freqs = jnp.exp(-jnp.log(F32(theta))
                    * jnp.arange(0, depth, 2, dtype=F32) / depth)
    angles = (first + jnp.arange(t)).astype(F32)[:, None] \
        * freqs[None, :]
    cos, sin = jnp.cos(angles)[:, None, :], jnp.sin(angles)[:, None, :]
    x1, x2 = x[..., :depth // 2], x[..., depth // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                           axis=-1)


def attention(a, w, *, heads: int, kv_rank: int, nope: int,
              rope_dim: int, theta: float, eps: float):
    """The latent attention mixer on normed a [T, d] -> [T, d], T a
    whole number of ROW_BLOCKs, in the EXPANDED form: every head's
    keys and values multiplied out of the compressed vector, the one
    rotary key appended to each head's key."""
    t = a.shape[0]
    c_q = rmsnorm(matmul(a, w["q_down"]["kernel"]),
                  w["q_norm"]["scale"], eps)
    down = matmul(a, w["kv_down"]["kernel"])
    c = rmsnorm(down[:, :kv_rank], w["kv_norm"]["scale"], eps)
    k_rope = rope(down[:, None, kv_rank:], theta)          # [T, 1, rope]
    kv = matmul(c, w["kv_up"]).reshape(t, heads, -1)
    keys = jnp.concatenate(
        [kv[..., :nope],
         jnp.broadcast_to(k_rope, (t, heads, rope_dim))], axis=-1)
    values = kv[..., nope:]
    scale = 1.0 / jnp.sqrt(F32(nope + rope_dim))
    j = jnp.arange(t)[None, :]

    def block(lo):
        # (the queries are multiplied out a row block at a time)
        rows = matmul(
            jax.lax.dynamic_slice_in_dim(c_q, lo, ROW_BLOCK),
            w["q_up"]["kernel"]).reshape(ROW_BLOCK, heads, -1)
        rows = jnp.concatenate(
            [rows[..., :nope], rope(rows[..., nope:], theta, lo)],
            axis=-1)
        scores = jnp.einsum("qhd,khd->hqk", rows, keys,
                            precision=HIGHEST) * scale
        i = lo + jnp.arange(ROW_BLOCK)[:, None]
        probs = jax.nn.softmax(
            jnp.where(j <= i, scores, -jnp.inf), axis=-1)
        return jnp.einsum("hqk,khd->qhd", probs, values,
                          precision=HIGHEST).reshape(ROW_BLOCK, -1)

    # (a sequence of one block without the loop around it)
    out = block(0) if t == ROW_BLOCK else jax.lax.map(
        block, jnp.arange(0, t, ROW_BLOCK))
    return matmul(out.reshape(t, -1), w["o_proj"]["kernel"])


def swiglu(m, gate, up, down):
    return matmul(jax.nn.silu(matmul(m, gate)) * matmul(m, up), down)


def route(m, w, handed, top_k: int, scale: float):
    """-> (the experts used [T, k], their weights [T, k], slack [T]).
    handed int32 [T, k]: a row of -1 takes the reference's own."""
    scores = jax.nn.sigmoid(matmul(m, w["router_kernel"]))
    select = scores + w["e_score_correction_bias"].astype(F32)
    own_select, own = jax.lax.top_k(select, top_k)
    use = jnp.where(handed[:, :1] >= 0, handed, own)
    slack = own_select[:, -1] - jnp.min(
        jnp.take_along_axis(select, use, axis=-1), axis=-1)
    picked = jnp.take_along_axis(scores, use, axis=-1)
    weights = picked / (jnp.sum(picked, axis=-1, keepdims=True)
                        + 1e-20) * scale
    return use, weights, slack


def routed_part(m, w, use, weights, first: int):
    """sum_i w_i Expert_i(m) over the used experts that are HELD
    (first .. first + E - 1), one held expert after another, each over
    every row and weighed 0 where it was not used."""
    def one(total, expert):
        index, gate, up, down = expert
        weight = jnp.sum(jnp.where(use == index, weights, 0.0), axis=-1)
        return total + weight[:, None] * swiglu(m, gate, up, down), None

    held = w["experts_up"].shape[0]
    total, _ = jax.lax.scan(
        one, jnp.zeros_like(m),
        (first + jnp.arange(held), w["experts_gate"], w["experts_up"],
         w["experts_down"]))
    return total


def experts(m, w, handed, *, top_k: int, scale: float, first: int):
    """The sparse feed-forward on normed m [T, d] -> ([T, d], slack
    [T]): the held part of the routed sum, and the shared expert
    once."""
    use, weights, slack = route(m, w, handed, top_k, scale)
    shared = swiglu(m, w["shared_gate"], w["shared_up"],
                    w["shared_down"])
    return routed_part(m, w, use, weights, first) + shared, slack


_ATTN = ("heads", "kv_rank", "nope", "rope_dim", "theta")


@functools.partial(jax.jit, static_argnames=_ATTN + (
    "top_k", "scale", "first", "eps"))
def layer(h, mixer, feed, handed, *, eps: float, top_k: int,
          scale: float, first: int, **sizes):
    """One published layer under sandwich norms: ``mixer`` the
    program's attn block ({"norm", "attn", "post_norm"}), ``feed`` its
    feed-forward block ({"norm", "mlp" | "experts", "post_norm"}).
    -> (h'', slack [T]: zeros for a dense layer)."""
    u = attention(rmsnorm(h, mixer["norm"]["scale"], eps),
                  mixer["attn"], eps=eps, **sizes)
    h = h + rmsnorm(u, mixer["post_norm"]["scale"], eps)
    m = rmsnorm(h, feed["norm"]["scale"], eps)
    if "mlp" in feed:
        w = feed["mlp"]
        f = swiglu(m, w["gate_proj"]["kernel"], w["up_proj"]["kernel"],
                   w["down_proj"]["kernel"])
        slack = jnp.zeros(h.shape[:1], F32)
    else:
        f, slack = experts(m, feed["experts"], handed, top_k=top_k,
                           scale=scale, first=first)
    return h + rmsnorm(f, feed["post_norm"]["scale"], eps), slack


@functools.partial(jax.jit, static_argnames=("eps",))
def head_logits(hidden, norm, lm_head, eps: float):
    """A final norm and the untied head: hidden [R, d] -> [R, vocab]."""
    return matmul(rmsnorm(hidden, norm["scale"], eps), lm_head)


@functools.partial(jax.jit, static_argnames=("eps",))
def mtp_input(embedded, hidden, w, eps: float):
    """x = [norm_e(Emb(next)) ; norm_h(h_L)] W_proj."""
    return matmul(jnp.concatenate(
        [rmsnorm(embedded, w["embed_norm"]["scale"], eps),
         rmsnorm(hidden, w["hidden_norm"]["scale"], eps)], axis=-1),
        w["proj"]["kernel"])


def _padded(length: int) -> int:
    padded = -(-length // LENGTH_BUCKET) * LENGTH_BUCKET
    while padded // 2 >= max(length, ROW_BLOCK):
        padded //= 2
    return padded


def stack_hidden(params, tokens, *, dense: tuple, decisions, **sizes):
    """The stack over ``tokens`` [T] (T a bucket's length) -> (h_L
    [T, d] before the final norm, {layer name: slack [T]} of the
    sparse layers)."""
    top_k = sizes["top_k"]
    h = params["embed"]["embedding"][tokens].astype(F32)
    own = jnp.full((tokens.shape[0], top_k), -1, jnp.int32)
    slacks = {}
    for l, is_dense in enumerate(dense):
        name = f"layer_{2 * l + 1}"
        handed = own if decisions is None or is_dense \
            else decisions[name]
        h, slack = layer(h, params[f"layer_{2 * l}"], params[name],
                         handed, **sizes)
        if not is_dense:
            slacks[name] = slack
    return h, slacks


def mtp_hidden(params, tokens, hidden, length: int, *, decisions,
               **sizes):
    """The module over the stack's ``hidden`` [T, d]: position i reads
    the embedding of token i+1 (the sequence's first ``length`` tokens
    are its own; the last position's next token is no part of it and
    a zero row stands in: nothing before it sees it) -> (x' [T, d]
    before the module's norm, slack [T], 0 from the last position
    on)."""
    w = params[MTP]
    following = jnp.concatenate([tokens[1:], tokens[:1] * 0])
    x = mtp_input(params["embed"]["embedding"][following].astype(F32),
                  hidden, w, sizes["eps"])
    handed = jnp.full((tokens.shape[0], sizes["top_k"]), -1,
                      jnp.int32) if decisions is None else decisions[MTP]
    x, slack = layer(x, w["layer_0"], w["layer_1"], handed, **sizes)
    judged = jnp.arange(tokens.shape[0]) < length - 1
    return x, jnp.where(judged, slack, 0.0)


def teacher_forced_logits(params, tokens, rows, *, dense: tuple,
                          heads: int, kv_rank: int, nope: int,
                          rope_dim: int, theta: float, top_k: int,
                          scale: float, first: int, eps: float,
                          decisions=None, mtp_rows=None):
    """One full forward over ``tokens`` [T] (no cache), a published
    layer at a time (``dense``: one entry a published layer); the
    logits of the positions in ``rows`` -> [len(rows), vocab] float32,
    and with ``decisions`` also {layer name: slack [T]}, named as the
    program names its experts blocks (layer_{2l+1}, and "mtp" for the
    module's, which is run whenever decisions name it). ``mtp_rows``:
    also the MODULE's logits at those positions (position i's predicts
    token i+2), third in the result. The sequence is padded at its end
    to a power of two from ROW_BLOCK up, and beyond LENGTH_BUCKET to a
    whole number of those (attention is causal and everything else is
    a function of the row alone, so no position that is read sees the
    padding)."""
    length = tokens.shape[0]
    padded = _padded(length)
    sizes = dict(heads=heads, kv_rank=kv_rank, nope=nope,
                 rope_dim=rope_dim, theta=float(theta), top_k=top_k,
                 scale=float(scale), first=int(first), eps=eps)
    if decisions is not None:
        decisions = {
            name: jnp.pad(value, ((0, padded - length), (0, 0)),
                          constant_values=-1)
            for name, value in decisions.items()}
    tokens = jnp.pad(tokens, (0, padded - length))
    h, slacks = stack_hidden(params, tokens, dense=dense,
                             decisions=decisions, **sizes)
    logits = head_logits(h[rows], params["final_norm"],
                         params["lm_head"]["kernel"], eps)
    with_module = mtp_rows is not None or (
        decisions is not None and MTP in decisions)
    if with_module:
        x, slacks[MTP] = mtp_hidden(params, tokens, h, length,
                                    decisions=decisions, **sizes)
    slacks = {name: slack[:length] for name, slack in slacks.items()}
    out = (logits,) if decisions is None else (logits, slacks)
    if mtp_rows is not None:
        out += (head_logits(x[mtp_rows], params[MTP]["norm"],
                            params["lm_head"]["kernel"], eps),)
    return out[0] if len(out) == 1 else out
