"""Decoder-only transformer LM: the framework's flagship distributed
workload (the TensorFlow-Distributed/ResNet recipe analog for the
long-context era).

TPU-first design decisions:
  - bfloat16 activations/params with float32 RMSNorm statistics and
    attention accumulation (MXU-friendly, HBM-light);
  - attention is pluggable via config.attention_fn so the same module
    runs single-chip flash (Pallas), blockwise (XLA scan), or ring
    attention over the sp mesh axis (ops/ring_attention.py);
  - rotary position embeddings computed from *global* positions so
    sequence-parallel shards agree;
  - SwiGLU MLP sized to keep matmuls MXU-tiled (multiples of 128);
  - optional per-layer remat (jax.checkpoint) to trade FLOPs for HBM.

Tensor-parallel sharding is applied from outside via parameter
PartitionSpec rules (parallel/sharding.py) — the module itself stays
sharding-agnostic, which is what lets XLA insert the collectives.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
from flax import linen as nn

from batch_shipyard_tpu.ops import attention as attn_ops
from batch_shipyard_tpu.ops import paged_attention as paged_ops


REMASK_RULES = ("low_confidence_static", "low_confidence_dynamic")


@dataclasses.dataclass(frozen=True)
class BlockDiffusion:
    """Generation by diffusion over blocks (TransformerConfig.
    block_diffusion): the sequence is cut into blocks of ``block``
    positions (a power of two), attention is BLOCK-causal (key j is
    visible to query i iff j // block <= i // block: causal across
    blocks, every key of the query's own block visible), the logit at
    position i scores the token AT position i, and a block is generated
    by iterated denoising: it opens as ``mask_id`` at every position
    that is not given, each pass runs the whole block against the
    cached blocks before it and unmasks, among the masked positions,
    the ``block // steps`` most confident (``remask``
    "low_confidence_static"), or every position whose confidence is
    above ``threshold`` where those are at least as many
    ("low_confidence_dynamic"); a block without a mask is committed by
    one pass more, which keeps its K/V rows (models/serving.py:
    _denoise_or_commit). Greedy: a position's token is its argmax, its
    confidence that token's softmax probability.
    ``bidirectional`` False keeps the plain causal mask inside a block
    too, in the prefill and in the block step: not the model, but what
    a check's control switches on."""
    block: int = 4
    steps: int = 4
    remask: str = "low_confidence_static"
    threshold: float = 0.9
    mask_id: int = 0
    bidirectional: bool = True

    def __post_init__(self):
        if self.block < 1 or self.block & (self.block - 1):
            raise ValueError(f"block {self.block}: a power of two")
        if not 1 <= self.steps <= self.block or self.block % self.steps:
            raise ValueError(
                f"steps {self.steps} does not divide block {self.block}")
        if self.remask not in REMASK_RULES:
            raise ValueError(f"remask {self.remask!r}: one of "
                             f"{REMASK_RULES}")


@dataclasses.dataclass(frozen=True)
class LatentKV:
    """Multi-head latent attention (TransformerConfig.latent): the
    query comes through a low rank with a norm of its own (``q_rank``),
    and a token's keys and values for ALL heads come out of ONE
    compressed vector c of ``kv_rank`` channels (normed) beside ONE
    rotary key of ``rope_dim`` channels that every head shares. A
    head's query and key are ``nope_dim`` unrotated channels followed
    by the ``rope_dim`` rotated ones (scores scaled by
    1 / sqrt(nope_dim + rope_dim)), its value ``v_dim`` channels; keys
    and values are c times one up-projection [kv_rank,
    H * (nope_dim + v_dim)]. The cache holds the ROW [c ; rotary key]
    a token a layer and nothing a head (LatentAttention below), padded
    with zeros to whole lane tiles (``row_lanes``: a TPU lays a row of
    576 lanes out as 640 whatever it is told, and a copy of the 576
    alone is one it refuses)."""
    q_rank: int = 1536
    kv_rank: int = 512
    nope_dim: int = 128
    rope_dim: int = 64
    v_dim: int = 128

    @property
    def qk_dim(self) -> int:
        return self.nope_dim + self.rope_dim

    @property
    def row_lanes(self) -> int:
        return -(-(self.kv_rank + self.rope_dim) // 128) * 128


@dataclasses.dataclass(frozen=True)
class TransformerConfig:
    vocab_size: int = 32000
    d_model: int = 512
    n_layers: int = 4
    n_heads: int = 8
    d_head: int = 64
    d_ff: int = 1408          # SwiGLU hidden (multiple of 128)
    max_seq_len: int = 2048
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    remat: bool = False
    attention_fn: Optional[Callable] = None  # (q,k,v,causal)->out
    rope_theta: float = 10000.0
    # Mixture-of-experts: replace the MLP of every `moe_every`-th
    # block with routed experts (ep-shardable). None = dense.
    moe: Optional[Any] = None        # models.moe.MoEConfig
    moe_every: int = 2
    moe_aux_weight: float = 0.01
    # Autoregressive decode mode: attention maintains a KV cache (flax
    # 'cache' collection) and consumes one token step per call.
    decode: bool = False
    max_decode_len: int = 2048
    # Fuse each block's RMSNorm into its first projection matmul via
    # the Pallas kernel (ops/fused_norm.py): q/k/v collapse into one
    # [d, 3F] matmul and gate/up into one [d, 2*d_ff] matmul, with the
    # normalized activation never touching HBM. Changes the parameter
    # layout (qkv_kernel / gate_up_kernel instead of per-projection
    # Dense kernels) — opt-in, mutually exclusive with tp_axis /
    # quantize_matmuls / decode.
    fused_norm: bool = False
    # Run projection/MLP matmuls through the int8 Pallas kernels
    # (ops/quantization.py): both operands quantized per-row with
    # stochastic rounding, int32 MXU accumulation (2x the bf16 rate on
    # v5e), full-precision QAT backward. Opt-in — changes numerics.
    quantize_matmuls: bool = False
    # Quantize the decode KV cache (dense rows OR the paged pool) to
    # int8 with per-(position, head) scales: K/V absmax-quantize on
    # write and dequantize fused into the attention matmuls on read
    # (in-kernel per tile on the Pallas paged path) — half the HBM
    # per cached token vs bf16, so 2x the decode slots/context per
    # chip. Opt-in ("int8"); changes numerics within quantization
    # noise.
    kv_cache_dtype: Optional[str] = None
    # Paged KV cache for decode (vLLM-style): slots hold page-index
    # block tables into a shared page pool instead of reserving
    # max_decode_len rows each. None = dense cache.
    kv_page_size: Optional[int] = None
    kv_num_pages: int = 0
    # Speculative-decode write margin for the PAGED cache: widens each
    # slot's block table by ceil(spec_window/page) entries so a
    # draft/verify block starting near max_decode_len can spill its
    # (never-committed) tail writes past the logical length without
    # the table gather clamping onto a REAL page of the same slot.
    # The extra entries default to the allocator's scratch page, which
    # absorbs the garbage. Set by the serving engine to gamma; the
    # dense cache needs no margin (out-of-bounds scatters drop).
    spec_window: int = 0
    # Paged decode attention implementation: 'kernel' (Pallas, reads
    # only live pages), 'xla' (gather over the full table width), or
    # None = kernel on TPU and xla elsewhere, for every pool; which
    # kernel (MHA or grouped/windowed) the pool and the layer decide
    # (ops/paged_attention.paged_decode_road).
    paged_attention_impl: Optional[str] = None
    # DENSE int8 decode attention implementation: 'kernel' (Pallas,
    # int8 cache + per-(position, head) scales dequantized in VMEM
    # per tile — HBM holds int8 + scales only), 'xla' (dequant
    # multiply outside the kernel, fused — or not — by XLA), or
    # None = kernel on TPU and xla elsewhere
    # (ops/decode_attention.resolve_dense_decode_impl).
    decode_attention_impl: Optional[str] = None
    # Megatron-style tensor parallelism INSIDE a shard_map body (the
    # pipeline path): q/k/v/gate/up are column-sharded and
    # o_proj/down_proj row-sharded over this mesh axis, with explicit
    # psums after the row-sharded matmuls. The module then sees LOCAL
    # head/ff counts (configure n_heads/d_ff divided by tp). The
    # global-view jit path leaves this None — there XLA inserts the
    # collectives from parameter shardings.
    tp_axis: Optional[str] = None
    # Fewer K/V heads than query heads (grouped-query attention):
    # n_heads // n_kv_heads query heads read each K/V head, and the
    # cache (dense rows, paged pool [P, page, n_kv_heads * d_head])
    # holds the K/V heads alone. None = n_heads.
    n_kv_heads: Optional[int] = None
    # False: attention applies no positional embedding (a stack whose
    # stateful layers carry position).
    use_rope: bool = True
    # True: attention's output is gated elementwise before o_proj,
    # o_proj(attn * sigmoid(gate_proj(x))), the gate a projection of
    # its own to n_heads * d_head.
    attn_output_gate: bool = False
    # RMSNorm epsilon of every block norm and of the final norm.
    norm_eps: float = 1e-6
    # False: a separate lm_head [d_model, vocab] instead of the
    # transposed embedding.
    tie_embeddings: bool = True
    # One kind per layer, in order (layer_kinds): "dense" (attention
    # + SwiGLU MLP, each after a norm), "dense_moe" (its MLP replaced
    # by ``moe``'s capacity-routed experts), or a block that is ONE
    # mixer after ONE norm: "ssm" (models/ssm.py, sized by ``ssm``),
    # "delta" (models/delta.py, sized by ``delta``), "attn" (the
    # attention above alone), "experts" (moe.RoutedExperts, sized by
    # ``experts``), "mlp" (the SwiGLU MLP above alone, d_ff wide: a
    # dense feed-forward layer among routed ones). None = "dense"
    # throughout, "dense_moe" at every moe_every-th layer when ``moe``
    # is set.
    block_kinds: Optional[tuple] = None
    ssm: Optional[Any] = None        # models.ssm.SSMConfig
    delta: Optional[Any] = None      # models.delta.DeltaConfig
    experts: Optional[Any] = None    # models.moe.RoutedConfig
    # One entry per layer, beside block_kinds (an entry of a layer
    # without attention is ignored). layer_windows: the layer's
    # sliding window, 0 = full attention: key j is visible to query i
    # iff j <= i and (window == 0 or j > i - window), the query's own
    # key counted. A window layer of a PAGED decode cache keeps a
    # slot-owned ring of ring_pages(cfg, window) pages instead of a
    # block table into the pool (Attention._decode_attend_ring).
    # layer_rope: whether the layer rotates q and k. None = no window
    # anywhere / use_rope everywhere.
    layer_windows: Optional[tuple] = None
    layer_rope: Optional[tuple] = None
    # True: the router of an "experts" block reads the normed INPUT of
    # the mixer block before it (a layer whose router is placed before
    # its token mixer; the experts' matmuls still read their own
    # norm's output).
    router_before_mixer: bool = False
    # True: a decode-mode multi-token insert (a serving prefill)
    # attends in blocks over the keys its mask admits
    # (ops/attention.cached_prefill_attention: bounded by the segment
    # and the window, a Pallas kernel on a TPU) instead of one masked
    # softmax over [chunk, max_decode_len] scores, and the dense cache
    # keeps its rows as that kernel reads them, [B, T, Hkv*D].
    prefill_blocks: bool = False
    # The type a window or grouped-kernel layer's decode attention
    # and a prefill_blocks insert keep their softmax's scores and
    # running terms in (ops/attention.kept_in). bfloat16 is the
    # nearest precision below: what a check's control switches on.
    attn_softmax_dtype: Any = jnp.float32
    # True: q and k pass an RMSNorm over each head's d_head channels
    # (one learned scale of d_head a layer each, eps norm_eps) after
    # their projections and before any rotation.
    qk_norm: bool = False
    # Multi-token-prediction modules behind the stack (0 or 1; MTPModule
    # below): at position i the module reads the embedding of token
    # i+1 and the stack's last hidden state BEFORE the final norm and
    # predicts token i+2 through the model's own embedding and head.
    # Its parameters are the subtree "mtp" of the same tree, its K/V a
    # subtree "mtp" of the same cache (a full layer: a block table into
    # the same pool). A serving engine whose model has one drafts with
    # it (models/serving.py); a forward that does not ask for it is
    # what it was. mtp_rope: whether the module's attention rotates q
    # and k (None = use_rope).
    mtp_modules: int = 0
    mtp_rope: Optional[bool] = None
    # Generation by diffusion over blocks (BlockDiffusion above): every
    # attention layer's mask is block-causal, and a serving engine
    # whose model has one generates a block of positions a slot by
    # iterated denoising in place of one token a step
    # (models/serving.py). None = autoregressive.
    block_diffusion: Optional[BlockDiffusion] = None
    # Multi-head latent attention (LatentKV above) in every "attn"
    # block and in a multi-token-prediction module's: the cache is one
    # leaf of latent rows a layer (no V leaf, no head axis), a paged
    # decode call computes the ABSORBED form over it, a prefill the
    # expanded one. n_kv_heads, d_head and qk_norm are not read.
    # None = the attention above.
    latent: Optional[LatentKV] = None
    # True: a block that is one mixer after one norm (MixerBlock, a
    # multi-token-prediction module's two among them) norms the
    # mixer's OUTPUT too before adding it, x + post(Mixer(norm(x))),
    # "post_norm" beside "norm" in its tree.
    sandwich_norm: bool = False

    @property
    def kv_heads(self) -> int:
        return self.n_kv_heads or self.n_heads

    @property
    def attend_block(self) -> int:
        """The block of a block-causal attention mask (0: plain
        causal): block_diffusion's, unless its control keeps the mask
        inside a block causal."""
        diffusion = self.block_diffusion
        return diffusion.block if diffusion is not None and \
            diffusion.bidirectional else 0


MIXER_KINDS = ("ssm", "delta", "attn", "experts", "mlp")
# ... of which these keep a fixed-size state per slot in the cache
# (the leaves their modules declare: inference.SLOT_STATE_LEAVES)
STATEFUL_KINDS = ("ssm", "delta")


def layer_kinds(cfg: TransformerConfig) -> tuple:
    """The kind of every layer, in order."""
    if cfg.mtp_modules not in (0, 1):
        raise NotImplementedError(
            f"mtp_modules {cfg.mtp_modules}: one module at most")
    if cfg.block_kinds is not None:
        kinds = tuple(cfg.block_kinds)
        if len(kinds) != cfg.n_layers or any(
                kind not in MIXER_KINDS + ("dense", "dense_moe")
                for kind in kinds):
            raise ValueError(f"block_kinds {kinds!r} is not one known "
                             f"kind for each of {cfg.n_layers} layers")
        return kinds
    stride = max(cfg.moe_every, 1)
    return tuple(
        "dense_moe" if cfg.moe is not None and i % stride == stride - 1
        else "dense" for i in range(cfg.n_layers))


# The multi-token-prediction module's name: its subtree of the
# parameters and of the cache, and, where its feed-forward is routed,
# the name its choices are recorded under.
MTP_NAME = "mtp"


def decision_layer_names(cfg: TransformerConfig) -> tuple:
    """The layers that choose experts per position and record the
    choice ("experts" blocks), by their names in the tree."""
    names = tuple(f"layer_{i}" for i, kind in enumerate(layer_kinds(cfg))
                  if kind == "experts")
    return names + (MTP_NAME,) if mtp_routed(cfg) else names


def mtp_routed(cfg: TransformerConfig) -> bool:
    """Whether the model has a multi-token-prediction module whose
    feed-forward chooses experts (``experts`` set: the module's layer
    is attn + experts; else attn + mlp)."""
    return bool(cfg.mtp_modules) and cfg.experts is not None


ATTENTION_KINDS = ("dense", "dense_moe", "attn")


def layer_window(cfg: TransformerConfig, idx: int) -> int:
    """Layer idx's sliding window (0 = full attention)."""
    if cfg.layer_windows is None:
        return 0
    if len(cfg.layer_windows) != cfg.n_layers:
        raise ValueError(f"layer_windows {cfg.layer_windows!r} is not "
                         f"one entry for each of {cfg.n_layers} layers")
    return int(cfg.layer_windows[idx])


def layer_rope(cfg: TransformerConfig, idx: int) -> bool:
    """Whether layer idx rotates q and k."""
    if cfg.layer_rope is None:
        return cfg.use_rope
    if len(cfg.layer_rope) != cfg.n_layers:
        raise ValueError(f"layer_rope {cfg.layer_rope!r} is not one "
                         f"entry for each of {cfg.n_layers} layers")
    return bool(cfg.layer_rope[idx])


def attention_windows(cfg: TransformerConfig) -> tuple:
    """The window of every layer that keeps K/V, in layer order; a
    multi-token-prediction module's attention (full) last."""
    return tuple(layer_window(cfg, i)
                 for i, kind in enumerate(layer_kinds(cfg))
                 if kind in ATTENTION_KINDS) + (0,) * cfg.mtp_modules


def ring_pages(cfg: TransformerConfig, window: int) -> int:
    """Pages a slot's ring holds in a window layer of the paged
    cache: ceil((window + drafts) / page) + 1, drafts being
    cfg.spec_window, the tokens a step may write beyond the one it
    commits (0 for a one-token step: ceil(window / page) + 1). The
    ring then holds window + drafts keys whole, so that the keys the
    FIRST position of a verify block still sees and the row its LAST
    position writes straddle that many pages at most, and a rejected
    draft's write lands on a key every query of the block, and the
    rewound next step's, has already left. Never more than a whole
    context's."""
    page = cfg.kv_page_size
    return min(-(-(window + cfg.spec_window) // page) + 1,
               -(-(cfg.max_decode_len + cfg.spec_window) // page))


def paged_layer_count(cfg: TransformerConfig) -> int:
    """How many layers keep K/V behind a block table into the page
    pool: the attention layers without a window (a window layer's
    ring is the slot's own and needs none)."""
    return sum(not window for window in attention_windows(cfg))


def has_slot_state(cfg: TransformerConfig) -> bool:
    """Whether a slot holds a fixed-size state beside its K/V: one
    that no page names, so a prefix's pages cannot stand in for it."""
    return any(kind in STATEFUL_KINDS for kind in layer_kinds(cfg))


def collect_decisions(sown, cfg: TransformerConfig, mtp: bool = False):
    """The "decisions" collection of one apply -> int32
    [decision layers, B, T, k] in layer order, or None for a model
    with no such layer. ``mtp``: of an apply of the multi-token-
    prediction module alone ([1, B, T, k], or None where its
    feed-forward is not routed); an apply of the stack gives the
    stack's layers alone."""
    if mtp:
        if not mtp_routed(cfg):
            return None
        return sown[MTP_NAME]["layer_1"]["experts"]["chosen"][0][None]
    names = [name for name in decision_layer_names(cfg)
             if name != MTP_NAME]
    if not names:
        return None
    return jnp.stack([sown[name]["experts"]["chosen"][0]
                      for name in names])


def output_logits(cfg: TransformerConfig, params, hidden):
    """float32 logits of final-normed hidden states [..., d] through
    the model's head: the transposed embedding, or lm_head."""
    hidden = hidden.astype(jnp.float32)
    if cfg.tie_embeddings:
        return jnp.dot(hidden, params["embed"]["embedding"].astype(
            jnp.float32).T)
    return jnp.dot(hidden, params["lm_head"]["kernel"].astype(
        jnp.float32))


def rotary_embedding(x, positions, theta: float):
    """Apply RoPE. x: [B, T, H, D]; positions: [T] global positions
    shared across the batch, or [B, T] per-sequence positions (the
    continuous-batching decode case, where each slot sits at its own
    depth)."""
    depth = x.shape[-1]
    freqs = jnp.exp(
        -jnp.log(theta) *
        jnp.arange(0, depth, 2, dtype=jnp.float32) / depth)
    angles = positions[..., None].astype(jnp.float32) * freqs
    if positions.ndim == 1:
        cos = jnp.cos(angles)[None, :, None, :]   # [1, T, 1, D/2]
        sin = jnp.sin(angles)[None, :, None, :]
    else:
        cos = jnp.cos(angles)[:, :, None, :]      # [B, T, 1, D/2]
        sin = jnp.sin(angles)[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    rotated = jnp.concatenate(
        [x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return rotated.astype(x.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def tp_region_input(x, axis_name: str):
    """Megatron's "f" operator: identity forward, psum backward.

    Placed where a REPLICATED activation enters a tensor-parallel
    region (column-sharded matmuls): each tp member's backward
    produces only its shard's partial cotangent, and this is the
    point where those partials sum. Explicit custom_vjp — psum's AD
    transpose under shard_map is exactly the thing one should not
    lean on.
    """
    return x


def _tpi_fwd(x, axis_name):
    return x, None


def _tpi_bwd(axis_name, _res, g):
    return (jax.lax.psum(g, axis_name),)


tp_region_input.defvjp(_tpi_fwd, _tpi_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def tp_region_output(x, axis_name: str):
    """Megatron's "g" operator: psum forward, identity backward.

    Placed where a tensor-parallel region's row-sharded partial sums
    leave it: forward reduces the partials; backward passes the
    (replicated) cotangent straight through to every member.
    """
    return jax.lax.psum(x, axis_name)


def _tpo_fwd(x, axis_name):
    return jax.lax.psum(x, axis_name), None


def _tpo_bwd(axis_name, _res, g):
    return (g,)


tp_region_output.defvjp(_tpo_fwd, _tpo_bwd)


class RMSNorm(nn.Module):
    eps: float = 1e-6
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, x):
        scale = self.param("scale", nn.initializers.ones, (x.shape[-1],),
                           jnp.float32)
        norm = jnp.asarray(x, jnp.float32)
        norm = norm * jax.lax.rsqrt(
            jnp.mean(norm * norm, axis=-1, keepdims=True) + self.eps)
        return (norm * scale).astype(self.dtype)


class Attention(nn.Module):
    config: TransformerConfig
    # this layer's sliding window (0 = full attention) and whether it
    # rotates q and k (None = config.use_rope): TransformerConfig's
    # layer_windows / layer_rope, handed down by the block
    window: int = 0
    rope: Optional[bool] = None

    @nn.compact
    def __call__(self, x, positions, live=None):
        cfg = self.config
        features = cfg.n_heads * cfg.d_head
        kv_features = cfg.kv_heads * cfg.d_head
        if cfg.n_heads % cfg.kv_heads:
            raise ValueError(
                f"n_heads {cfg.n_heads} is not a multiple of "
                f"n_kv_heads {cfg.kv_heads}")
        dense = functools_partial_dense(cfg)
        if cfg.fused_norm:
            if kv_features != features:
                raise NotImplementedError(
                    "fused_norm projects q, k and v as one [d, 3F] "
                    "matmul: no grouped-query attention")
            # x arrives UN-normed; the block's attn RMSNorm is fused
            # into one [d, 3F] qkv projection (ops/fused_norm.py).
            from batch_shipyard_tpu.ops import fused_norm as fn_ops
            norm_scale = self.param(
                "norm_scale", nn.initializers.ones,
                (x.shape[-1],), jnp.float32)
            qkv_kernel = self.param(
                "qkv_kernel", nn.initializers.lecun_normal(),
                (x.shape[-1], 3 * features), cfg.param_dtype)
            batch, seq = x.shape[0], x.shape[1]
            qkv = fn_ops.rmsnorm_matmul(
                x.reshape(batch * seq, -1), norm_scale,
                qkv_kernel.astype(cfg.dtype))
            q, k, v = jnp.split(
                qkv.reshape(batch, seq, 3 * features), 3, axis=-1)
        else:
            if cfg.tp_axis:
                x = tp_region_input(x, cfg.tp_axis)
            q = dense(features, "q_proj")(x)
            k = dense(kv_features, "k_proj")(x)
            v = dense(kv_features, "v_proj")(x)
            batch, seq = x.shape[0], x.shape[1]
        q = q.reshape(batch, seq, cfg.n_heads, cfg.d_head)
        k = k.reshape(batch, seq, cfg.kv_heads, cfg.d_head)
        v = v.reshape(batch, seq, cfg.kv_heads, cfg.d_head)
        if cfg.qk_norm:
            q = RMSNorm(eps=cfg.norm_eps, dtype=cfg.dtype,
                        name="q_norm")(q)
            k = RMSNorm(eps=cfg.norm_eps, dtype=cfg.dtype,
                        name="k_norm")(k)
        if cfg.use_rope if self.rope is None else self.rope:
            q = rotary_embedding(q, positions, cfg.rope_theta)
            k = rotary_embedding(k, positions, cfg.rope_theta)
        if cfg.decode:
            if cfg.tp_axis:
                raise NotImplementedError(
                    "tp_axis is a training-path (shard_map pipeline) "
                    "feature; the decode path would return "
                    "un-reduced o_proj partial sums")
            if cfg.kv_cache_dtype not in (None, "int8"):
                raise ValueError(
                    f"kv_cache_dtype={cfg.kv_cache_dtype!r}: only "
                    f"'int8' (or None) is supported")
            out = (self._decode_attend_paged(q, k, v, live)
                   if cfg.kv_page_size else self._decode_attend(q, k, v))
            return dense(cfg.d_model, "o_proj")(self._gated(
                out.reshape(batch, seq, features), x))
        attention_fn = cfg.attention_fn or (
            lambda q_, k_, v_, causal: attn_ops.attention(
                q_, k_, v_, causal=causal))
        if self.window or cfg.attend_block:
            # the band, or the block-causal mask, over grouped K/V as
            # they are
            out = attn_ops.blockwise_mha(
                q, k, v, causal=True, window=self.window,
                block_size=math.gcd(seq, 512), block=cfg.attend_block)
        else:
            if cfg.kv_heads != cfg.n_heads:
                # The training-path kernels take one K/V head a query
                # head: each K/V head repeated for its group.
                group = cfg.n_heads // cfg.kv_heads
                k = jnp.repeat(k, group, axis=2)
                v = jnp.repeat(v, group, axis=2)
            out = attention_fn(q, k, v, causal=True)
        out = self._gated(out.reshape(batch, seq, features), x)
        out = dense(cfg.d_model, "o_proj")(out)
        if cfg.tp_axis:
            # Row-sharded o_proj: each tp member holds a partial sum.
            out = tp_region_output(out, cfg.tp_axis)
        return out

    def _gated(self, out, x):
        """attn_output_gate: the heads' outputs [B, T, F] times
        sigmoid(gate_proj(x)), elementwise, before o_proj."""
        cfg = self.config
        if not cfg.attn_output_gate:
            return out
        if cfg.fused_norm:
            raise NotImplementedError(
                "attn_output_gate reads the normed input, which "
                "fused_norm never materializes")
        gate = functools_partial_dense(cfg)(out.shape[-1], "gate_proj")(x)
        return out * jax.nn.sigmoid(gate.astype(jnp.float32)).astype(
            out.dtype)

    def _decode_attend(self, q, k, v):
        """Cache-writing decode attention. seq == 1 is the per-token
        decode step; seq > 1 is BATCHED PREFILL / chunked insert: all
        seq K/V rows land in the cache in one scatter and the queries
        attend causally over the cache in one MXU-batched pass —
        prefill wall-clock is one forward instead of L sequential
        micro-steps (VERDICT r2 order #2).

        The write index is PER SLOT ([B] int32), so independent
        sequences at different depths share one batched cache — the
        requirement for continuous batching (models/serving.py).
        Multi-token inserts start at each slot's current index."""
        cfg = self.config
        int8_kv = cfg.kv_cache_dtype == "int8"  # validated at dispatch
        store_dtype = jnp.int8 if int8_kv else cfg.dtype
        batch, seq, heads, depth = q.shape
        kv_heads = k.shape[2]
        # prefill_blocks: the rows as the blockwise prefill's kernel
        # reads them, heads folded into the lanes
        folded = cfg.prefill_blocks
        if int8_kv and (folded or self.window):
            raise NotImplementedError(
                "no int8 KV under prefill_blocks or a window")
        row = (kv_heads * depth,) if folded else (kv_heads, depth)
        cache_k = self.variable(
            "cache", "k", jnp.zeros,
            (batch, cfg.max_decode_len) + row, store_dtype)
        cache_v = self.variable(
            "cache", "v", jnp.zeros,
            (batch, cfg.max_decode_len) + row, store_dtype)
        if int8_kv:
            # Per-(position, head) absmax scales; fp32 so dequant
            # error is the int8 rounding alone.
            scale_k = self.variable(
                "cache", "k_scale", jnp.zeros,
                (batch, cfg.max_decode_len, kv_heads), jnp.float32)
            scale_v = self.variable(
                "cache", "v_scale", jnp.zeros,
                (batch, cfg.max_decode_len, kv_heads), jnp.float32)

        if int8_kv:
            from batch_shipyard_tpu.ops.quantization import (
                quantize_int8_rows as quantize)

        index = self.variable(
            "cache", "index", lambda: jnp.zeros((batch,), jnp.int32))
        idx = index.value  # [B]
        key_pos = jax.lax.broadcasted_iota(
            jnp.int32, (cfg.max_decode_len, 1), 0)[:, 0]
        if seq == 1:
            rows = jnp.arange(batch)
            k_in, v_in = k[:, 0], v[:, 0]
            if int8_kv:
                k_in, ks = quantize(k_in)
                v_in, vs = quantize(v_in)
                scale_k.value = scale_k.value.at[rows, idx].set(ks)
                scale_v.value = scale_v.value.at[rows, idx].set(vs)
            cache_k.value = cache_k.value.at[rows, idx].set(
                k_in.astype(store_dtype).reshape(batch, *row))
            cache_v.value = cache_v.value.at[rows, idx].set(
                v_in.astype(store_dtype).reshape(batch, *row))
            index.value = idx + 1
            visible = key_pos[None, :] <= idx[:, None]
            if self.window:
                visible &= key_pos[None, :] > idx[:, None] - self.window
            mask = visible[:, None, None, :]
        else:
            rows = jnp.arange(batch)[:, None]                 # [B, 1]
            cols = idx[:, None] + jnp.arange(seq)[None, :]    # [B, S]
            k_in, v_in = k, v
            if int8_kv:
                k_in, ks = quantize(k_in)
                v_in, vs = quantize(v_in)
                scale_k.value = scale_k.value.at[rows, cols].set(ks)
                scale_v.value = scale_v.value.at[rows, cols].set(vs)
            cache_k.value = cache_k.value.at[rows, cols].set(
                k_in.astype(store_dtype).reshape(batch, seq, *row))
            cache_v.value = cache_v.value.at[rows, cols].set(
                v_in.astype(store_dtype).reshape(batch, seq, *row))
            index.value = idx + seq
            if folded:
                # in blocks, over the rows the mask admits alone
                return attn_ops.cached_prefill_attention(
                    q, cache_k.value, cache_v.value, idx,
                    window=self.window,
                    softmax_dtype=cfg.attn_softmax_dtype,
                    block=cfg.attend_block).astype(cfg.dtype)
            # Causal over absolute cache positions: query s (absolute
            # idx+s) sees keys <= idx+s — earlier chunks AND the
            # causal prefix of this one (of a block-diffusion model:
            # the keys up to the end of its own block).
            visible = key_pos[None, None, :] <= attn_ops.block_end(
                cols, cfg.attend_block)[:, :, None]
            if self.window:
                visible &= key_pos[None, None, :] > \
                    cols[:, :, None] - self.window
            mask = visible[:, None, :, :]             # [B, 1, S, T]
        if int8_kv and seq == 1 and kv_heads == heads:
            # Single-token decode dispatches through
            # ops/decode_attention: impl='kernel' dequantizes the
            # int8 rows + scales in VMEM tile by tile (no dequantized
            # cache ever exists in HBM — the dense_decode_hlo check
            # pins that on the compiled step); 'xla' is the
            # dequant+einsum reference formulation. lengths =
            # keys visible to the query = idx + 1 (the key_pos <= idx
            # mask below, as a count).
            from batch_shipyard_tpu.ops import decode_attention as dd
            return dd.dense_decode_attention(
                q, cache_k.value, cache_v.value, scale_k.value,
                scale_v.value, idx + 1,
                impl=cfg.decode_attention_impl).astype(cfg.dtype)
        if int8_kv:
            # Multi-token prefill/insert path: dequant is elementwise
            # on the matmul operands — XLA fuses it into the dots (a
            # bet the int8_kv_dequant_fusion check measures); HBM
            # holds int8 + scales only
            # (ops/quantization.dequantize_int8 is the shared
            # contract partner of the quantize above).
            from batch_shipyard_tpu.ops import quantization as qz
            k_all = qz.dequantize_int8(
                cache_k.value,
                scale_k.value[..., None]).astype(cfg.dtype)
            v_all = qz.dequantize_int8(
                cache_v.value,
                scale_v.value[..., None]).astype(cfg.dtype)
        else:
            k_all, v_all = (cache.value.reshape(
                batch, cfg.max_decode_len, kv_heads, depth)
                for cache in (cache_k, cache_v))
        return paged_ops.masked_attention(q, k_all, v_all, mask, cfg.dtype)

    def _decode_attend_ring(self, q, k, v, live=None):
        """A WINDOW layer's paged decode attention: the slot keeps its
        newest keys in a ring of its own, ring_pages(cfg, window)
        pages of the layer's [B * ring, page, Hkv*D] leaves (slot b's
        are b * ring .. (b + 1) * ring - 1; position p is row p % page
        of ring page (p // page) % ring), never more however long the
        context grows: no block table, no page of the shared pool, no
        books on the host. The window's keys straddle ring - 1 pages
        at most, so the row a step writes overwrites a key the window
        has already left. One token a call, or a verify block of up to
        spec_window + 1 (position r of it sees the keys up to its own
        and, of them, its newest ``window``; ring_pages counts the
        block in); a prefill fills the ring from its batch-1 cache
        (serving._prefill_paged). ``live``: _decode_attend_paged's."""
        cfg = self.config
        batch, seq, heads, depth = q.shape
        if seq > cfg.spec_window + 1 or cfg.kv_cache_dtype is not None:
            raise NotImplementedError(
                "a window layer's ring takes spec_window + 1 tokens a "
                "call at most, in the served type (no int8)")
        page = cfg.kv_page_size
        ring = ring_pages(cfg, self.window)
        width = k.shape[2] * depth
        k_ring = self.variable(
            "cache", "k_ring", jnp.zeros,
            (batch * ring, page, width), cfg.dtype)
        v_ring = self.variable(
            "cache", "v_ring", jnp.zeros,
            (batch * ring, page, width), cfg.dtype)
        length = self.variable(
            "cache", "length", lambda: jnp.zeros((batch,), jnp.int32))
        idx = length.value                                   # [B]
        table = jnp.arange(batch * ring, dtype=jnp.int32).reshape(
            batch, ring)
        if seq == 1:
            # (kept apart from the block's form below, which holds it:
            # the one-token step's lowering stays the recorded one)
            page_idx = table[:, 0] + (idx // page) % ring
            offset = idx % page
            rows = (batch, width)
        else:
            cols = idx[:, None] + jnp.arange(seq)[None, :]    # [B, S]
            page_idx = table[:, :1] + (cols // page) % ring
            offset = cols % page
            rows = (batch, seq, width)
        k_ring.value = k_ring.value.at[page_idx, offset].set(
            k.astype(cfg.dtype).reshape(rows))
        v_ring.value = v_ring.value.at[page_idx, offset].set(
            v.astype(cfg.dtype).reshape(rows))
        length.value = idx + seq
        return paged_ops.paged_decode_attention(
            q, k_ring.value, v_ring.value, table,
            _live_lengths(length.value, live),
            impl=cfg.paged_attention_impl, window=self.window,
            softmax_dtype=cfg.attn_softmax_dtype).astype(cfg.dtype)

    def _decode_attend_paged(self, q, k, v, live=None):
        """Paged decode attention (vLLM-style block tables): K/V live
        in a SHARED page pool [P, page, H*D]; each slot owns a row of
        page indices (block_table) covering only its actual length —
        the memory win over the dense cache is that the pool is sized
        for aggregate live tokens, not num_slots * max_decode_len.

        The pool is STORED as the decode kernel blocks it
        (ops/paged_attention.py: one [page, H*D] tile a page), heads
        folded into the lane dimension. On a TPU a [.., H, D] array and
        its [.., H*D] reshape are tiled differently, so a pool kept
        [P, page, H, D] is relaid out whole, every layer, every step,
        on its way into the kernel; kept as the kernel reads it, the
        row scatter below and the kernel share one buffer and a step
        that is given the cache to consume (models/serving.py donates
        it) touches only the rows it writes and the pages it reads.

        block_table/length are duplicated per layer (tiny int arrays)
        so everything stays inside the flax cache collection; the
        serving engine's page allocator mutates every layer's copy
        identically (models/serving.py).

        ``live`` ([B] bool, None = every slot): the serving step's
        mask of the slots that hold a request (int32 [B] from a step
        that feeds two blocks a slot: how many of the slot's query
        positions anybody reads, 0 for a slot without a request; the
        kernel passes over a dead second block, paged_ops.
        gqa_paged_decode_attention_kernel). A slot without one
        stays a row of the full-batch step: its row is written (the
        scratch page, where its table points, absorbs it) and its
        cursor advances like any other, but the attention call is
        handed length 0 for it, which every road of
        paged_ops.paged_decode_road answers with zeros and the kernels
        with no page fetched and no tile computed. The mask, never
        ``cursor == 0``, says which: a live slot decoding its first
        key from an empty cache has that cursor and attends its key.
        """
        cfg = self.config
        if self.window:
            return self._decode_attend_ring(q, k, v, live)
        int8_kv = cfg.kv_cache_dtype == "int8"  # validated at dispatch
        store_dtype = jnp.int8 if int8_kv else cfg.dtype
        batch, seq, heads, depth = q.shape
        page = cfg.kv_page_size
        if seq > cfg.spec_window + 1:
            # Without table margin, a multi-token insert starting
            # within seq of max_decode_len would CLAMP its tail
            # gather onto the slot's last real page — silent cache
            # corruption. The serving engine sizes spec_window=gamma
            # for its gamma+1-token verify blocks; fail fast for any
            # other caller.
            raise ValueError(
                f"paged decode insert of {seq} tokens needs "
                f"spec_window >= {seq - 1} (got {cfg.spec_window}) "
                f"so tail writes spill onto scratch-backed table "
                f"entries instead of live pages")
        max_blocks = (cfg.max_decode_len + cfg.spec_window
                      + page - 1) // page
        kv_heads = k.shape[2]
        width = kv_heads * depth
        k_pages = self.variable(
            "cache", "k_pages", jnp.zeros,
            (cfg.kv_num_pages, page, width), store_dtype)
        v_pages = self.variable(
            "cache", "v_pages", jnp.zeros,
            (cfg.kv_num_pages, page, width), store_dtype)
        if int8_kv:
            scale_k = self.variable(
                "cache", "k_page_scales", jnp.zeros,
                (cfg.kv_num_pages, page, kv_heads), jnp.float32)
            scale_v = self.variable(
                "cache", "v_page_scales", jnp.zeros,
                (cfg.kv_num_pages, page, kv_heads), jnp.float32)
        block_table = self.variable(
            "cache", "block_table",
            lambda: jnp.zeros((batch, max_blocks), jnp.int32))
        length = self.variable(
            "cache", "length", lambda: jnp.zeros((batch,), jnp.int32))
        idx = length.value                       # [B]
        # Absolute write positions per token, routed through the
        # slot's block table (seq > 1 is the speculative verify
        # block: y + gamma drafts insert at consecutive positions;
        # table entries past the slot's allocation point at the
        # engine's scratch page, which absorbs never-committed tail
        # writes — spec_window guarantees cols//page < max_blocks).
        cols = idx[:, None] + jnp.arange(seq)[None, :]        # [B, S]
        page_idx = jnp.take_along_axis(
            block_table.value, cols // page, axis=1)          # [B, S]
        offset = cols % page
        k_in, v_in = k, v
        if int8_kv:
            from batch_shipyard_tpu.ops.quantization import (
                quantize_int8_rows)
            k_in, ks = quantize_int8_rows(k_in)
            v_in, vs = quantize_int8_rows(v_in)
            scale_k.value = scale_k.value.at[page_idx, offset].set(ks)
            scale_v.value = scale_v.value.at[page_idx, offset].set(vs)
        k_pages.value = k_pages.value.at[page_idx, offset].set(
            k_in.astype(store_dtype).reshape(batch, seq, width))
        v_pages.value = v_pages.value.at[page_idx, offset].set(
            v_in.astype(store_dtype).reshape(batch, seq, width))
        length.value = idx + seq
        if seq > 1 and cfg.attend_block and seq % cfg.attend_block:
            raise ValueError(
                f"a block-diffusion model's paged insert is a whole "
                f"number of blocks of {cfg.attend_block} positions, "
                f"not {seq}")
        live_positions = None
        if live is not None and live.dtype != jnp.bool_:
            live_positions, live = live, live > 0
        if seq == 1 or (kv_heads != heads and not int8_kv):
            # one token, or a verify block over a grouped pool: the
            # kernel (the windowed gather elsewhere) reads each live
            # page once for all seq positions, position r masked to
            # the keys up to its own; or a block-diffusion model's
            # blocks (the cursor on a block's edge), whose positions
            # see the keys up to their own block's end
            return paged_ops.paged_decode_attention(
                q, k_pages.value, v_pages.value, block_table.value,
                _live_lengths(length.value, live),
                impl=cfg.paged_attention_impl,
                k_scales=scale_k.value if int8_kv else None,
                v_scales=scale_v.value if int8_kv else None,
                softmax_dtype=cfg.attn_softmax_dtype,
                block=cfg.attend_block,
                live_positions=live_positions).astype(cfg.dtype)
        # Multi-token verify pass over an MHA (or int8) pool: gather
        # the slot's full logical view
        # and attend causally over absolute cache positions (query s
        # at position idx+s sees keys <= idx+s) — the paged analog of
        # the dense multi-token insert path above. Every key a
        # COMMITTED query can see is either prior committed state or
        # freshly written this block, so scratch-page garbage only
        # ever feeds draft positions whose logits get discarded.
        k_all = k_pages.value[block_table.value].reshape(
            batch, max_blocks * page, kv_heads, depth)
        v_all = v_pages.value[block_table.value].reshape(
            batch, max_blocks * page, kv_heads, depth)
        if int8_kv:
            ks_all = scale_k.value[block_table.value].reshape(
                batch, max_blocks * page, kv_heads)
            vs_all = scale_v.value[block_table.value].reshape(
                batch, max_blocks * page, kv_heads)
            k_all = (k_all.astype(jnp.float32) *
                     ks_all[..., None]).astype(cfg.dtype)
            v_all = (v_all.astype(jnp.float32) *
                     vs_all[..., None]).astype(cfg.dtype)
        key_pos = jax.lax.broadcasted_iota(
            jnp.int32, (max_blocks * page, 1), 0)[:, 0]
        mask = (key_pos[None, None, :] <= attn_ops.block_end(
            cols, cfg.attend_block)[:, :, None])[:, None, :, :]
        return paged_ops.masked_attention(q, k_all, v_all, mask, cfg.dtype)


class LatentAttention(nn.Module):
    """Multi-head latent attention (config.latent, a LatentKV), on the
    normed input a [B, T, d], H = n_heads, every norm an RMSNorm with
    a learned scale:

        c_q    = q_norm(a W_dq)                      q_down, [q_rank]
        q_h    = [q_h^N ; q_h^R] = (c_q W_uq)_h      q_up, nope + rope
        [c;kR] = a W_dkv;  c = kv_norm(c)            kv_down
        q_h^R, kR rotated at the token's position (theta rope_theta)
        [k_h^N ; v_h] = (c W_ukv)_h                  kv_up, nope + v
        s_hij  = (q_h^N . k_hj^N + q_h^R . kR_j) / sqrt(nope + rope)
        out    = concat_h(sum_j softmax_j(s_hij) v_hj) W_o     o_proj

    TWO paths through the same weights. EXPANDED (a forward without a
    cache, and every insert into a DENSE cache: a serving prefill's
    segments): keys and values of every cached row the segment can see
    are expanded from the cache's latent rows (``latent_expand``) and
    attended in blocks (ops/attention.cached_prefill_attention, q and
    k nope + rope deep beside values of v_dim). ABSORBED (a PAGED
    decode call: one token, or a verify block): with
    q~_h = q_h^N W_uk,h^T the scores are (q~_h . c_j + q_h^R . kR_j)
    and the output (sum_j p_hij c_j) W_uv,h, so the cache is read as
    it lies, one row a token for all heads, and nothing is expanded
    (ops/paged_attention.mla_paged_decode_attention; the absorbed
    products accumulate in float32).

    The cache: ONE leaf a layer, ``kv`` [B, T, row_lanes] dense or
    ``kv_pages`` [P, page, row_lanes] paged (block_table and length
    as Attention's), a row [c ; kR ; zeros to the lane tile]."""
    config: TransformerConfig

    @nn.compact
    def __call__(self, x, positions, live=None, key_reach=None):
        cfg, lat = self.config, self.config.latent
        if cfg.tp_axis or cfg.fused_norm or cfg.quantize_matmuls or \
                cfg.kv_cache_dtype or cfg.attend_block or \
                cfg.attn_output_gate:
            raise NotImplementedError(
                "latent attention: no tp_axis, fused_norm, "
                "quantize_matmuls, int8 cache, block diffusion or "
                "output gate")
        dense = functools_partial_dense(cfg)
        batch, seq = x.shape[0], x.shape[1]
        heads = cfg.n_heads

        def norm(name):
            return RMSNorm(eps=cfg.norm_eps, dtype=cfg.dtype, name=name)

        q = dense(heads * lat.qk_dim, "q_up")(
            norm("q_norm")(dense(lat.q_rank, "q_down")(x)))
        q = q.reshape(batch, seq, heads, lat.qk_dim)
        down = dense(lat.kv_rank + lat.rope_dim, "kv_down")(x)
        c = norm("kv_norm")(down[..., :lat.kv_rank])
        q_nope = q[..., :lat.nope_dim]
        q_rope = rotary_embedding(q[..., lat.nope_dim:], positions,
                                  cfg.rope_theta)
        k_rope = rotary_embedding(down[..., None, lat.kv_rank:],
                                  positions, cfg.rope_theta)[:, :, 0]
        # [kv_rank, H, nope + v]: head h's key and value up-projection
        kv_up = self.param(
            "kv_up", nn.initializers.lecun_normal(),
            (lat.kv_rank, heads * (lat.nope_dim + lat.v_dim)),
            cfg.param_dtype).astype(cfg.dtype).reshape(
                lat.kv_rank, heads, lat.nope_dim + lat.v_dim)
        scale = 1.0 / math.sqrt(lat.qk_dim)
        if not cfg.decode:
            k, v = self._expand(c, k_rope, kv_up, lat.qk_dim)
            visible = positions[..., :, None] >= positions[..., None, :]
            scores = jnp.einsum(
                "bqhd,bkhd->bhqk",
                jnp.concatenate([q_nope, q_rope], axis=-1), k,
                preferred_element_type=jnp.float32) * scale
            probs = jax.nn.softmax(jnp.where(
                visible if visible.ndim == 2 else visible[:, None],
                scores, paged_ops._NEG_INF), axis=-1)
            out = jnp.einsum("bhqk,bkhd->bqhd", probs.astype(cfg.dtype),
                             v, preferred_element_type=jnp.float32)
        else:
            row = self._row(c, k_rope)
            if cfg.kv_page_size:
                out = self._absorbed_paged(q_nope, q_rope, row, kv_up,
                                           scale, live)
            else:
                out = self._expanded_dense(q_nope, q_rope, row, kv_up,
                                           scale, key_reach)
        return dense(cfg.d_model, "o_proj")(out.astype(cfg.dtype).reshape(
            batch, seq, heads * lat.v_dim))

    def _row(self, main, rope):
        """[main (kv_rank) ; rope (rope_dim) ; zeros] over the last
        axis, row_lanes wide, in the served type: a cached row from a
        token's compressed vector and rotary key, and an absorbed
        query row from a head's q~ and rotated lanes."""
        lat = self.config.latent
        fill = jnp.zeros(main.shape[:-1] + (
            lat.row_lanes - lat.kv_rank - lat.rope_dim,), main.dtype)
        return jnp.concatenate([main, rope.astype(main.dtype), fill],
                               axis=-1).astype(self.config.dtype)

    def _expand(self, c, k_rope, kv_up, key_depth: int):
        """Latent rows c [B, R, kv_rank], k_rope [B, R, rope] ->
        (keys [B, R, H, key_depth], values [B, R, H, v_dim]): each
        head's unrotated key channels, then the one rotary key, then
        zeros where ``key_depth`` is beyond nope + rope (a lane tile's
        fill, ops/attention.prefill_key_depth)."""
        lat = self.config.latent
        with jax.named_scope("latent_expand"):
            # (two products, each rounded to the served type as it
            # leaves the MXU's float32 accumulator: the values are the
            # second as it stands, and no float32 copy of either is
            # held beside a segment's other temporaries)
            k_nope = jnp.einsum("brc,chd->brhd", c,
                                kv_up[..., :lat.nope_dim])
            values = jnp.einsum("brc,chd->brhd", c,
                                kv_up[..., lat.nope_dim:])
            shared = jnp.broadcast_to(
                k_rope[:, :, None, :].astype(c.dtype),
                k_nope.shape[:3] + (lat.rope_dim,))
            fill = jnp.zeros(
                k_nope.shape[:3] + (key_depth - lat.qk_dim,), c.dtype)
            return jnp.concatenate([k_nope, shared, fill],
                                   axis=-1), values

    def _expanded_dense(self, q_nope, q_rope, row, kv_up, scale,
                        key_reach):
        """An insert of S rows into the dense cache at each slot's
        cursor, then the S queries against the cache's first
        ``key_reach`` rows (None: all), keys and values expanded from
        them. -> [B, S, H, v_dim]."""
        cfg, lat = self.config, self.config.latent
        batch, seq, heads, _ = q_nope.shape
        cache = self.variable(
            "cache", "kv", jnp.zeros,
            (batch, cfg.max_decode_len, lat.row_lanes), cfg.dtype)
        index = self.variable(
            "cache", "index", lambda: jnp.zeros((batch,), jnp.int32))
        idx = index.value
        cols = idx[:, None] + jnp.arange(seq)[None, :]        # [B, S]
        cache.value = cache.value.at[
            jnp.arange(batch)[:, None], cols].set(row)
        index.value = idx + seq
        reach = min(key_reach or cfg.max_decode_len, cfg.max_decode_len)
        depth = attn_ops.prefill_key_depth(lat.qk_dim, seq, reach)
        rows = cache.value[:, :reach]
        keys, values = self._expand(
            rows[..., :lat.kv_rank],
            rows[..., lat.kv_rank:lat.kv_rank + lat.rope_dim], kv_up,
            depth)
        q = jnp.concatenate(
            [q_nope, q_rope, jnp.zeros(
                (batch, seq, heads, depth - lat.qk_dim), q_nope.dtype)],
            axis=-1)
        return attn_ops.cached_prefill_attention(
            q, keys.reshape(batch, reach, heads * depth),
            values.reshape(batch, reach, heads * lat.v_dim), idx,
            softmax_dtype=cfg.attn_softmax_dtype, v_depth=lat.v_dim,
            scale=scale)

    def _absorbed_paged(self, q_nope, q_rope, row, kv_up, scale, live):
        """A paged decode call: the S rows written through the slot's
        block table, then the absorbed form over the pool as it lies.
        ``live``: Attention._decode_attend_paged's mask. -> [B, S, H,
        v_dim]."""
        cfg, lat = self.config, self.config.latent
        batch, seq = q_nope.shape[:2]
        page = cfg.kv_page_size
        if seq > cfg.spec_window + 1:
            raise ValueError(
                f"paged decode insert of {seq} tokens needs "
                f"spec_window >= {seq - 1} (got {cfg.spec_window})")
        max_blocks = (cfg.max_decode_len + cfg.spec_window
                      + page - 1) // page
        pages = self.variable(
            "cache", "kv_pages", jnp.zeros,
            (cfg.kv_num_pages, page, lat.row_lanes), cfg.dtype)
        block_table = self.variable(
            "cache", "block_table",
            lambda: jnp.zeros((batch, max_blocks), jnp.int32))
        length = self.variable(
            "cache", "length", lambda: jnp.zeros((batch,), jnp.int32))
        idx = length.value
        cols = idx[:, None] + jnp.arange(seq)[None, :]        # [B, S]
        page_idx = jnp.take_along_axis(
            block_table.value, cols // page, axis=1)
        pages.value = pages.value.at[page_idx, cols % page].set(row)
        length.value = idx + seq
        if live is not None and live.dtype != jnp.bool_:
            raise NotImplementedError(
                "no live query positions over a latent pool")
        absorbed = jnp.einsum(
            "bshn,chn->bshc", q_nope, kv_up[..., :lat.nope_dim],
            preferred_element_type=jnp.float32).astype(cfg.dtype)
        summed = paged_ops.mla_paged_decode_attention(
            self._row(absorbed, q_rope), pages.value, block_table.value,
            _live_lengths(length.value, live),
            value_lanes=lat.kv_rank, scale=scale,
            impl=cfg.paged_attention_impl,
            softmax_dtype=cfg.attn_softmax_dtype)
        return jnp.einsum(
            "bshc,chv->bshv", summed, kv_up[..., lat.nope_dim:],
            preferred_element_type=jnp.float32)


def _live_rows(live, length: int):
    """[B, length] bool, the query positions somebody reads, from an
    int32 ``live`` [B] (how many of each slot's, from its first on:
    Attention._decode_attend_paged); None (all of them) from a mask
    or from none."""
    if live is None or live.dtype == jnp.bool_:
        return None
    return jnp.arange(length)[None, :] < live[:, None]


def _live_lengths(lengths, live):
    """The key counts a paged decode call is handed: 0 for a slot the
    step's ``live`` mask ([B] bool, None = every slot) leaves out."""
    return lengths if live is None else jnp.where(live, lengths, 0)


def prefix_rows_from_pages(layer_cache: dict, page_ids,
                           page: int) -> dict:
    """Gather a shared-prefix page chain out of ONE layer's paged
    pool into dense-cache row layout — the paged prefill entry point
    for cross-request prefix reuse (models/serving.py).

    The serving engine's prefix index stores immutable full pages by
    content hash; a request that matches n pages skips their prefill
    entirely and instead seeds a batch-1 dense cache with these rows
    (index = n*page), then runs only its suffix through the model.
    The gather works because a page id indexes EVERY layer's pool at
    the same position (the engine pushes one shared block table into
    all layers), so one id list reconstructs the prefix in each layer.

    layer_cache: one attention layer's paged leaves (k_pages
    [P, page, H*D], v_pages, and the int8 k_page_scales/v_page_scales
    [P, page, H] when present). page_ids: [n] int32 page indices —
    entries past the true prefix may point at the scratch page; their
    garbage rows are masked-on-read by the dense cache's index leaf.
    Returns {"k": [n*page, H*D], "v": ..., ("k_scale": [n*page, H],
    "v_scale": ...)} in the pool's storage dtype (int8 rows + fp32
    scales pass through untouched, so a shared prefix dequantizes to
    exactly the bytes the original prefill produced); the caller
    unfolds the heads ([n*page, H, D]) for its dense cache."""
    k = layer_cache["k_pages"][page_ids]          # [n, page, H*D]
    rows = k.shape[0] * page
    out = {"k": k.reshape(rows, k.shape[-1]),
           "v": layer_cache["v_pages"][page_ids].reshape(
               rows, k.shape[-1])}
    if "k_page_scales" in layer_cache:
        ks = layer_cache["k_page_scales"][page_ids]
        out["k_scale"] = ks.reshape(rows, ks.shape[-1])
        out["v_scale"] = layer_cache["v_page_scales"][
            page_ids].reshape(rows, ks.shape[-1])
    return out


class QuantDense(nn.Module):
    """Bias-free linear layer running on the int8 MXU path.

    Parameter layout matches nn.Dense ("kernel" [in, features]) so the
    tensor-parallel PartitionSpec rules in parallel/sharding.py apply
    unchanged. Forward quantizes activations and weights per-row on
    the fly (ops/quantization.quantized_linear); backward is the
    standard full-precision QAT straight-through.
    """

    features: int
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x):
        from batch_shipyard_tpu.ops import quantization as qz
        kernel = self.param(
            "kernel", nn.initializers.lecun_normal(),
            (x.shape[-1], self.features), self.param_dtype)
        flat = x.reshape(-1, x.shape[-1])
        out = qz.quantized_linear(flat, kernel.astype(self.dtype))
        return out.reshape(*x.shape[:-1],
                           self.features).astype(self.dtype)


def functools_partial_dense(cfg: TransformerConfig):
    def make(features: int, name: str):
        if getattr(cfg, "quantize_matmuls", False):
            return QuantDense(features, dtype=cfg.dtype,
                              param_dtype=cfg.param_dtype, name=name)
        return nn.Dense(features, use_bias=False, dtype=cfg.dtype,
                        param_dtype=cfg.param_dtype, name=name)
    return make


class MLP(nn.Module):
    config: TransformerConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.config
        dense = functools_partial_dense(cfg)
        if cfg.fused_norm:
            # x arrives UN-normed; the block's mlp RMSNorm fuses into
            # one [d, 2*d_ff] gate/up projection.
            from batch_shipyard_tpu.ops import fused_norm as fn_ops
            norm_scale = self.param(
                "norm_scale", nn.initializers.ones,
                (x.shape[-1],), jnp.float32)
            gate_up_kernel = self.param(
                "gate_up_kernel", nn.initializers.lecun_normal(),
                (x.shape[-1], 2 * cfg.d_ff), cfg.param_dtype)
            batch, seq = x.shape[0], x.shape[1]
            gu = fn_ops.rmsnorm_matmul(
                x.reshape(batch * seq, -1), norm_scale,
                gate_up_kernel.astype(cfg.dtype))
            gate, up = jnp.split(
                gu.reshape(batch, seq, 2 * cfg.d_ff), 2, axis=-1)
            return dense(cfg.d_model, "down_proj")(
                nn.silu(gate) * up)
        if cfg.tp_axis:
            x = tp_region_input(x, cfg.tp_axis)
        gate = dense(cfg.d_ff, "gate_proj")(x)
        up = dense(cfg.d_ff, "up_proj")(x)
        out = dense(cfg.d_model, "down_proj")(nn.silu(gate) * up)
        if cfg.tp_axis:
            # Row-sharded down_proj: partial sums across tp members.
            out = tp_region_output(out, cfg.tp_axis)
        return out


class Block(nn.Module):
    config: TransformerConfig
    use_moe: bool = False
    window: int = 0                 # Attention's, for this layer
    rope: Optional[bool] = None

    @nn.compact
    def __call__(self, x, positions, live=None):
        cfg = self.config
        if cfg.fused_norm:
            if (cfg.tp_axis or cfg.quantize_matmuls or cfg.decode
                    or self.use_moe):
                raise NotImplementedError(
                    "fused_norm composes only with the plain dense "
                    "training path (no tp_axis / quantize_matmuls / "
                    "decode / moe)")
            # The norms live INSIDE Attention/MLP (fused into their
            # first projection); pass the raw residual stream.
            x = x + Attention(cfg, self.window, self.rope,
                              name="attn")(x, positions)
            return x + MLP(cfg, name="mlp")(x)
        x = x + Attention(cfg, self.window, self.rope, name="attn")(
            RMSNorm(eps=cfg.norm_eps, dtype=cfg.dtype,
                    name="attn_norm")(x), positions, live)
        normed = RMSNorm(eps=cfg.norm_eps, dtype=cfg.dtype,
                         name="mlp_norm")(x)
        if self.use_moe:
            from batch_shipyard_tpu.models.moe import MoEMLP
            out, aux = MoEMLP(cfg.moe, name="moe")(normed)
            self.sow("losses", "moe_aux", aux)
            x = x + out
        else:
            x = x + MLP(cfg, name="mlp")(normed)
        return x


class MixerBlock(nn.Module):
    """A block that is ONE mixer after ONE norm: x + Mixer(RMSNorm(x)),
    the mixer of ``kind`` (MIXER_KINDS) and named by it, so that the
    tree, the cache and a device trace's operation names all say which
    kind a layer is (layer_3/ssm/..., layer_4/delta/...,
    layer_5/attn/..., layer_1/mlp/...). -> (the block's output, its
    normed input:
    what the NEXT block's router reads in a model whose router is
    placed before the token mixer, ``router_input`` here)."""
    config: TransformerConfig
    kind: str = "attn"
    window: int = 0                 # Attention's, for this layer
    rope: Optional[bool] = None

    @nn.compact
    def __call__(self, x, positions, valid_len=None,
                 router_input=None, live=None, key_reach=None):
        cfg = self.config
        normed = RMSNorm(eps=cfg.norm_eps, dtype=cfg.dtype,
                         name="norm")(x)
        if self.kind == "ssm":
            from batch_shipyard_tpu.models.ssm import Mamba2Mixer
            out = Mamba2Mixer(cfg, name="ssm")(normed, valid_len)
        elif self.kind == "delta":
            from batch_shipyard_tpu.models.delta import DeltaMixer
            out = DeltaMixer(cfg, name="delta")(normed, valid_len)
        elif self.kind == "experts":
            from batch_shipyard_tpu.models.moe import RoutedExperts
            out = RoutedExperts(cfg.experts, dtype=cfg.dtype,
                                param_dtype=cfg.param_dtype,
                                name="experts")(
                normed, router_input, _live_rows(live, x.shape[1]))
        elif self.kind == "mlp":
            out = MLP(cfg, name="mlp")(normed)
        elif cfg.latent is not None:
            if self.window:
                raise NotImplementedError(
                    "no sliding window over latent rows")
            out = LatentAttention(cfg, name="attn")(
                normed, positions, live, key_reach)
        else:
            out = Attention(cfg, self.window, self.rope,
                            name="attn")(normed, positions, live)
        if cfg.sandwich_norm:
            out = RMSNorm(eps=cfg.norm_eps, dtype=cfg.dtype,
                          name="post_norm")(out)
        return x + out, normed


class MTPModule(nn.Module):
    """One multi-token-prediction module (the wiring DeepSeek-V3
    published for ``num_nextn_predict_layers``): at position i,

        x_i  = [embed_norm(Emb(t_{i+1})) ; hidden_norm(h_L,i)] W_proj
        x'_i = one layer on x (attn, full, K/V of its own over the x
               positions; then experts where the model has routed
               experts, else mlp: two MixerBlocks, layer_0 and layer_1)
        out  = norm(x'_i)

    ``embedded`` [B, T, d]: the model's embedding of the NEXT tokens;
    ``hidden`` [B, T, d]: the stack's last hidden state before the
    final norm. -> the module's normed output [B, T, d], which the
    model's own head turns into a prediction of t_{i+2}."""
    config: TransformerConfig

    @nn.compact
    def __call__(self, embedded, hidden, positions, valid_len=None,
                 live=None, key_reach=None):
        cfg = self.config

        def norm(name):
            return RMSNorm(eps=cfg.norm_eps, dtype=cfg.dtype, name=name)

        x = jnp.concatenate(
            [norm("embed_norm")(embedded), norm("hidden_norm")(hidden)],
            axis=-1)
        x = functools_partial_dense(cfg)(cfg.d_model, "proj")(x)
        x, _ = MixerBlock(cfg, "attn", 0, cfg.mtp_rope, name="layer_0")(
            x, positions, valid_len, live=live, key_reach=key_reach)
        x, _ = MixerBlock(
            cfg, "experts" if cfg.experts is not None else "mlp",
            name="layer_1")(x, positions, valid_len)
        return norm("norm")(x)


class TransformerLM(nn.Module):
    config: TransformerConfig

    @nn.compact
    def __call__(self, tokens, return_hidden: bool = False,
                 positions=None, valid_len=None,
                 stack_hidden: bool = False, mtp_hidden=None,
                 live=None, head_rows=None, key_reach=None):
        """tokens: [B, T] int32 -> logits [B, T, vocab] (or the final
        hidden states [B, T, d_model] when return_hidden — used by the
        chunked-loss training path so the full fp32 logits tensor,
        B*T*vocab, never materializes in HBM). In decode mode pass
        positions=[absolute position] for the current step.
        ``valid_len`` (traced int, decode-mode prefill only): how many
        leading tokens of this call are the sequence's own; the rest
        is bucket padding, which a layer that keeps a running state
        (models/ssm.py, models/delta.py) must not let advance it. K/V
        rows are masked on read and need no such care.
        ``live`` ([B] bool, a paged decode step only; None = every
        slot): the slots that hold a request; the others' attention is
        skipped (Attention._decode_attend_paged, which has what an
        int32 ``live`` says).
        ``head_rows`` (int32 [B, n]): the n positions of each row of
        the batch that the head runs over, in place of all T.
        ``key_reach`` (a Python int, a decode-mode insert into a DENSE
        cache of latent rows only; None = the whole cache): no query
        of this call sits at or beyond that position, so a latent
        layer expands its cache's first ``key_reach`` rows alone.

        A model with a multi-token-prediction module (mtp_modules):
        ``stack_hidden`` True -> (the result as above, the stack's
        last hidden state BEFORE the final norm [B, T, d]); and
        ``mtp_hidden`` [B, T, d] (that state, of positions
        ``positions``) runs THE MODULE ALONE, ``tokens`` then being
        each position's NEXT token: its logits (its normed output
        under return_hidden) through the model's own embedding and
        head. Neither asked for, the forward is what it is without a
        module."""
        cfg = self.config
        embed = nn.Embed(cfg.vocab_size, cfg.d_model,
                         dtype=cfg.dtype, param_dtype=cfg.param_dtype,
                         name="embed")
        x = embed(tokens)
        if positions is None:
            positions = jnp.arange(tokens.shape[1], dtype=jnp.int32)

        def head(normed):
            if return_hidden:
                return normed
            if cfg.tie_embeddings:
                # Tied output projection via attend (embedding
                # transpose).
                return embed.attend(normed.astype(jnp.float32))
            return nn.Dense(cfg.vocab_size, use_bias=False,
                            dtype=cfg.dtype, param_dtype=cfg.param_dtype,
                            name="lm_head")(normed)

        if mtp_hidden is not None:
            if not cfg.mtp_modules:
                raise ValueError("mtp_hidden: the model has no "
                                 "multi-token-prediction module")
            return head(MTPModule(cfg, name=MTP_NAME)(
                x, mtp_hidden, positions, valid_len, live, key_reach))
        block, mixer_block = Block, MixerBlock
        if cfg.remat:
            block = nn.remat(Block, static_argnums=())
            mixer_block = nn.remat(MixerBlock, static_argnums=())
        mixer_input = None      # the last token mixer's normed input
        for idx, kind in enumerate(layer_kinds(cfg)):
            per_layer = (layer_window(cfg, idx), layer_rope(cfg, idx)) \
                if kind in ATTENTION_KINDS else ()
            if kind in MIXER_KINDS:
                router_input = None
                if kind == "experts" and cfg.router_before_mixer:
                    if mixer_input is None:
                        raise ValueError(
                            "router_before_mixer: an experts block "
                            "with no token mixer before it")
                    router_input = mixer_input
                x, normed = mixer_block(
                    cfg, kind, *per_layer, name=f"layer_{idx}")(
                        x, positions, valid_len, router_input, live,
                        key_reach)
                if kind not in ("experts", "mlp"):
                    mixer_input = normed
            else:
                if cfg.latent is not None or cfg.sandwich_norm:
                    raise NotImplementedError(
                        "latent attention and sandwich norms are "
                        "MixerBlock's (block_kinds of attn / mlp / "
                        "experts)")
                x = block(cfg, kind == "dense_moe", *per_layer,
                          name=f"layer_{idx}")(x, positions, live)
        last = x
        if head_rows is not None:
            x = jnp.take_along_axis(x, head_rows[:, :, None], axis=1)
        out = head(RMSNorm(eps=cfg.norm_eps, dtype=cfg.dtype,
                           name="final_norm")(x))
        if cfg.mtp_modules and self.is_initializing():
            # the module's parameters and cache leaves, made with the
            # stack's (nothing of it reaches the result)
            MTPModule(cfg, name=MTP_NAME)(last, last, positions,
                                          valid_len)
        return (out, last) if stack_hidden else out


def lm_loss(logits, targets, ignore_id: int = -1):
    """Causal LM cross-entropy (next-token prediction is the caller's
    responsibility via target shifting)."""
    vocab = logits.shape[-1]
    mask = (targets != ignore_id)
    onehot = jax.nn.one_hot(targets, vocab, dtype=logits.dtype)
    logprobs = jax.nn.log_softmax(logits, axis=-1)
    loss = -jnp.sum(onehot * logprobs, axis=-1)
    return jnp.sum(loss * mask) / jnp.maximum(jnp.sum(mask), 1)


def lm_loss_chunked(hidden, embedding, targets, ignore_id: int = -1,
                    chunk_size: int = 128, impl: str = "auto"):
    """Memory-efficient tied-embedding cross-entropy.

    Never materializes the full [B, T, vocab] fp32 logits tensor (for
    T=2048, V=32k, B=16 that's 4 GB saved in the forward and again in
    the backward). Mathematically the same loss as
    lm_loss(embed.attend(hidden), targets), computed in fp32
    throughout (attend produces bf16 logits, so values differ at bf16
    precision — the chunked path is the more accurate one).

    Delegates to ops/chunked_loss.chunked_softmax_xent: impl='auto'
    is the fused Pallas kernel on a TPU backend (lane-aligned
    d_model), the scan-chunked XLA path elsewhere.
    """
    from batch_shipyard_tpu.ops import chunked_loss
    # chunk_size here means time-steps per batch row (the historical
    # contract); the flattened op counts rows, so scale by batch to
    # keep the per-slab matmul the same shape as before.
    rows = chunk_size * (hidden.shape[0] if hidden.ndim == 3 else 1)
    return chunked_loss.chunked_softmax_xent(
        hidden, embedding, targets, ignore_id=ignore_id, impl=impl,
        chunk_size=rows)
