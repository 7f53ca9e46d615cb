"""Mixture-of-Experts layer with expert parallelism.

GShard-style top-1 routing with capacity limits, expressed as dense
one-hot dispatch/combine einsums — the XLA-native formulation: the
dispatch tensor contraction becomes an all-to-all over the ``ep`` mesh
axis when the expert dimension of the parameters is sharded
P('ep', ...), with no manual collectives.

Pieces:
  - Router: softmax gate, top-1 expert per token, position-in-expert
    via a cumulative sum, tokens beyond capacity dropped (their
    contribution is the residual path).
  - Dispatch: one-hot [tokens, experts, capacity] einsum packs token
    activations into per-expert buffers.
  - Experts: batched SwiGLU MLPs, parameters [E, ...] (ep-sharded).
  - Combine: the same tensor weighted by gate probabilities unpacks
    expert outputs back to token order.

Auxiliary load-balancing loss per GShard/Switch: mean(fraction of
tokens per expert * mean gate prob per expert) * num_experts^2.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional

import jax
import jax.numpy as jnp
from flax import linen as nn


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    num_experts: int = 8
    d_model: int = 512
    d_ff: int = 1408
    capacity_factor: float = 1.25
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    router_noise: float = 0.0
    num_selected: int = 1    # 1 = Switch-style, 2 = GShard top-2
    # "tokens": tokens pick experts (top-1/top-k above, needs the
    # load-balancing aux loss). "expert_choice": experts pick their
    # top-C tokens (Zhou et al. 2022) — perfectly load-balanced by
    # construction, no aux loss.
    routing: str = "tokens"


def top1_routing(logits, capacity: int):
    """logits: [G, E] (G = flattened tokens). Returns
    (dispatch [G, E, C] bool-ish, combine [G, E, C] float,
    aux_loss scalar)."""
    groups, num_experts = logits.shape
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    expert_index = jnp.argmax(probs, axis=-1)            # [G]
    expert_mask = jax.nn.one_hot(expert_index, num_experts,
                                 dtype=jnp.float32)      # [G, E]
    # Position of each token within its chosen expert's buffer.
    position_in_expert = (jnp.cumsum(expert_mask, axis=0) *
                          expert_mask) - expert_mask      # [G, E]
    keep = position_in_expert < capacity
    expert_mask = expert_mask * keep
    gate = jnp.sum(probs * expert_mask, axis=-1)          # [G]
    pos = jnp.sum(position_in_expert * expert_mask,
                  axis=-1).astype(jnp.int32)              # [G]
    pos_onehot = jax.nn.one_hot(pos, capacity,
                                dtype=jnp.float32)        # [G, C]
    dispatch = expert_mask[:, :, None] * pos_onehot[:, None, :]
    combine = dispatch * gate[:, None, None]
    # Load-balancing auxiliary loss (Switch Transformer eq. 4).
    density = jnp.mean(expert_mask, axis=0)               # [E]
    density_proxy = jnp.mean(probs, axis=0)               # [E]
    aux = jnp.sum(density * density_proxy) * (num_experts ** 2) / (
        num_experts)
    return dispatch, combine, aux


def topk_routing(logits, capacity: int, num_selected: int = 2):
    """GShard-style top-k routing. logits: [G, E]. Returns (dispatch
    [G, E, C], combine [G, E, C], aux). First choices get buffer
    priority; second choices fill remaining capacity; gates of the
    selected experts are renormalized per token."""
    groups, num_experts = logits.shape
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    gate_vals, expert_idx = jax.lax.top_k(probs, num_selected)
    gate_vals = gate_vals / jnp.maximum(
        jnp.sum(gate_vals, axis=-1, keepdims=True), 1e-9)
    dispatch = jnp.zeros((groups, num_experts, capacity),
                         dtype=jnp.float32)
    combine = jnp.zeros_like(dispatch)
    used = jnp.zeros((num_experts,), dtype=jnp.float32)
    first_mask = None
    for choice in range(num_selected):
        mask = jax.nn.one_hot(expert_idx[:, choice], num_experts,
                              dtype=jnp.float32)
        if first_mask is None:
            first_mask = mask
        position = (jnp.cumsum(mask, axis=0) - 1.0 +
                    used[None, :]) * mask
        keep = (position < capacity) & (mask > 0)
        mask = mask * keep
        pos = jnp.sum(position * mask, axis=-1).astype(jnp.int32)
        pos_onehot = jax.nn.one_hot(pos, capacity, dtype=jnp.float32)
        sel = mask[:, :, None] * pos_onehot[:, None, :]
        dispatch = dispatch + sel
        combine = combine + sel * gate_vals[:, choice][:, None, None]
        used = used + jnp.sum(mask, axis=0)
    density = jnp.mean(first_mask, axis=0)
    density_proxy = jnp.mean(probs, axis=0)
    aux = jnp.sum(density * density_proxy) * num_experts
    return dispatch, combine, aux


def expert_choice_routing(logits, capacity: int):
    """Expert-choice routing (Zhou et al. 2022): each EXPERT selects
    its top-C tokens by affinity, the transpose of token-choice.
    Load is perfectly balanced by construction (every expert processes
    exactly C tokens), so there is no auxiliary loss (returns 0.0);
    a token may be picked by several experts (outputs sum) or by none
    (the residual path carries it).

    logits: [G, E]. Returns (dispatch [G, E, C], combine [G, E, C],
    aux=0.0).
    """
    groups, num_experts = logits.shape
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    # Per-expert token affinities: [E, G]; each expert takes top-C.
    gate_vals, token_idx = jax.lax.top_k(probs.T, capacity)  # [E, C]
    dispatch = jax.nn.one_hot(
        token_idx, groups, dtype=jnp.float32)                # [E, C, G]
    dispatch = dispatch.transpose(2, 0, 1)                   # [G, E, C]
    combine = dispatch * gate_vals[None, :, :]
    return dispatch, combine, jnp.float32(0.0)


class MoEMLP(nn.Module):
    """Drop-in MLP replacement: top-1 routed SwiGLU experts."""

    config: MoEConfig

    @nn.compact
    def __call__(self, x):
        """x: [B, T, D] -> ([B, T, D], aux_loss)."""
        cfg = self.config
        if cfg.routing not in ("tokens", "expert_choice"):
            raise ValueError(
                f"unknown MoE routing {cfg.routing!r} "
                f"(expected 'tokens' or 'expert_choice')")
        batch, t_len, d_model = x.shape
        groups = batch * t_len
        capacity = max(1, int(cfg.capacity_factor * groups /
                              cfg.num_experts))
        router = nn.Dense(cfg.num_experts, use_bias=False,
                          dtype=jnp.float32,
                          param_dtype=cfg.param_dtype, name="router")
        flat = x.reshape(groups, d_model)
        logits = router(flat.astype(jnp.float32))
        if cfg.router_noise > 0.0:
            noise = jax.random.uniform(
                self.make_rng("router"), logits.shape,
                minval=1.0 - cfg.router_noise,
                maxval=1.0 + cfg.router_noise)
            logits = logits * noise
        if cfg.routing == "expert_choice":
            dispatch, combine, aux = expert_choice_routing(logits,
                                                           capacity)
        elif cfg.num_selected > 1:
            dispatch, combine, aux = topk_routing(
                logits, capacity, cfg.num_selected)
        else:
            dispatch, combine, aux = top1_routing(logits, capacity)
        # Expert parameters: leading E dim is the ep-sharded axis.
        w_gate = self.param(
            "w_gate", nn.initializers.lecun_normal(),
            (cfg.num_experts, d_model, cfg.d_ff), cfg.param_dtype)
        w_up = self.param(
            "w_up", nn.initializers.lecun_normal(),
            (cfg.num_experts, d_model, cfg.d_ff), cfg.param_dtype)
        w_down = self.param(
            "w_down", nn.initializers.lecun_normal(),
            (cfg.num_experts, cfg.d_ff, d_model), cfg.param_dtype)
        # Dispatch tokens into per-expert buffers: [E, C, D]. With
        # dispatch replicated and experts ep-sharded, XLA lowers the
        # downstream per-expert compute to an all-to-all exchange.
        expert_in = jnp.einsum(
            "gec,gd->ecd", dispatch.astype(cfg.dtype),
            flat.astype(cfg.dtype))
        gate_act = jnp.einsum("ecd,edf->ecf", expert_in,
                              w_gate.astype(cfg.dtype))
        up_act = jnp.einsum("ecd,edf->ecf", expert_in,
                            w_up.astype(cfg.dtype))
        expert_out = jnp.einsum(
            "ecf,efd->ecd", nn.silu(gate_act) * up_act,
            w_down.astype(cfg.dtype))
        out = jnp.einsum("gec,ecd->gd", combine.astype(cfg.dtype),
                         expert_out)
        return out.reshape(batch, t_len, d_model), aux.astype(
            jnp.float32)


def moe_ep_apply_shard(flat, router_kernel, w_gate, w_up, w_down,
                       capacity: int, outer_axis: Optional[str],
                       inner_axis: str, routing: str = "top1",
                       num_selected: int = 2,
                       dtype=jnp.bfloat16):
    """Explicit expert-parallel MoE body for shard_map, with the
    cross-slice exchange on ops/collectives.hierarchical_all_to_all
    (ROADMAP 'wire it into a shard_map MoE dispatch variant').

    The flax MoEMLP leaves the exchange to XLA's sharding propagation
    — correct, but on a multi-slice mesh a flat all-to-all over the
    combined ep axis sends n_inner^2 small DCN messages per slice
    pair. This body routes locally, packs destination-indexed
    buffers, and exchanges them hierarchically (ICI phase inside the
    slice, then ONE aggregated DCN message per slice pair), runs the
    local expert shard, and reverses the exchange — the MoE dispatch
    pattern for experts spanning slices.

    Per-device arguments (call inside shard_map):
      flat          [G_local, D]    this device's tokens
      router_kernel [D, E]          replicated
      w_gate/w_up   [E_local, D, F] local expert shard
      w_down        [E_local, F, D] local expert shard
    Expert e's global id is (outer * n_inner + inner) * E_local + el
    — i.e. leading-dim sharding of [E, ...] weights over the factored
    (outer_axis, inner_axis) mesh axes, which is exactly what
    in_specs=P((outer, inner), ...) hands each device.

    outer_axis=None runs the SINGLE-AXIS case (experts sharded over
    one mesh axis — the common single-slice ep layout): the exchange
    degenerates to one plain all_to_all over inner_axis.

    Returns ([G_local, D] combined output, aux loss averaged over the
    ep group). Token routing/capacity is PER DEVICE GROUP (each
    device's G_local tokens route independently) — same semantics as
    running the dense MoEMLP on each group.
    """
    from batch_shipyard_tpu.ops import collectives

    n_out = 1 if outer_axis is None else jax.lax.psum(1, outer_axis)
    n_in = jax.lax.psum(1, inner_axis)
    n_ep = n_out * n_in

    def exchange(x):
        """Destination-indexed [n_out, n_in, ...] -> source-indexed
        (an involution): hierarchical over (outer, inner), or one
        plain all_to_all when there is no outer axis."""
        if outer_axis is None:
            return jax.lax.all_to_all(x, inner_axis, split_axis=1,
                                      concat_axis=1)
        return collectives.hierarchical_all_to_all(
            x, outer_axis, inner_axis)
    e_local, d_model = w_gate.shape[0], w_gate.shape[1]
    num_experts = e_local * n_ep

    logits = flat.astype(jnp.float32) @ router_kernel.astype(
        jnp.float32)
    if routing == "expert_choice":
        dispatch, combine, aux = expert_choice_routing(logits,
                                                       capacity)
    elif routing == "topk":
        dispatch, combine, aux = topk_routing(logits, capacity,
                                              num_selected)
    else:
        dispatch, combine, aux = top1_routing(logits, capacity)
    # Pack per-expert send buffers [E, C, D], then view the expert
    # dim as destination coordinates [n_out, n_in, E_local, C, D].
    expert_in = jnp.einsum("gec,gd->ecd", dispatch.astype(dtype),
                           flat.astype(dtype))
    x = expert_in.reshape(n_out, n_in, e_local, capacity, d_model)
    # ICI-then-DCN exchange: arrives source-indexed (a[o, i] = the
    # buffer device (o, i) sent to MY experts).
    a = exchange(x)
    # Batch all sources through the local expert shard.
    a = a.reshape(n_ep, e_local, capacity, d_model)
    a = a.transpose(1, 0, 2, 3).reshape(e_local, n_ep * capacity,
                                        d_model)
    gate_act = jnp.einsum("end,edf->enf", a, w_gate.astype(dtype))
    up_act = jnp.einsum("end,edf->enf", a, w_up.astype(dtype))
    out = jnp.einsum("enf,efd->end", nn.silu(gate_act) * up_act,
                     w_down.astype(dtype))
    # Reverse exchange: the same hierarchical a2a returns each
    # processed buffer to its origin device (the exchange is an
    # involution on the [n_out, n_in] block layout).
    out = out.reshape(e_local, n_ep, capacity, d_model)
    out = out.transpose(1, 0, 2, 3).reshape(n_out, n_in, e_local,
                                            capacity, d_model)
    r = exchange(out)
    r = r.reshape(num_experts, capacity, d_model)
    y = jnp.einsum("gec,ecd->gd", combine.astype(dtype), r)
    aux = jax.lax.pmean(aux, inner_axis)
    if outer_axis is not None:
        aux = jax.lax.pmean(aux, outer_axis)
    return y, aux.astype(jnp.float32)


def moe_ep_stage(flat, router_kernel, w_gate, w_up, w_down,
                 capacity: int, inner_axis: str,
                 outer_axis: Optional[str] = None,
                 routing: str = "top1", num_selected: int = 2,
                 dtype=jnp.bfloat16):
    """Expert-parallel MoE for a shard_map STAGE whose activations are
    REPLICATED across the ep axis — the pipeline-parallel composition
    (dp x pp x ep): pipeline stages shard over pp, activations stream
    through replicated across ep, and this stage splits the tokens by
    ep rank, runs the explicit dispatch (moe_ep_apply_shard) on the
    local shard, and all_gathers the outputs back into the replicated
    stream. Everything is unconditional collectives, so it is legal
    inside the (non-interleaved) 1F1B tick like tp is.

    CONTRACT: differentiate INSIDE the shard_map body (the pipeline
    does — manual vjp per tick), where the cotangent arriving at the
    region output is replicated-full by construction. Taking
    jax.grad ACROSS the shard_map boundary instead hits shard_map's
    replicated-output transpose (cotangent split across members) and
    undercounts expert-shard grads.

    The whole split->dispatch->gather region carries a custom VJP:
    with replicated in/out cotangents, naive autodiff would overcount
    the gather's transpose by the ep size and leave the replicated
    router's (and the sliced input's) per-rank PARTIAL grads
    un-summed. The backward here takes each rank's slice of the full
    cotangent through the local pullback, then assembles dx from the
    rank-disjoint scatters and psums the replicated router grad —
    the Megatron f/g discipline applied to a replicated stream.

    flat: [G, D] REPLICATED across ep (G divisible by the ep size).
    Weights: local expert shards [E_local, ...] (ep-sharded specs).
    Returns ([G, D] replicated, aux scalar).
    """
    axes = ([inner_axis] if outer_axis is None
            else [inner_axis, outer_axis])

    def _psum_all(v):
        for ax in axes:
            v = jax.lax.psum(v, ax)
        return v

    n_out = 1 if outer_axis is None else jax.lax.psum(1, outer_axis)
    n_in = jax.lax.psum(1, inner_axis)
    n_ep = n_out * n_in

    def _my():
        # axis_index is TRACED: recompute inside every custom_vjp
        # stage (fwd and bwd trace separately under jax.grad; a
        # closed-over tracer from one would leak into the other).
        my_in = jax.lax.axis_index(inner_axis)
        if outer_axis is None:
            return my_in
        return jax.lax.axis_index(outer_axis) * n_in + my_in

    g_total, _d = flat.shape
    if g_total % n_ep:
        raise ValueError(
            f"moe_ep_stage: {g_total} tokens not divisible by the "
            f"ep size {n_ep}")
    g_local = g_total // n_ep
    flat_shape_dtype = jax.ShapeDtypeStruct(flat.shape, flat.dtype)

    def local(mine, router, wg, wu, wd):
        return moe_ep_apply_shard(
            mine, router, wg, wu, wd, capacity=capacity,
            outer_axis=outer_axis, inner_axis=inner_axis,
            routing=routing, num_selected=num_selected, dtype=dtype)

    def _gather(y_local):
        y = jax.lax.all_gather(y_local, inner_axis, axis=0,
                               tiled=True)
        if outer_axis is not None:
            y = jax.lax.all_gather(y, outer_axis, axis=0, tiled=True)
        return y

    @jax.custom_vjp
    def region(flat, router, wg, wu, wd):
        mine = jax.lax.dynamic_slice_in_dim(
            flat, _my() * g_local, g_local, axis=0)
        y_local, aux = local(mine, router, wg, wu, wd)
        return _gather(y_local), aux

    def region_fwd(flat, router, wg, wu, wd):
        mine = jax.lax.dynamic_slice_in_dim(
            flat, _my() * g_local, g_local, axis=0)
        (y_local, aux), pullback = jax.vjp(local, mine, router, wg,
                                           wu, wd)
        return (_gather(y_local), aux), pullback

    def region_bwd(pullback, cot):
        dy, daux = cot
        # Full (replicated) dy: every rank pulls ITS token slice back
        # through the local region. daux is also replicated-full, but
        # the pullback routes it through the pmean's psum transpose
        # AND region_bwd psums the router partials below — divide by
        # the ep size so the aux gradient is counted exactly once
        # (empirically n_ep-times overcounted otherwise).
        my = _my()
        dy_local = jax.lax.dynamic_slice_in_dim(
            dy, my * g_local, g_local, axis=0)
        dmine, drouter, dwg, dwu, dwd = pullback(
            (dy_local, daux / n_ep))
        # Rank-disjoint scatters assemble the replicated dx; the
        # replicated router grad is the sum of per-rank partials.
        dflat = jnp.zeros(flat_shape_dtype.shape,
                          flat_shape_dtype.dtype)
        dflat = jax.lax.dynamic_update_slice_in_dim(
            dflat, dmine.astype(dflat.dtype), my * g_local, axis=0)
        return (_psum_all(dflat), _psum_all(drouter), dwg, dwu, dwd)

    region.defvjp(region_fwd, region_bwd)
    return region(flat, router_kernel, w_gate, w_up, w_down)


def moe_param_specs():
    """PartitionSpec patterns for MoE params (merged into the
    transformer rules): experts over ep, expert-internal dims over
    tp/fsdp."""
    from jax.sharding import PartitionSpec as P
    return [
        (r".*moe/router/kernel$", P(None, None)),
        (r".*moe/(w_gate|w_up)$", P("ep", "fsdp", "tp")),
        (r".*moe/w_down$", P("ep", "tp", "fsdp")),
    ]


# --------------------------------------------------------------------
# Routed experts with NO capacity, for serving: a token's output never
# depends on who shares its step.


@dataclasses.dataclass(frozen=True)
class RoutedConfig:
    """Top-k routed experts beside one shared expert (or none), and
    which of the experts THIS chip holds: the router always scores all
    ``n_experts`` and takes its ``top_k``; the layer computes the
    choices that fall on experts first_expert .. first_expert +
    experts_held - 1 and adds nothing for the others (in an
    expert-parallel deployment the chips holding them add their part;
    on one chip the layer runs without that exchange)."""
    d_model: int = 512
    n_experts: int = 8           # the router's outputs
    top_k: int = 2
    d_expert: int = 1024
    d_shared: int = 2048
    scale: float = 1.0           # on the normalised weights
    experts_held: Optional[int] = None   # None = all of them
    first_expert: int = 0
    # Expert(x) and Shared(x): False down(relu(up x)^2); True the gated
    # down(act(gate x) * up x), a third matrix an expert.
    gated: bool = False
    # The gate's activation of a gated expert: "silu" (SwiGLU) or
    # "relu" (ReGLU).
    gate_act: str = "silu"
    # How the router's outputs become choices and weights: "sigmoid"
    # (route_sigmoid: a selection bias, weights normalised to
    # ``scale``) or "softmax" (route_softmax: the k largest logits,
    # softmax over those k alone; no bias leaf, no scale).
    scoring: str = "sigmoid"
    # The type the router's logits come out of their matmul in, and in
    # which they are compared and weighed. bfloat16 is the nearest
    # precision below: what a check's control switches on.
    router_dtype: Any = jnp.float32

    @property
    def held(self) -> int:
        return (self.n_experts if self.experts_held is None
                else self.experts_held)


def route_sigmoid(scores_in, bias, top_k: int, scale: float):
    """scores_in [M, n] float32 router outputs -> (chosen [M, k] int32,
    weights [M, k] float32): the k largest of sigmoid + bias, weighed
    by their sigmoids alone, normalised to sum ``scale``."""
    scores = jax.nn.sigmoid(scores_in)
    _best, chosen = jax.lax.top_k(scores + bias, top_k)
    picked = jnp.take_along_axis(scores, chosen, axis=-1)
    weights = picked / (jnp.sum(picked, axis=-1, keepdims=True)
                        + 1e-20) * scale
    return chosen.astype(jnp.int32), weights


def route_softmax(logits, top_k: int):
    """logits [M, n] float32 router outputs -> (chosen [M, k] int32,
    weights [M, k] float32): the k largest logits, weighed by a
    softmax over those k alone (they sum to 1)."""
    best, chosen = jax.lax.top_k(logits, top_k)
    return chosen.astype(jnp.int32), jax.nn.softmax(best, axis=-1)


def _expert_act(up, gate=None, gate_act: str = "silu"):
    """An expert's hidden activation (float32) from its up
    projection, and its gate projection where it has one (``gate_act``
    of the gate, times up: silu or relu)."""
    if gate is None:
        return jnp.square(jax.nn.relu(up))
    if gate_act == "relu":
        return jax.nn.relu(gate) * up
    return jax.nn.silu(gate) * up


def dense_experts(rows, chosen, weights, up, down, first: int,
                  gate=None, gate_act: str = "silu"):
    """sum_i w_i Expert_i(row) over the chosen experts that are held,
    as two matmuls over ALL the held experts (three for gated ones):
    rows [M, d] against up [E, d, f] gives every expert's hidden
    [M, E, f]; each is squared-relu'd (with ``gate`` [E, d, f]:
    multiplied by silu of the rows against it) and multiplied by the
    row's weight for that expert (0 where
    the row did not choose it); then ONE contraction over (E, f)
    against down [E, f, d] sums the experts' outputs in the matmul's
    own accumulator. Nothing is sorted, gathered or dropped, and a
    row's output is a function of that row alone. Each expert's
    weights are read once whatever the rows: the least a step can do
    when nearly every held expert is hit (96 rows x 6 of 128 leave one
    held expert in a hundred unchosen); the price is M x E x d x f
    multiply-adds where k / E of them are wanted, which hide under the
    weights' read up to a few hundred rows and are the whole cost of
    the layer beyond (PERF.md, PR 37). There the other road,
    grouped_experts, computes the same sum; experts_road says which
    one a layer traced with M rows takes. rows [M, d]; chosen /
    weights [M, k]; up [E, d, f], down
    [E, f, d] the held experts first .. first+E-1; Expert(x) =
    down(relu(up x)^2), or down(silu(gate x) * up x) with ``gate``.
    -> float32 [M, d]."""
    held = up.shape[0]
    local = chosen - first
    # [M, E]: the row's weight for each held expert
    weigh = jnp.sum(jnp.where(
        local[:, :, None] == jnp.arange(held), weights[:, :, None], 0.0),
        axis=1)

    def every(stack):
        return jax.lax.dot_general(
            rows, stack, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)          # [M, E, f]

    hidden = _expert_act(every(up),
                         None if gate is None else every(gate), gate_act)
    hidden = (hidden * weigh[:, :, None]).astype(rows.dtype)
    return jax.lax.dot_general(
        hidden, down, (((1, 2), (0, 1)), ((), ())),
        preferred_element_type=jnp.float32)


def grouped_experts(rows, chosen, weights, up, down, first: int,
                    gate=None, gate_act: str = "silu",
                    interpret: bool = False):
    """dense_experts' sum by its other road: only the wanted
    multiply-adds. The M x k (row, choice) pairs are sorted by expert
    (stable; pairs on experts this chip does not hold go behind the
    last held group and are never computed), the rows gathered in that
    order, and ``up`` (``gate``) and ``down`` are each ONE grouped
    matmul over the held stack as stored
    (ops/grouped_matmul.py: no copy of a stack); the pairs' outputs go
    back to their rows through the inverse permutation and a row's k
    are added. The rounding points are dense_experts': operands as
    given (bfloat16 in serving), products accumulated in float32, the
    activation in float32, a pair's hidden row weighed by its float32
    router weight BEFORE its one rounding to the operands' type,
    ``down`` accumulated in float32, a row's k outputs added in
    float32. No capacity, nothing dropped: every pair on ONE expert is
    one long group. A row's output is a function of that row alone.
    What the kernel leaves unwritten behind the last group (garbage,
    NaN included) is taken out by a select, never multiplied by 0.
    Same arguments as dense_experts; ``interpret`` runs the kernel in
    the Pallas interpreter (off the TPU). -> float32 [M, d]."""
    from batch_shipyard_tpu.ops import grouped_matmul as gm
    held = up.shape[0]
    m, k = chosen.shape
    pairs = m * k
    local = (chosen - first).reshape(pairs)
    here = (local >= 0) & (local < held)
    # held pairs in expert order, then the rest: a stable sort keeps
    # a group's pairs in row order
    order = jnp.argsort(jnp.where(here, local, held), stable=True)
    sizes = jnp.sum(local[:, None] == jnp.arange(held), axis=0,
                    dtype=jnp.int32)
    # the kernel walks whole row tiles: pad the ORDER (cheap), never a
    # stack; the padding lies behind every group
    padded = -(-pairs // gm.ROW_TILE) * gm.ROW_TILE
    order = jnp.pad(order, (0, padded - pairs))
    sorted_rows = jnp.take(rows, order // k, axis=0)

    def every(stack):
        return gm.grouped_matmul(sorted_rows, stack, sizes,
                                 interpret=interpret)   # [P, f]

    hidden = _expert_act(every(up),
                         None if gate is None else every(gate), gate_act)
    hidden = (hidden * jnp.take(weights.reshape(pairs), order)[:, None]
              ).astype(rows.dtype)
    out = gm.grouped_matmul(hidden, down, sizes,
                            interpret=interpret)        # [P, d]
    # pair p's output is at sorted position inverse[p]
    inverse = jnp.zeros((pairs,), jnp.int32).at[order[:pairs]].set(
        jnp.arange(pairs, dtype=jnp.int32))
    out = jnp.take(out, inverse, axis=0).reshape(m, k, -1)
    return jnp.sum(jnp.where(here.reshape(m, k, 1), out, 0.0), axis=1)


# Rows from which a layer takes the grouped road. dense_experts'
# multiply-adds (rows x held x d x f) hide under the read of the held
# stacks up to about 197e12 / 819e9 = 240 rows on a v5e. Timed there a
# layer (tools/experts_road_timing.py; PERF.md, PR 37): at 256 rows
# the two roads tie, one hybrid configuration a tenth faster grouped
# and the other a tenth slower; at 384 both are a fifth faster
# grouped, at 1,024 two and a half times. So one number serves both,
# set where both are past the tie (prefill buckets are powers of two:
# today it reads "from the 512 bucket").
GROUPED_FROM_ROWS = 384


def experts_road(rows: int, config: RoutedConfig) -> str:
    """"dense" or "grouped": the road RoutedExperts takes when it is
    traced with ``rows`` = batch x length rows. A function of that
    static shape alone (and of the backend: the grouped road's kernel
    is a TPU kernel, so off the TPU the answer is "dense"): a decode
    step's slots and the short prefill buckets stay dense, where every
    held expert is hit and its read hides the unwanted multiply-adds;
    the long buckets go grouped. A serving engine asks the same
    question to count its grouped prefills (serving.Launch.road)."""
    del config      # one crossover serves every shape measured so far
    if jax.default_backend() != "tpu":
        return "dense"
    return "grouped" if rows >= GROUPED_FROM_ROWS else "dense"


class RoutedExperts(nn.Module):
    """x -> sum_i w_i Expert_i(x) [held experts] + Shared(x), every
    expert down(relu(up x)^2), or with ``config.gated``
    down(act(gate x) * up x), act silu or relu (``config.gate_act``),
    without bias, with no capacity, by
    the road experts_road picks for the rows this call is traced with
    (dense_experts for a decode step and a short prefill,
    grouped_experts for a long one: the same sum). ``config.d_shared``
    0: no shared expert, no ``shared_*`` leaf, nothing added. The
    router runs in float32, by one of two scoring rules
    (``config.scoring``): "sigmoid" (route_sigmoid: sigmoid scores, a
    selection bias, weights normalised to ``scale``) or "softmax"
    (route_softmax: top-k on the logits, softmax over the chosen; no
    bias leaf; ``config.router_dtype`` bfloat16 is the lower-precision
    control). It reads ``router_input`` where one is handed (the
    normed input of ANOTHER block: a model whose router is placed
    before the token mixer), else x; the experts' matmuls read x. The
    choices
    [B, T, k] (indices over all n_experts) are sown into the
    "decisions" collection, for a serving engine to hand to whoever
    checks them (serving.ContinuousBatcher.take_decisions).
    ``live_rows`` ([B, T] bool, None = all): the rows somebody reads.
    The others' pairs fall on no held expert: on the grouped road they
    lie behind the last group, make no visit to a stack and are never
    computed (a row tile more is a read of a slab more:
    ops/grouped_matmul.py), and their routed output is zeros; what
    they chose is sown all the same.
    """
    config: RoutedConfig
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x, router_input=None, live_rows=None):
        cfg = self.config
        if cfg.scoring not in ("sigmoid", "softmax") or \
                cfg.gate_act not in ("silu", "relu"):
            raise ValueError(f"no scoring rule {cfg.scoring!r} or gate "
                             f"activation {cfg.gate_act!r}")
        batch, length, d_model = x.shape
        kernel = nn.initializers.lecun_normal()
        stacked = nn.initializers.lecun_normal(in_axis=-2, out_axis=-1,
                                               batch_axis=(0,))
        router = self.param("router_kernel", kernel,
                            (d_model, cfg.n_experts), self.param_dtype)
        if cfg.scoring == "sigmoid":
            bias = self.param("e_score_correction_bias",
                              nn.initializers.zeros, (cfg.n_experts,),
                              jnp.float32)
        up = self.param("experts_up", stacked,
                        (cfg.held, d_model, cfg.d_expert),
                        self.param_dtype)
        down = self.param("experts_down", stacked,
                          (cfg.held, cfg.d_expert, d_model),
                          self.param_dtype)
        if cfg.d_shared:
            shared_up = self.param(
                "shared_up", kernel, (d_model, cfg.d_shared),
                self.param_dtype)
            shared_down = self.param(
                "shared_down", kernel, (cfg.d_shared, d_model),
                self.param_dtype)
        gate = shared_gate = None
        if cfg.gated:
            gate = self.param("experts_gate", stacked,
                              (cfg.held, d_model, cfg.d_expert),
                              self.param_dtype).astype(self.dtype)
            if cfg.d_shared:
                shared_gate = self.param(
                    "shared_gate", kernel, (d_model, cfg.d_shared),
                    self.param_dtype).astype(self.dtype)
        rows = x.reshape(batch * length, d_model).astype(self.dtype)
        scored = rows if router_input is None else router_input.reshape(
            batch * length, d_model).astype(self.dtype)
        # bfloat16 operands multiply exactly into float32: the router
        # is a float32 computation on the activations as they are
        logits = jnp.dot(scored, router.astype(self.dtype),
                         preferred_element_type=cfg.router_dtype)
        if cfg.scoring == "sigmoid":
            chosen, weights = route_sigmoid(logits, bias, cfg.top_k,
                                            cfg.scale)
        else:
            chosen, weights = route_softmax(logits, cfg.top_k)
        self.sow("decisions", "chosen",
                 chosen.reshape(batch, length, cfg.top_k))
        if live_rows is not None:
            chosen = jnp.where(live_rows.reshape(-1, 1), chosen, -1)
        stacks = (up.astype(self.dtype), down.astype(self.dtype),
                  cfg.first_expert, gate, cfg.gate_act)
        if experts_road(batch * length, cfg) == "grouped":
            routed = grouped_experts(
                rows, chosen, weights, *stacks,
                interpret=jax.default_backend() != "tpu")
        else:
            routed = dense_experts(rows, chosen, weights, *stacks)
        if not cfg.d_shared:
            return routed.astype(self.dtype).reshape(
                batch, length, d_model)

        def shared_in(kernel):
            return jnp.dot(rows, kernel.astype(self.dtype),
                           preferred_element_type=jnp.float32)

        hidden = _expert_act(
            shared_in(shared_up), None if shared_gate is None
            else shared_in(shared_gate), cfg.gate_act).astype(self.dtype)
        shared = jnp.dot(hidden, shared_down.astype(self.dtype),
                         preferred_element_type=jnp.float32)
        return (routed + shared).astype(self.dtype).reshape(
            batch, length, d_model)
