"""Explicit expert-parallel MoE dispatch over the hierarchical
all-to-all (ROADMAP 'shard_map MoE dispatch variant'): equivalence
with the dense einsum formulation on a factored 2x4 ep mesh."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from flax import linen as nn
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from batch_shipyard_tpu.models import moe

E, D, F = 8, 64, 128          # experts, d_model, d_ff
G_LOCAL = 16                  # tokens per device group
CAP = 4


def _mesh():
    devices = np.array(jax.devices()[:8]).reshape(2, 4)
    return Mesh(devices, ("ep_out", "ep_in"))


def _weights(seed=0):
    rng = np.random.RandomState(seed)
    return (
        jnp.asarray(rng.randn(D, E) / 8, jnp.float32),       # router
        jnp.asarray(rng.randn(E, D, F) / 8, jnp.float32),    # gate
        jnp.asarray(rng.randn(E, D, F) / 8, jnp.float32),    # up
        jnp.asarray(rng.randn(E, F, D) / 11, jnp.float32),   # down
    )


def _dense_group(flat_g, router, w_gate, w_up, w_down, routing,
                 num_selected=2):
    """The einsum formulation on ONE device group with FULL expert
    weights — the oracle for the distributed exchange."""
    logits = flat_g.astype(jnp.float32) @ router
    if routing == "expert_choice":
        dispatch, combine, aux = moe.expert_choice_routing(logits, CAP)
    elif routing == "topk":
        dispatch, combine, aux = moe.topk_routing(logits, CAP,
                                                  num_selected)
    else:
        dispatch, combine, aux = moe.top1_routing(logits, CAP)
    expert_in = jnp.einsum("gec,gd->ecd", dispatch, flat_g)
    gate_act = jnp.einsum("ecd,edf->ecf", expert_in, w_gate)
    up_act = jnp.einsum("ecd,edf->ecf", expert_in, w_up)
    out = jnp.einsum("ecf,efd->ecd", nn.silu(gate_act) * up_act,
                     w_down)
    return jnp.einsum("gec,ecd->gd", combine, out), aux


@pytest.mark.parametrize("routing", ["top1", "topk",
                                     "expert_choice"])
def test_hierarchical_ep_dispatch_matches_dense(routing):
    mesh = _mesh()
    router, w_gate, w_up, w_down = _weights()
    rng = np.random.RandomState(3)
    tokens = jnp.asarray(rng.randn(8 * G_LOCAL, D), jnp.float32)

    def body(flat, router, wg, wu, wd):
        return moe.moe_ep_apply_shard(
            flat, router, wg, wu, wd, capacity=CAP,
            outer_axis="ep_out", inner_axis="ep_in",
            routing=routing, dtype=jnp.float32)

    ep = ("ep_out", "ep_in")
    fn = shard_map(
        body, mesh=mesh,
        in_specs=(P(ep, None), P(None, None), P(ep, None, None),
                  P(ep, None, None), P(ep, None, None)),
        out_specs=(P(ep, None), P()),
        check_vma=False)
    got, aux = jax.jit(fn)(tokens, router, w_gate, w_up, w_down)

    want = []
    want_aux = []
    for g in range(8):
        y, a = _dense_group(tokens[g * G_LOCAL:(g + 1) * G_LOCAL],
                            router, w_gate, w_up, w_down, routing)
        want.append(y)
        want_aux.append(a)
    want = jnp.concatenate(want, axis=0)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(float(aux),
                               float(np.mean(want_aux)), rtol=1e-5)


def test_hierarchical_ep_dispatch_differentiable():
    """The exchange is an involution of transposable collectives, so
    the whole body must be trainable end to end."""
    mesh = _mesh()
    router, w_gate, w_up, w_down = _weights(seed=5)
    rng = np.random.RandomState(7)
    tokens = jnp.asarray(rng.randn(8 * G_LOCAL, D), jnp.float32)
    ep = ("ep_out", "ep_in")

    def loss(params, flat):
        def body(flat, router, wg, wu, wd):
            y, aux = moe.moe_ep_apply_shard(
                flat, router, wg, wu, wd, capacity=CAP,
                outer_axis="ep_out", inner_axis="ep_in",
                dtype=jnp.float32)
            return jnp.sum(y ** 2)[None] + 0.01 * aux[None]

        fn = shard_map(
            body, mesh=mesh,
            in_specs=(P(ep, None), P(None, None),
                      P(ep, None, None), P(ep, None, None),
                      P(ep, None, None)),
            out_specs=P(ep),
            check_vma=False)
        return jnp.sum(fn(flat, *params))

    grads = jax.jit(jax.grad(loss))((router, w_gate, w_up, w_down),
                                    tokens)
    for g in grads:
        arr = np.asarray(g)
        assert np.all(np.isfinite(arr))
        assert np.abs(arr).sum() > 0

def test_single_axis_ep_dispatch_matches_dense():
    """outer_axis=None: the exchange degenerates to one all_to_all
    over a single 8-way ep axis — same per-group outputs."""
    mesh = Mesh(np.array(jax.devices()[:8]), ("ep",))
    router, w_gate, w_up, w_down = _weights(seed=11)
    rng = np.random.RandomState(13)
    tokens = jnp.asarray(rng.randn(8 * G_LOCAL, D), jnp.float32)

    def body(flat, router, wg, wu, wd):
        return moe.moe_ep_apply_shard(
            flat, router, wg, wu, wd, capacity=CAP,
            outer_axis=None, inner_axis="ep", dtype=jnp.float32)

    fn = shard_map(
        body, mesh=mesh,
        in_specs=(P("ep", None), P(None, None), P("ep", None, None),
                  P("ep", None, None), P("ep", None, None)),
        out_specs=(P("ep", None), P()),
        check_vma=False)
    got, aux = jax.jit(fn)(tokens, router, w_gate, w_up, w_down)
    outs, auxes = zip(*[
        _dense_group(tokens[g * G_LOCAL:(g + 1) * G_LOCAL],
                     router, w_gate, w_up, w_down, "top1")
        for g in range(8)])
    want = jnp.concatenate(outs, axis=0)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(float(aux), float(np.mean(auxes)),
                               rtol=1e-5)


@pytest.mark.slow
def test_moe_stage_inside_1f1b_pipeline():
    """dp x pp x ep composition (ROADMAP 'wire it into the training
    path'): a 2-stage 1F1B pipeline whose stages each run an
    expert-parallel MoE over the ep axis — loss AND parameter
    gradients match the sequential dense computation."""
    from batch_shipyard_tpu.parallel import pipeline as pl

    S, N_EP, B, M = 2, 4, 32, 2
    mb = B // M                      # tokens per microbatch
    g_local = mb // N_EP
    cap = max(1, g_local)            # per-group capacity
    mesh = Mesh(np.array(jax.devices()[:8]).reshape(S, N_EP),
                ("pp", "ep"))
    rng = np.random.RandomState(17)

    def stage_params(seed):
        r = np.random.RandomState(seed)
        return {
            "router": jnp.asarray(r.randn(D, E) / 8, jnp.float32),
            "wg": jnp.asarray(r.randn(E, D, F) / 8, jnp.float32),
            "wu": jnp.asarray(r.randn(E, D, F) / 8, jnp.float32),
            "wd": jnp.asarray(r.randn(E, F, D) / 11, jnp.float32),
        }

    per_stage = [stage_params(1), stage_params(2)]
    stacked = pl.stack_stage_params(per_stage)
    x = jnp.asarray(rng.randn(B, D), jnp.float32)
    targets = jnp.asarray(rng.randn(B, D), jnp.float32)
    last = {"w": jnp.asarray(rng.randn(D, D) / 8, jnp.float32)}

    def stage_fn(p, xin):
        y, _aux = moe.moe_ep_stage(
            xin, p["router"], p["wg"], p["wu"], p["wd"],
            capacity=cap, inner_axis="ep", dtype=jnp.float32)
        return xin + y  # residual, like a transformer block

    def last_fn(lp, y, tgt):
        return jnp.mean((y @ lp["w"] - tgt) ** 2)

    specs = {
        "router": P("pp", None, None),
        "wg": P("pp", "ep", None, None),
        "wu": P("pp", "ep", None, None),
        "wd": P("pp", "ep", None, None),
    }
    loss, dstage, dlast, _dx = pl.pipeline_1f1b_train(
        stacked, x, targets, last, mesh=mesh, stage_fn=stage_fn,
        last_fn=last_fn, num_microbatches=M, batch_axes=(),
        stage_param_specs=specs)

    # Sequential dense reference with the SAME routing groups: each
    # microbatch's tokens split into N_EP groups routed
    # independently with full expert weights.
    def dense_stage(p, xin):
        outs = []
        for g in range(N_EP):
            seg = xin[g * g_local:(g + 1) * g_local]
            logits = seg.astype(jnp.float32) @ p["router"]
            d_, c_, _a = moe.top1_routing(logits, cap)
            ein = jnp.einsum("gec,gd->ecd", d_, seg)
            ga = jnp.einsum("ecd,edf->ecf", ein, p["wg"])
            ua = jnp.einsum("ecd,edf->ecf", ein, p["wu"])
            eo = jnp.einsum("ecf,efd->ecd", nn.silu(ga) * ua,
                            p["wd"])
            outs.append(jnp.einsum("gec,ecd->gd", c_, eo))
        return xin + jnp.concatenate(outs, axis=0)

    def ref_loss(stages, lastp, x):
        total = 0.0
        for m in range(M):
            h = x[m * mb:(m + 1) * mb]
            tgt = targets[m * mb:(m + 1) * mb]
            for p in stages:
                h = dense_stage(p, h)
            total = total + last_fn(lastp, h, tgt)
        return total / M

    want = ref_loss(per_stage, last, x)
    np.testing.assert_allclose(float(loss), float(want), rtol=2e-5)
    g_want = jax.grad(
        lambda stages, lastp: ref_loss(stages, lastp, x),
        argnums=(0, 1))(per_stage, last)
    for k in ("router", "wg", "wu", "wd"):
        got = np.asarray(dstage[k])          # [S, ...]
        ref0 = np.asarray(g_want[0][0][k])
        ref1 = np.asarray(g_want[0][1][k])
        np.testing.assert_allclose(got[0], ref0, rtol=3e-4,
                                   atol=3e-5)
        np.testing.assert_allclose(got[1], ref1, rtol=3e-4,
                                   atol=3e-5)
    np.testing.assert_allclose(np.asarray(dlast["w"]),
                               np.asarray(g_want[1]["w"]),
                               rtol=3e-4, atol=3e-5)


@pytest.mark.parametrize("axes", [("ep",), ("ep_out", "ep_in")])
def test_moe_ep_stage_grads_including_aux(axes):
    """moe_ep_stage on a replicated stream: loss = f(y) + c*aux must
    match the dense reference's gradients EXACTLY (the aux path is
    where a VJP miscount hides — it was n_ep-times overcounted before
    this test existed), on both the single-axis and factored-mesh
    forms."""
    if len(axes) == 1:
        mesh = Mesh(np.array(jax.devices()[:8]), axes)
        outer, inner = None, axes[0]
    else:
        mesh = Mesh(np.array(jax.devices()[:8]).reshape(2, 4), axes)
        outer, inner = axes
    router, w_gate, w_up, w_down = _weights(seed=21)
    rng = np.random.RandomState(23)
    n_ep = 8
    tokens = jnp.asarray(rng.randn(n_ep * G_LOCAL, D), jnp.float32)
    spec_e = P(axes if len(axes) > 1 else axes[0], None, None)

    # moe_ep_stage's contract is the pipeline's: differentiation
    # happens INSIDE the shard_map body (manual vjp per device, like
    # pipeline_1f1b_train's tick), where the replicated-full
    # cotangent invariant holds by construction. Replicating that
    # here: grads computed in-body, shipped out with their natural
    # specs (router replicated, experts ep-sharded).
    def body(flat, r, a, b, c):
        def local_loss(r, a, b, c):
            y, aux = moe.moe_ep_stage(
                flat, r, a, b, c, capacity=CAP, inner_axis=inner,
                outer_axis=outer, dtype=jnp.float32)
            return jnp.sum(y ** 2) + 0.3 * aux

        return jax.grad(local_loss, argnums=(0, 1, 2, 3))(r, a, b, c)

    fn = shard_map(
        body, mesh=mesh,
        in_specs=(P(), P(None, None), spec_e, spec_e, spec_e),
        out_specs=(P(None, None), spec_e, spec_e, spec_e),
        check_vma=False)
    got = jax.jit(fn)(tokens, router, w_gate, w_up, w_down)

    def dense_loss(params, flat):
        r, wg, wu, wd = params
        total = 0.0
        auxes = []
        for g in range(n_ep):
            seg = flat[g * G_LOCAL:(g + 1) * G_LOCAL]
            logits = seg.astype(jnp.float32) @ r
            d_, c_, a_ = moe.top1_routing(logits, CAP)
            ein = jnp.einsum("gec,gd->ecd", d_, seg)
            ga = jnp.einsum("ecd,edf->ecf", ein, wg)
            ua = jnp.einsum("ecd,edf->ecf", ein, wu)
            eo = jnp.einsum("ecf,efd->ecd", nn.silu(ga) * ua, wd)
            total = total + jnp.sum(
                jnp.einsum("gec,ecd->gd", c_, eo) ** 2)
            auxes.append(a_)
        return total + 0.3 * jnp.mean(jnp.stack(auxes))

    want = jax.grad(dense_loss)((router, w_gate, w_up, w_down),
                                tokens)
    for a, b in zip(got, want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=3e-5, atol=3e-6)


def test_moe_ep_stage_rejects_indivisible_tokens():
    mesh = Mesh(np.array(jax.devices()[:8]), ("ep",))
    router, w_gate, w_up, w_down = _weights()
    tokens = jnp.zeros((30, D), jnp.float32)  # 30 % 8 != 0

    def body(flat, r, a, b, c):
        return moe.moe_ep_stage(flat, r, a, b, c, capacity=CAP,
                                inner_axis="ep",
                                dtype=jnp.float32)[0]

    fn = shard_map(
        body, mesh=mesh,
        in_specs=(P(), P(None, None), P("ep", None, None),
                  P("ep", None, None), P("ep", None, None)),
        out_specs=P(), check_vma=False)
    with pytest.raises(ValueError) as exc:
        jax.jit(fn)(tokens, router, w_gate, w_up, w_down)
    assert "not divisible" in str(exc.value)
