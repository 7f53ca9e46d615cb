"""Pipeline parallelism: GPipe-style microbatched execution over a
``pp`` mesh axis.

Each pipeline stage (one mesh slot along ``pp``) owns a slice of the
layer stack; microbatches flow through stages via neighbor ppermute —
on a TPU pod slice each hop rides one ICI link. The schedule is the
classic (S + M - 1)-tick wavefront: at tick t, stage s processes
microbatch (t - s). The whole schedule is a single differentiable
lax.scan, so the backward pass is the reverse wavefront generated by
autodiff — no hand-written bwd schedule.

Shapes: stage activations must be shape-invariant through stage_fn
(true for transformer blocks: [mb, T, D] -> [mb, T, D]); embedding and
head live outside the pipelined middle.

Composes with the other axes: the batch dim can remain sharded over
dp/fsdp and the sequence over sp inside each microbatch.
"""

from __future__ import annotations

import functools
import math
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P


def stack_stage_params(per_stage_params: list[Any]) -> Any:
    """Stack S per-stage parameter pytrees into one pytree with a
    leading stage dim (the dim sharded over pp)."""
    return jax.tree_util.tree_map(
        lambda *leaves: jnp.stack(leaves), *per_stage_params)


def pipeline_apply(stage_params: Any, x, *, mesh: Mesh,
                   stage_fn: Callable[[Any, Any], Any],
                   num_microbatches: int, axis_name: str = "pp",
                   batch_axes: tuple = ("dp", "fsdp")):
    """Run x through S pipelined stages.

    stage_params: pytree with leading dim S (sharded over axis_name).
    x: [B, ...] global input to stage 0; B divisible by
    num_microbatches. stage_fn(params_one_stage, x_mb) -> y_mb with
    y_mb.shape == x_mb.shape. Returns [B, ...] output of the last
    stage, replicated over axis_name.
    """
    num_stages = mesh.shape[axis_name]
    batch = x.shape[0]
    local_batch = batch // math.prod(
        mesh.shape[a] for a in batch_axes)
    if local_batch % num_microbatches:
        raise ValueError(
            f"per-shard batch {local_batch} (global {batch}) not "
            f"divisible by microbatches {num_microbatches}")

    def per_device(params_local, x_local):
        s = jax.lax.axis_index(axis_name)
        params_stage = jax.tree_util.tree_map(
            lambda p: p[0], params_local)
        mb = x_local.shape[0] // num_microbatches
        mbs = x_local.reshape(num_microbatches, mb, *x_local.shape[1:])
        ticks = num_stages + num_microbatches - 1
        fwd_perm = [(i, i + 1) for i in range(num_stages - 1)]

        def tick(carry, t):
            buf, outputs = carry
            # Stage 0 injects microbatch t; later stages consume the
            # activation handed over by their predecessor.
            mb_idx = jnp.clip(t, 0, num_microbatches - 1)
            x_in = jnp.where(s == 0, mbs[mb_idx], buf)
            y = stage_fn(params_stage, x_in)
            active = jnp.logical_and(t - s >= 0,
                                     t - s < num_microbatches)
            y = jnp.where(active, y, buf)
            # Last stage records its finished microbatch.
            out_idx = jnp.clip(t - (num_stages - 1), 0,
                               num_microbatches - 1)
            record = jnp.logical_and(s == num_stages - 1,
                                     jnp.logical_and(
                                         t - (num_stages - 1) >= 0,
                                         active))
            outputs = jnp.where(
                record,
                outputs.at[out_idx].set(y),
                outputs)
            # Hand the activation to the next stage (stage 0 receives
            # zeros, which it overwrites with its next microbatch).
            buf_next = (jax.lax.ppermute(y, axis_name, fwd_perm)
                        if num_stages > 1 else y)
            return (buf_next, outputs), None

        zeros_buf = jnp.zeros((mb, *x_local.shape[1:]), x_local.dtype)
        zeros_out = jnp.zeros(
            (num_microbatches, mb, *x_local.shape[1:]), x_local.dtype)
        (_, outputs), _ = jax.lax.scan(
            tick, (zeros_buf, zeros_out), jnp.arange(ticks))
        # Only the last stage holds real outputs; psum over pp (other
        # stages contribute zeros) replicates the result everywhere.
        mask = (s == num_stages - 1).astype(outputs.dtype)
        outputs = jax.lax.psum(outputs * mask, axis_name)
        return outputs.reshape(x_local.shape)

    data_spec = P(batch_axes, *([None] * (x.ndim - 1)))
    param_spec = jax.tree_util.tree_map(
        lambda p: P(axis_name, *([None] * (p.ndim - 1))), stage_params)
    fn = shard_map(
        per_device, mesh=mesh,
        in_specs=(param_spec, data_spec), out_specs=data_spec,
        check_vma=False)
    return fn(stage_params, x)


def pipeline_1f1b_train(stage_params: Any, x, targets, last_params: Any,
                        *, mesh: Mesh,
                        stage_fn: Callable[[Any, Any], Any],
                        last_fn: Callable[[Any, Any, Any], Any],
                        num_microbatches: int, axis_name: str = "pp",
                        batch_axes: tuple = ("dp",),
                        stage_param_specs: Any = None):
    """One-forward-one-backward pipelined training pass.

    Unlike ``pipeline_apply`` (GPipe: a forward wavefront whose
    backward is autodiff's reverse wavefront — holding every
    microbatch's stage activations live), this runs the classic 1F1B
    schedule as ONE manual fwd+bwd scan: stage s forwards microbatch i
    at tick s+i and backwards microbatch j at tick 2S-2-s+j, so each
    tick does exactly one forward and one backward per stage and a
    stage holds at most 2(S-s) in-flight activations — bounded by the
    stage count, independent of the microbatch count. The backward
    recomputes the stage forward from its stored INPUT (Megatron-style
    full recompute), so only [2S, mb, ...] inputs are buffered.

    The loss must be computable at the LAST stage per microbatch (that
    is what lets its backward start while earlier microbatches are
    still forwarding), so the head lives inside ``last_fn``:

      last_fn(last_params, y_mb, target_mb) -> scalar mean loss

    Returns (loss, dstage_params, dlast_params, dx):
      loss          mean over microbatches;
      dstage_params pytree like stage_params ([S, ...], sharded pp);
      dlast_params  pytree like last_params (replicated);
      dx            [B, ...] cotangent of x (for the embedding's bwd).

    stage_param_specs: optional PartitionSpec pytree for the stage
    params (default: P(pp) on the leading dim, replicated elsewhere).
    Pass tp-sharded specs to run Megatron tensor parallelism INSIDE
    each stage — stage_fn then sees tp-local parameter shards and owns
    the intra-stage psums (e.g. TransformerConfig.tp_axis).

    Reference analog: none (SURVEY.md 5.8 net-new) — schedule follows
    the public 1F1B literature (PipeDream-flush / Megatron-LM).
    """
    num_stages = mesh.shape[axis_name]
    batch = x.shape[0]
    local_batch = batch // math.prod(
        mesh.shape[a] for a in batch_axes)
    if local_batch % num_microbatches:
        raise ValueError(
            f"per-shard batch {local_batch} (global {batch}) not "
            f"divisible by microbatches {num_microbatches}")
    if num_microbatches < num_stages:
        raise ValueError(
            f"1F1B needs microbatches >= stages "
            f"({num_microbatches} < {num_stages})")

    def per_device(params_local, x_local, tgt_local, last_local):
        s = jax.lax.axis_index(axis_name)
        params_stage = jax.tree_util.tree_map(
            lambda p: p[0], params_local)
        mb = x_local.shape[0] // num_microbatches
        mbs = x_local.reshape(num_microbatches, mb, *x_local.shape[1:])
        tgts = tgt_local.reshape(num_microbatches, mb,
                                 *tgt_local.shape[1:])
        S, M = num_stages, num_microbatches
        cap = 2 * S  # ring capacity; lifetime i-j <= 2S-2 < cap
        ticks = M + 2 * S - 2
        fwd_perm = [(i, i + 1) for i in range(S - 1)]
        bwd_perm = [(i, i - 1) for i in range(1, S)]

        def fwd_only(p, xin):
            return stage_fn(p, xin)

        def tick(carry, t):
            (x_buf, g_buf, act_store, dstage, dlast, loss_acc,
             dx_out) = carry
            i = t - s                      # fwd microbatch index
            j = t - (2 * S - 2 - s)        # bwd microbatch index
            do_fwd = jnp.logical_and(i >= 0, i < M)
            do_bwd = jnp.logical_and(j >= 0, j < M)
            i_c = jnp.clip(i, 0, M - 1)
            j_c = jnp.clip(j, 0, M - 1)

            # ---- forward slot ----
            x_in = jnp.where(s == 0, mbs[i_c], x_buf)
            y = fwd_only(params_stage, x_in)
            act_store = jnp.where(
                do_fwd, act_store.at[i_c % cap].set(x_in), act_store)

            # Last stage: per-microbatch loss + its cotangent, the
            # trigger of the backward wavefront. last_fn is the model
            # head (chunked CE over the vocab) — gate it with cond so
            # non-last stages skip it entirely instead of computing a
            # discarded copy every tick (last_fn has no collectives,
            # so per-device divergence is legal under shard_map).
            is_last = (s == S - 1)

            def head_fwd_bwd(y, tgt):
                return jax.value_and_grad(
                    last_fn, argnums=(0, 1))(last_local, y, tgt)

            def head_skip(y, tgt):
                return (jnp.float32(0.0),
                        (jax.tree_util.tree_map(jnp.zeros_like,
                                                last_local),
                         jnp.zeros_like(y)))

            loss_i, (dlast_i, dy_i) = jax.lax.cond(
                is_last, head_fwd_bwd, head_skip, y, tgts[i_c])
            use_loss = jnp.logical_and(is_last, do_fwd)
            loss_acc = loss_acc + jnp.where(use_loss, loss_i, 0.0)

            # ---- backward slot (recompute fwd from stored input) ----
            x_saved = act_store[j_c % cap]
            g_in = jnp.where(is_last, dy_i.astype(g_buf.dtype), g_buf)
            _, vjp_fn = jax.vjp(fwd_only, params_stage, x_saved)
            dp_j, dx_j = vjp_fn(g_in.astype(y.dtype))
            dstage = jax.tree_util.tree_map(
                lambda a, d: a + jnp.where(do_bwd, d, 0.0).astype(
                    a.dtype), dstage, dp_j)
            dlast = jax.tree_util.tree_map(
                lambda a, d: a + jnp.where(use_loss, d, 0.0).astype(
                    a.dtype), dlast, dlast_i)
            dx_out = jnp.where(
                jnp.logical_and(s == 0, do_bwd),
                dx_out.at[j_c].set(dx_j.astype(dx_out.dtype)),
                dx_out)

            # ---- lockstep neighbor exchange ----
            x_next = (jax.lax.ppermute(
                jnp.where(do_fwd, y, x_buf), axis_name, fwd_perm)
                if S > 1 else y)
            g_next = (jax.lax.ppermute(
                jnp.where(do_bwd, dx_j.astype(g_buf.dtype), g_buf),
                axis_name, bwd_perm) if S > 1 else g_buf)
            return (x_next, g_next, act_store, dstage, dlast,
                    loss_acc, dx_out), None

        zeros_mb = jnp.zeros((mb, *x_local.shape[1:]), x_local.dtype)
        carry0 = (
            zeros_mb,
            jnp.zeros_like(zeros_mb),
            jnp.zeros((cap, mb, *x_local.shape[1:]), x_local.dtype),
            jax.tree_util.tree_map(jnp.zeros_like, params_stage),
            jax.tree_util.tree_map(jnp.zeros_like, last_local),
            jnp.float32(0.0),
            jnp.zeros((M, mb, *x_local.shape[1:]), jnp.float32),
        )
        (_, _, _, dstage, dlast, loss_acc, dx_out), _ = jax.lax.scan(
            tick, carry0, jnp.arange(ticks))
        S_mask = (s == S - 1)
        # Replicate over pp (only one stage holds each quantity), then
        # data-parallel mean over the batch axes.
        loss = jax.lax.psum(
            jnp.where(S_mask, loss_acc, 0.0), axis_name) / M
        loss = jax.lax.pmean(loss, batch_axes)
        dlast = jax.tree_util.tree_map(
            lambda d: jax.lax.pmean(
                jax.lax.psum(
                    jnp.where(S_mask, d, jnp.zeros_like(d)),
                    axis_name) / M, batch_axes), dlast)
        # dstage grads are per-stage local: average over microbatches
        # (matches a mean-of-means loss) and re-attach the stage dim.
        dstage = jax.tree_util.tree_map(
            lambda d: jax.lax.pmean(d / M, batch_axes)[None], dstage)
        dx = jax.lax.psum(
            jnp.where(s == 0, dx_out, jnp.zeros_like(dx_out)),
            axis_name).reshape(x_local.shape) / M
        return loss, dstage, dlast, dx

    data_spec = P(batch_axes, *([None] * (x.ndim - 1)))
    tgt_spec = P(batch_axes, *([None] * (targets.ndim - 1)))
    param_spec = stage_param_specs
    if param_spec is None:
        param_spec = jax.tree_util.tree_map(
            lambda p: P(axis_name, *([None] * (p.ndim - 1))),
            stage_params)
    last_spec = jax.tree_util.tree_map(lambda p: P(), last_params)
    fn = shard_map(
        per_device, mesh=mesh,
        in_specs=(param_spec, data_spec, tgt_spec, last_spec),
        out_specs=(P(), param_spec, last_spec, data_spec),
        check_vma=False)
    return fn(stage_params, x, targets, last_params)


def sequential_apply(stage_params: Any, x,
                     stage_fn: Callable[[Any, Any], Any]):
    """Reference semantics: run stages one after another (what the
    pipeline must equal)."""
    num_stages = jax.tree_util.tree_leaves(stage_params)[0].shape[0]
    for s in range(num_stages):
        params_stage = jax.tree_util.tree_map(
            lambda p: p[s], stage_params)
        x = stage_fn(params_stage, x)
    return x


# ------------------- interleaved (virtual-stage) 1F1B -------------------

def interleaved_1f1b_schedule(num_stages: int, num_chunks: int,
                              num_microbatches: int) -> dict:
    """Build the Megatron-style interleaved 1F1B schedule as STATIC
    per-tick tables (numpy, host-side).

    Each device owns V = num_chunks model chunks (chunk k lives on
    device k % S); per-device op order is the Megatron interleave —
    warmup fwds (2(S-1-s) + (V-1)S of them), then alternating 1F/1B,
    then drain bwds — with chunk/microbatch selection by mixed radix:
    the k-th fwd on a device is chunk (k//S) % V of microbatch
    (k//(S*V))*S + k%S (bwds mirror with descending chunks). Timing
    comes from a greedy lockstep simulation: one op per device per
    tick, executed when its input (produced at an earlier tick;
    neighbor sends take one tick) is available. The tick tables drive
    a lax.scan — schedule as data, no data-dependent control flow on
    device.

    Returns a dict of [T, S] int32 tables (kind: 0 idle/1 fwd/2 bwd,
    chunk, mb; recv_* describing what arrives on each ring each tick)
    plus buffer sizes (x_slots/g_slots/act_slots) sized and
    collision-checked by the simulation.

    Reference analog: none (SURVEY.md 5.8 net-new); schedule follows
    the public Megatron-LM interleaved pipelining description
    (PAPERS.md).
    """
    import numpy as np

    S, V, M = num_stages, num_chunks, num_microbatches
    if M % S:
        raise ValueError(
            f"interleaved 1F1B needs microbatches % stages == 0 "
            f"(got {M} % {S})")
    total = M * V

    def radix(k):
        g, v, r = k // (S * V), (k // S) % V, k % S
        return v, g * S + r

    ops = []  # per device: list of (kind, chunk, mb)
    for s in range(S):
        warmup = min((S - s - 1) * 2 + (V - 1) * S, total)
        seq = []
        for k in range(warmup):
            v, m = radix(k)
            seq.append((1, v, m))
        fwd_k, bwd_k = warmup, 0
        while fwd_k < total:
            seq.append((1,) + radix(fwd_k))
            v, m = radix(bwd_k)
            seq.append((2, V - 1 - v, m))
            fwd_k += 1
            bwd_k += 1
        while bwd_k < total:
            v, m = radix(bwd_k)
            seq.append((2, V - 1 - v, m))
            bwd_k += 1
        ops.append(seq)

    # Greedy lockstep simulation.
    produced_f: dict = {}   # (s, v, m) -> tick fwd completed
    produced_b: dict = {}   # (s, v, m) -> tick bwd completed
    ptr = [0] * S
    kind_t, chunk_t, mb_t = [], [], []
    t = 0
    stall = 0
    while any(p < len(ops[s]) for s, p in enumerate(ptr)):
        row_k = np.zeros(S, np.int32)
        row_c = np.zeros(S, np.int32)
        row_m = np.zeros(S, np.int32)
        progressed = False
        for s in range(S):
            if ptr[s] >= len(ops[s]):
                continue
            kind, v, m = ops[s][ptr[s]]
            if kind == 1:
                if v == 0 and s == 0:
                    ready = True
                elif s > 0:
                    ready = produced_f.get((s - 1, v, m), t) < t
                else:
                    ready = produced_f.get((S - 1, v - 1, m), t) < t
            else:
                have_fwd = produced_f.get((s, v, m), t) < t
                if v == V - 1 and s == S - 1:
                    ready = have_fwd  # dy produced with the fwd
                elif s < S - 1:
                    ready = have_fwd and \
                        produced_b.get((s + 1, v, m), t) < t
                else:
                    ready = have_fwd and \
                        produced_b.get((0, v + 1, m), t) < t
            if ready:
                row_k[s], row_c[s], row_m[s] = kind, v, m
                (produced_f if kind == 1 else produced_b)[
                    (s, v, m)] = t
                ptr[s] += 1
                progressed = True
        kind_t.append(row_k)
        chunk_t.append(row_c)
        mb_t.append(row_m)
        stall = 0 if progressed else stall + 1
        if stall > 2:
            raise RuntimeError(
                f"interleaved schedule deadlocked at tick {t} "
                f"(S={S}, V={V}, M={M})")
        t += 1
    T = t
    kind = np.stack(kind_t)
    chunk = np.stack(chunk_t)
    mb = np.stack(mb_t)

    # Receive tables: what arrives at device s on each ring at tick t
    # (usable from t+1) = what its neighbor executed at tick t.
    rf_valid = np.zeros((T, S), np.int32)
    rf_chunk = np.zeros((T, S), np.int32)
    rf_mb = np.zeros((T, S), np.int32)
    rb_valid = np.zeros((T, S), np.int32)
    rb_chunk = np.zeros((T, S), np.int32)
    rb_mb = np.zeros((T, S), np.int32)
    for tt in range(T):
        for s in range(S):
            src = (s - 1) % S
            if kind[tt, src] == 1:
                v, m = chunk[tt, src], mb[tt, src]
                if not (v == V - 1 and src == S - 1):
                    dest_chunk = v if src < S - 1 else v + 1
                    rf_valid[tt, s] = 1
                    rf_chunk[tt, s] = dest_chunk
                    rf_mb[tt, s] = m
            src = (s + 1) % S
            if kind[tt, src] == 2:
                v, m = chunk[tt, src], mb[tt, src]
                if not (v == 0 and src == 0):
                    dest_chunk = v if src > 0 else v - 1
                    rb_valid[tt, s] = 1
                    rb_chunk[tt, s] = dest_chunk
                    rb_mb[tt, s] = m

    # Buffer sizing + modulo-collision check from outstanding sets.
    def assert_no_mod_collision(values, slots, what, key):
        mods = [m % slots for m in values]
        if len(set(mods)) != len(mods):
            raise AssertionError(
                f"interleaved schedule (S={S}, V={V}, M={M}): "
                f"{what} {values} at (stage,chunk)={key} collide "
                f"modulo {slots} slots — buffer would be silently "
                f"corrupted")

    def max_outstanding(recv_v, recv_c, recv_m, consume_kind,
                        check_slots=None):
        outstanding: dict = {}
        peak = 1
        for tt in range(T):
            for s in range(S):
                if recv_v[tt, s]:
                    key = (s, int(recv_c[tt, s]))
                    outstanding.setdefault(key, []).append(
                        int(recv_m[tt, s]))
                    peak = max(peak, len(outstanding[key]))
                    if check_slots is not None:
                        assert_no_mod_collision(
                            outstanding[key], check_slots,
                            "outstanding microbatches", key)
                if kind[tt, s] == consume_kind:
                    key = (s, int(chunk[tt, s]))
                    if key in outstanding and \
                            int(mb[tt, s]) in outstanding[key]:
                        outstanding[key].remove(int(mb[tt, s]))
        return peak

    x_slots = max_outstanding(rf_valid, rf_chunk, rf_mb, 1)
    g_slots = max(max_outstanding(rb_valid, rb_chunk, rb_mb, 2), 2)
    # Acts live from fwd to bwd of the same (s, chunk, mb).
    def act_outstanding(check_slots=None):
        live: dict = {}
        act_peak = 1
        for tt in range(T):
            for s in range(S):
                if kind[tt, s] == 1:
                    key = (s, int(chunk[tt, s]))
                    live.setdefault(key, []).append(int(mb[tt, s]))
                    act_peak = max(act_peak, len(live[key]))
                    if check_slots is not None:
                        assert_no_mod_collision(
                            live[key], check_slots,
                            "live activations", key)
                elif kind[tt, s] == 2:
                    key = (s, int(chunk[tt, s]))
                    if key in live and int(mb[tt, s]) in live[key]:
                        live[key].remove(int(mb[tt, s]))
        return act_peak

    act_slots = act_outstanding()
    # In-order production/consumption should make mb % slots
    # collision-free; VERIFY it by replaying the simulation with the
    # chosen slot counts rather than asserting by comment (advisor r2
    # finding #4) — an untested (S, V, M) combination violating the
    # invariant raises here instead of corrupting buffers at runtime.
    max_outstanding(rf_valid, rf_chunk, rf_mb, 1,
                    check_slots=x_slots)
    max_outstanding(rb_valid, rb_chunk, rb_mb, 2,
                    check_slots=g_slots)
    act_outstanding(check_slots=act_slots)
    idle = int((kind == 0).sum())
    return {
        "ticks": T, "kind": kind, "chunk": chunk, "mb": mb,
        "recv_fwd": (rf_valid, rf_chunk, rf_mb),
        "recv_bwd": (rb_valid, rb_chunk, rb_mb),
        "x_slots": x_slots, "g_slots": g_slots,
        "act_slots": act_slots,
        "idle_fraction": idle / (T * S),
    }


def stack_interleaved_chunk_params(per_chunk: list[Any],
                                   num_stages: int) -> Any:
    """Arrange S*V per-chunk param pytrees (layer order: chunk k holds
    layers k*L/(S*V)...) into the [S, V, ...] layout
    pipeline_interleaved_1f1b_train shards over pp: chunk k lives at
    [k % S, k // S]."""
    if len(per_chunk) % num_stages:
        raise ValueError(
            f"{len(per_chunk)} chunks do not divide over "
            f"{num_stages} stages")
    num_chunks = len(per_chunk) // num_stages

    def build(*leaves):
        rows = []
        for s in range(num_stages):
            rows.append(jnp.stack(
                [leaves[v * num_stages + s] for v in range(num_chunks)]))
        return jnp.stack(rows)

    return jax.tree_util.tree_map(build, *per_chunk)


def pipeline_interleaved_1f1b_train(
        chunk_params: Any, x, targets, last_params: Any, *,
        mesh: Mesh, stage_fn: Callable[[Any, Any], Any],
        last_fn: Callable[[Any, Any, Any], Any],
        num_microbatches: int, num_chunks: int,
        axis_name: str = "pp", batch_axes: tuple = ("dp",)):
    """Interleaved (virtual-stage) 1F1B pipelined training.

    Each device owns ``num_chunks`` model chunks instead of one
    contiguous stage, so warmup/drain bubbles are chunk-sized rather
    than stage-sized — the Megatron interleaved schedule. The static
    tick tables from interleaved_1f1b_schedule drive one lax.scan:
    each tick every device runs exactly one op (fwd, bwd-with-
    recompute, or idle) selected by lax.switch, then both ring
    ppermutes run UNconditionally (they are collectives; only the
    payload validity varies per device — the reason stage_fn must be
    collective-free here: no tp_axis inside interleaved stages).

    chunk_params: pytree with leading dims [S, V, ...] (see
    stack_interleaved_chunk_params), sharded over axis_name on dim 0.
    Returns (loss, dchunk_params, dlast, dx) like
    pipeline_1f1b_train.
    """
    S = mesh.shape[axis_name]
    V = num_chunks
    M = num_microbatches
    leading = jax.tree_util.tree_leaves(chunk_params)[0].shape[:2]
    if leading != (S, V):
        # A mismatch would otherwise clamp the traced chunk gather
        # silently (wrong params, no error).
        raise ValueError(
            f"chunk_params leading dims {leading} != (stages, chunks)"
            f" = ({S}, {V})")
    sched = interleaved_1f1b_schedule(S, V, M)
    T = sched["ticks"]
    kind_tbl = jnp.asarray(sched["kind"])
    chunk_tbl = jnp.asarray(sched["chunk"])
    mb_tbl = jnp.asarray(sched["mb"])
    rf_v, rf_c, rf_m = (jnp.asarray(a) for a in sched["recv_fwd"])
    rb_v, rb_c, rb_m = (jnp.asarray(a) for a in sched["recv_bwd"])
    XW, GW, AW = (sched["x_slots"], sched["g_slots"],
                  sched["act_slots"])

    batch = x.shape[0]
    local_batch = batch // math.prod(mesh.shape[a] for a in batch_axes)
    if local_batch % M:
        raise ValueError(
            f"per-shard batch {local_batch} not divisible by "
            f"microbatches {M}")

    def per_device(params_local, x_local, tgt_local, last_local):
        s = jax.lax.axis_index(axis_name)
        params_v = jax.tree_util.tree_map(
            lambda p: p[0], params_local)          # [V, ...]
        mb = x_local.shape[0] // M
        mbs = x_local.reshape(M, mb, *x_local.shape[1:])
        tgts = tgt_local.reshape(M, mb, *tgt_local.shape[1:])
        fwd_perm = [(i, (i + 1) % S) for i in range(S)]
        bwd_perm = [(i, (i - 1) % S) for i in range(S)]
        zeros_mb = jnp.zeros((mb, *x_local.shape[1:]), x_local.dtype)

        def pick_chunk(v):
            return jax.tree_util.tree_map(lambda p: p[v], params_v)

        def tick(carry, t):
            (x_buf, g_buf, acts, dstage, dlast, loss_acc,
             dx_out) = carry
            op = kind_tbl[t, s]
            v = chunk_tbl[t, s]
            m = mb_tbl[t, s]
            params_chunk = pick_chunk(v)
            is_head = jnp.logical_and(v == V - 1, s == S - 1)

            def idle_branch(carry_in):
                (x_buf, g_buf, acts, dstage, dlast, loss_acc,
                 dx_out) = carry_in
                return (carry_in, zeros_mb,
                        jnp.zeros_like(zeros_mb, jnp.float32))

            def fwd_branch(carry_in):
                (x_buf, g_buf, acts, dstage, dlast, loss_acc,
                 dx_out) = carry_in
                x_in = jnp.where(
                    jnp.logical_and(v == 0, s == 0),
                    mbs[m], x_buf[v, m % XW])
                y = stage_fn(params_chunk, x_in)
                acts = acts.at[v, m % AW].set(x_in)

                # Head: loss + dy, feeding this mb's bwd stream.
                def head(g_buf, dlast, loss_acc):
                    loss_i, (dlast_i, dy) = jax.value_and_grad(
                        last_fn, argnums=(0, 1))(last_local, y,
                                                 tgts[m])
                    g_buf = g_buf.at[V - 1, m % GW].set(
                        dy.astype(jnp.float32))
                    dlast = jax.tree_util.tree_map(
                        lambda a, d: a + d.astype(a.dtype),
                        dlast, dlast_i)
                    return g_buf, dlast, loss_acc + loss_i

                def no_head(g_buf, dlast, loss_acc):
                    return g_buf, dlast, loss_acc

                g_buf, dlast, loss_acc = jax.lax.cond(
                    is_head, head, no_head, g_buf, dlast, loss_acc)
                out = (x_buf, g_buf, acts, dstage, dlast, loss_acc,
                       dx_out)
                return (out, y, jnp.zeros_like(zeros_mb, jnp.float32))

            def bwd_branch(carry_in):
                (x_buf, g_buf, acts, dstage, dlast, loss_acc,
                 dx_out) = carry_in
                x_saved = acts[v, m % AW]
                g_in = g_buf[v, m % GW]
                _, vjp_fn = jax.vjp(stage_fn, params_chunk, x_saved)
                dp, dx_j = vjp_fn(g_in.astype(x_saved.dtype))
                dstage = jax.tree_util.tree_map(
                    lambda a, d: a.at[v].add(d.astype(a.dtype)),
                    dstage, dp)
                dx_out = jnp.where(
                    jnp.logical_and(v == 0, s == 0),
                    dx_out.at[m].set(dx_j.astype(jnp.float32)),
                    dx_out)
                out = (x_buf, g_buf, acts, dstage, dlast, loss_acc,
                       dx_out)
                return (out, zeros_mb, dx_j.astype(jnp.float32))

            carry_in = (x_buf, g_buf, acts, dstage, dlast, loss_acc,
                        dx_out)
            carry_out, fwd_send, bwd_send = jax.lax.switch(
                op, (idle_branch, fwd_branch, bwd_branch), carry_in)
            (x_buf, g_buf, acts, dstage, dlast, loss_acc,
             dx_out) = carry_out

            # Unconditional ring exchanges; receive tables say where
            # (and whether) the arriving payload lands.
            fwd_recv = jax.lax.ppermute(fwd_send, axis_name, fwd_perm)
            bwd_recv = jax.lax.ppermute(bwd_send, axis_name, bwd_perm)
            xc, xm = rf_c[t, s], rf_m[t, s] % XW
            x_buf = x_buf.at[xc, xm].set(
                jnp.where(rf_v[t, s] == 1, fwd_recv, x_buf[xc, xm]))
            gc, gm = rb_c[t, s], rb_m[t, s] % GW
            g_buf = g_buf.at[gc, gm].set(
                jnp.where(rb_v[t, s] == 1, bwd_recv, g_buf[gc, gm]))
            return (x_buf, g_buf, acts, dstage, dlast, loss_acc,
                    dx_out), None

        carry0 = (
            jnp.zeros((V, XW, mb, *x_local.shape[1:]), x_local.dtype),
            jnp.zeros((V, GW, mb, *x_local.shape[1:]), jnp.float32),
            jnp.zeros((V, AW, mb, *x_local.shape[1:]), x_local.dtype),
            jax.tree_util.tree_map(jnp.zeros_like, params_v),
            jax.tree_util.tree_map(jnp.zeros_like, last_local),
            jnp.float32(0.0),
            jnp.zeros((M, mb, *x_local.shape[1:]), jnp.float32),
        )
        (_, _, _, dstage, dlast, loss_acc, dx_out), _ = jax.lax.scan(
            tick, carry0, jnp.arange(T))
        s_idx = jax.lax.axis_index(axis_name)
        head_mask = (s_idx == S - 1)
        loss = jax.lax.psum(
            jnp.where(head_mask, loss_acc, 0.0), axis_name) / M
        loss = jax.lax.pmean(loss, batch_axes)
        dlast = jax.tree_util.tree_map(
            lambda d: jax.lax.pmean(
                jax.lax.psum(
                    jnp.where(head_mask, d, jnp.zeros_like(d)),
                    axis_name) / M, batch_axes), dlast)
        dstage = jax.tree_util.tree_map(
            lambda d: jax.lax.pmean(d / M, batch_axes)[None], dstage)
        dx = jax.lax.psum(
            jnp.where(s_idx == 0, dx_out, jnp.zeros_like(dx_out)),
            axis_name).reshape(x_local.shape) / M
        return loss, dstage, dlast, dx

    data_spec = P(batch_axes, *([None] * (x.ndim - 1)))
    tgt_spec = P(batch_axes, *([None] * (targets.ndim - 1)))
    param_spec = jax.tree_util.tree_map(
        lambda p: P(axis_name, *([None] * (p.ndim - 1))), chunk_params)
    last_spec = jax.tree_util.tree_map(lambda p: P(), last_params)
    fn = shard_map(
        per_device, mesh=mesh,
        in_specs=(param_spec, data_spec, tgt_spec, last_spec),
        out_specs=(P(), param_spec, last_spec, data_spec),
        check_vma=False)
    return fn(chunk_params, x, targets, last_params)
