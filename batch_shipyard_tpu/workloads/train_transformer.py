"""Transformer LM training payload: long-context flagship recipe.

Supports dp/fsdp/sp/tp over the global device mesh; with --sp > 1 the
attention runs as ring attention over the ICI ring (exact, memory
O(T/sp) per device) — the long-context mechanism SURVEY.md section 5.7
calls net-new design space.

Usage (recipe command):
    python -m batch_shipyard_tpu.workloads.train_transformer \
        --seq-len 8192 --sp 4 --tp 2 --steps 20
"""

from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from batch_shipyard_tpu import compilecache
from batch_shipyard_tpu.agent import preemption
from batch_shipyard_tpu.goodput import events as goodput_events
from batch_shipyard_tpu.parallel import mesh as mesh_mod
from batch_shipyard_tpu.parallel import train as train_mod
from batch_shipyard_tpu.workloads import checkpoint
from batch_shipyard_tpu.workloads import distributed


def build_parser() -> argparse.ArgumentParser:
    parser = argparse.ArgumentParser()
    parser.add_argument("--d-model", type=int, default=1024)
    parser.add_argument("--n-layers", type=int, default=12)
    parser.add_argument("--n-heads", type=int, default=16)
    parser.add_argument("--d-ff", type=int, default=2816)
    parser.add_argument("--vocab", type=int, default=32000)
    parser.add_argument("--seq-len", type=int, default=2048)
    parser.add_argument("--batch", type=int, default=8)
    parser.add_argument("--steps", type=int, default=20)
    parser.add_argument("--warmup", type=int, default=2)
    parser.add_argument("--tp", type=int, default=1)
    parser.add_argument("--sp", type=int, default=1)
    parser.add_argument("--fsdp", type=int, default=1)
    parser.add_argument("--ep", type=int, default=1,
                        help="expert-parallel axis (requires --moe-"
                             "experts divisible by ep)")
    parser.add_argument("--moe-experts", type=int, default=0,
                        help="replace every moe-every'th MLP with N "
                             "routed experts (0 = dense)")
    parser.add_argument("--moe-every", type=int, default=2)
    parser.add_argument("--int8", action="store_true",
                        help="int8 MXU matmuls for projections/MLP "
                             "(QAT straight-through backward)")
    parser.add_argument("--no-remat", action="store_true")
    checkpoint.add_checkpoint_args(parser)
    compilecache.add_compile_cache_args(parser)
    return parser


def build(args):
    """(mesh, config, harness) for the parsed flags: the mesh over
    every visible device, the model config matched to it, the
    persistent compile cache enabled, the train step built."""
    n_dev = jax.device_count()
    mesh = mesh_mod.make_mesh(mesh_mod.auto_axis_sizes(
        n_dev, tp=args.tp, sp=args.sp, fsdp=args.fsdp, ep=args.ep))
    moe = None
    if args.moe_experts:
        from batch_shipyard_tpu.models.moe import MoEConfig
        moe = MoEConfig(num_experts=args.moe_experts,
                        d_model=args.d_model, d_ff=args.d_ff,
                        dtype=jnp.bfloat16)
    config = train_mod.make_transformer_config(
        mesh, vocab_size=args.vocab, d_model=args.d_model,
        n_layers=args.n_layers, n_heads=args.n_heads,
        d_head=args.d_model // args.n_heads, d_ff=args.d_ff,
        max_seq_len=args.seq_len, dtype=jnp.bfloat16,
        moe=moe, moe_every=args.moe_every,
        quantize_matmuls=args.int8,
        remat=not args.no_remat)
    # Persistent compile cache: identity-keyed to this mesh + model
    # config so pool-wide seeding never ships entries that can only
    # miss; must be enabled BEFORE the first jit (the harness build's
    # init compile).
    compilecache.enable_from_args(
        args, mesh_shape=dict(mesh.shape),
        model_digest=compilecache.config_digest(config))
    harness = train_mod.build_transformer_train(
        mesh, config, batch_size=args.batch, seq_len=args.seq_len)
    return mesh, config, harness


def resolved_kernels(args, mesh) -> dict:
    """Which implementation each dispatch on the step resolves to for
    these flags on this backend — so a give-way (an untileable
    sequence length dropping flash for blockwise, a lane-misaligned
    d_model dropping the Pallas loss) is printed, not silent."""
    from batch_shipyard_tpu.ops import attention as attn_ops
    from batch_shipyard_tpu.ops import chunked_loss, ring_attention
    sp = mesh.shape["sp"]
    t_local = args.seq_len // sp
    attention = (
        f"ring:{ring_attention.resolve_ring_impl('auto', t_local)}"
        if sp > 1 else
        attn_ops.resolve_attention_impl(None, t_local, t_local))
    return {"attention": attention,
            "loss": chunked_loss.resolve_xent_impl("auto",
                                                   args.d_model)}


def synthetic_batch(args, harness):
    """One seeded random token batch, placed as the step expects."""
    from batch_shipyard_tpu.data import loader
    rng = np.random.RandomState(jax.process_index())
    local_batch = args.batch // jax.process_count()
    return loader.place_global({
        "tokens": np.asarray(
            rng.randint(0, args.vocab, (local_batch, args.seq_len)),
            np.int32),
        "targets": np.asarray(
            rng.randint(0, args.vocab, (local_batch, args.seq_len)),
            np.int32),
    }, harness.batch_sharding)


def main(argv=None) -> int:
    args = build_parser().parse_args(argv)
    ctx = distributed.setup()
    mesh, _config, harness = build(args)
    # --aot-precompile: the step compiles on a background thread while
    # the host builds the data pipeline below; joined before warm-up.
    join_aot = (compilecache.aot.precompile_async(harness)
                if args.aot_precompile else None)
    batch = synthetic_batch(args, harness)
    params, opt_state = harness.params, harness.opt_state
    ckpt = checkpoint.TrainCheckpointer.from_args(args)
    params, opt_state, start_step = ckpt.restore(params, opt_state)
    if start_step:
        distributed.log(ctx, f"resumed from step {start_step}")
    if join_aot is not None:
        join_aot()
    # Goodput program phases: the warm-up loop is jit compile time
    # (compile badput, stamped with the cache's hit/saved detail);
    # the measured loop is the productive step window, stamped with
    # step + token counters so the accounting engine can price
    # preemption-recovery rework after a restore.
    with goodput_events.phase(goodput_events.PROGRAM_COMPILE,
                              what="jit_warmup",
                              steps=args.warmup) as warm_attrs, \
            compilecache.tracked(warm_attrs, "transformer_warmup"):
        for _ in range(args.warmup):
            params, opt_state, metrics = harness.step(params,
                                                      opt_state, batch)
            float(metrics["loss"])  # hard sync
    start = time.perf_counter()
    # Step windows are flushed INCREMENTALLY at every checkpoint
    # boundary (not one span over the whole loop): a window recorded
    # only on clean exit would vanish with a preempted attempt, and
    # the accounting engine's replayed-step rework pricing needs the
    # crashed attempt's completed progress to survive on disk.
    window = {"step": start_step, "time": time.time()}

    def _flush_window(end_step: int) -> None:
        if end_step > window["step"]:
            goodput_events.record(
                goodput_events.PROGRAM_STEP_WINDOW,
                window["time"], time.time(),
                step_start=window["step"], step_end=end_step,
                tokens=args.batch * args.seq_len
                * (end_step - window["step"]))
        window["step"] = end_step
        window["time"] = time.time()

    # On-demand profiling (trace/profiling.py): `shipyard jobs
    # profile` drops a request file the agent forwards; the next N
    # steps run under jax.profiler.trace. O(one stat) per step while
    # disarmed.
    from batch_shipyard_tpu.trace.profiling import StepProfiler
    profiler = StepProfiler()
    for step_num in range(start_step, start_step + args.steps):
        profiler.tick(step_num)
        params, opt_state, metrics = harness.step(params,
                                                  opt_state, batch)
        # Cooperative preemption: drain at this step boundary, force
        # a COMMITTED checkpoint, exit with the distinct preempted
        # status — the agent requeues at full budget and the rerun
        # resumes exactly here (zero lost steps beyond the barrier).
        if ckpt.maybe_preempt(step_num + 1, params, opt_state):
            _flush_window(step_num + 1)
            profiler.close()
            return preemption.EXIT_PREEMPTED
        if ckpt.due(step_num + 1):
            _flush_window(step_num + 1)
            # Sync: pays the whole persist here (checkpoint badput).
            # --async-checkpoint: pays only the snapshot; the persist
            # overlaps the next steps' windows.
            ckpt.step_save(step_num + 1, params, opt_state)
            window["time"] = time.time()  # save span is not steps
    loss = float(metrics["loss"])  # hard sync before the final flush
    profiler.close()
    _flush_window(start_step + args.steps)
    elapsed = time.perf_counter() - start
    # Exit save dedups against the loop's cadenced save of the same
    # step, then drains any in-flight async persist.
    ckpt.finalize(start_step + args.steps, params, opt_state)
    tokens_per_sec = args.batch * args.seq_len * args.steps / elapsed
    device = distributed.device_info()
    distributed.log(ctx, (
        f"transformer: platform={device['platform']} "
        f"device_kind={device['kind']!r} devices={device['count']} "
        f"mesh={dict(mesh.shape)} "
        f"kernels={resolved_kernels(args, mesh)} "
        f"{tokens_per_sec:.0f} tok/s, loss={loss:.4f}, "
        f"{elapsed / args.steps * 1000:.1f} ms/step, "
        f"hbm_in_use_mib={distributed.device_memory_mib()}"))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
