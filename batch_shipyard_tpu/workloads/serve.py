"""Serving workload: HTTP front end over the continuous-batching
engine, with an optional built-in Poisson load benchmark.

Recipe command (Serving-ContinuousBatching):
    python -m batch_shipyard_tpu.workloads.serve \
        --num-slots 8 --max-decode-len 512 \
        --loadgen 64 --rate 16 --report latency_report.json

Without --loadgen the server runs until terminated (a long-lived
serving task); with it, the benchmark runs against the in-process
server, writes the latency-histogram JSON report, prints it as the
final stdout line, and exits nonzero if any request failed.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import sys

import jax
import jax.numpy as jnp

from batch_shipyard_tpu import compilecache
from batch_shipyard_tpu.models import inference as inf
from batch_shipyard_tpu.models import serving
from batch_shipyard_tpu.models import transformer as tfm
from batch_shipyard_tpu.models.server import ServingFrontEnd
from batch_shipyard_tpu.ops import paged_attention
from batch_shipyard_tpu.workloads import distributed


def warm_engine(args, engine: serving.ContinuousBatcher) -> None:
    """Warm one engine before its front end takes traffic: every
    prefill bucket via throwaway requests, or — with --aot-precompile
    and the persistent cache enabled — from abstract shapes alone, so
    no request is burned and restarts deserialize instead of
    compiling. AOT executables are discarded (their value IS the
    persistent cache they populate), so without an enabled cache the
    flag would leave the engine cold AND double-compile — fall back
    to the request-driven warm-up instead."""
    if args.aot_precompile and compilecache.current() is not None:
        engine.precompile()
    else:
        engine.warmup()


def build_config(args) -> tfm.TransformerConfig:
    return tfm.TransformerConfig(
        vocab_size=args.vocab, d_model=args.d_model,
        n_layers=args.n_layers, n_heads=args.n_heads,
        d_head=args.d_model // args.n_heads, d_ff=args.d_ff,
        max_seq_len=args.max_decode_len, dtype=jnp.bfloat16,
        kv_cache_dtype=args.kv_cache_dtype)


def build_params(args, config: tfm.TransformerConfig):
    """Init (or checkpoint-restore) ONE param tree — fleet mode
    shares it across every replica engine rather than paying the
    init/restore and a full weight copy per replica."""
    model = tfm.TransformerLM(config)
    params = model.init(
        jax.random.PRNGKey(args.seed),
        jnp.zeros((1, 8), jnp.int32))["params"]
    if args.checkpoint_dir:
        # Serve trained weights (train_transformer --checkpoint-dir
        # artifacts); dims must match the model args.
        from batch_shipyard_tpu.workloads import checkpoint
        restored = checkpoint.restore_params(args.checkpoint_dir)
        if restored is None:
            raise SystemExit(
                f"no checkpoint found in {args.checkpoint_dir}")
        restored_params, step = restored
        import jax.tree_util as jtu
        want = jtu.tree_structure(params)
        got = jtu.tree_structure(restored_params)
        if want != got:
            raise SystemExit(
                "checkpoint params do not match the model "
                "architecture flags (tree structure differs)")
        mismatched = [
            f"{jtu.keystr(path)}: {tuple(t.shape)} != "
            f"{tuple(r.shape)}"
            for (path, t), (_path2, r) in zip(
                jtu.tree_flatten_with_path(params)[0],
                jtu.tree_flatten_with_path(restored_params)[0])
            if tuple(t.shape) != tuple(r.shape)]
        if mismatched:
            raise SystemExit(
                "checkpoint params do not match the model "
                "architecture flags (shape mismatch): "
                + "; ".join(mismatched[:4]))
        params = jax.tree_util.tree_map(
            lambda t, r: jnp.asarray(r, t.dtype), params,
            restored_params)
        print(f"serving checkpoint step {step} from "
              f"{args.checkpoint_dir}", flush=True)
    return params


def build_draft(args) -> serving.SpeculativeConfig:
    """Draft model spec for --speculative: a small dense-cache
    transformer sharing the target's vocab (random init unless
    --draft-checkpoint-dir points at trained draft weights — a random
    draft exercises the worst case: near-zero acceptance, every round
    falls back to the target's correction token)."""
    draft_config = tfm.TransformerConfig(
        vocab_size=args.vocab, d_model=args.draft_d_model,
        n_layers=args.draft_n_layers, n_heads=args.n_heads,
        d_head=args.draft_d_model // args.n_heads,
        d_ff=args.draft_d_ff or args.draft_d_model * 3,
        max_seq_len=args.max_decode_len, dtype=jnp.bfloat16,
        kv_cache_dtype=args.kv_cache_dtype)
    draft_args = argparse.Namespace(**vars(args))
    draft_args.seed = args.seed + 7
    draft_args.checkpoint_dir = args.draft_checkpoint_dir
    draft_params = build_params(draft_args, draft_config)
    return serving.SpeculativeConfig(draft_config, draft_params,
                                     gamma=args.gamma)


def build_slo(args):
    """Resolve the serving SLO configuration (config/settings.py
    serving_slo_settings): --slo-config default -> the built-in class
    table; --slo-config PATH -> a JSON config mapping with a
    serving.slo section; neither -> SLO scheduling off (requests pass
    through untargeted). CLI --shed-grace-ms / --tpot-stall-factor
    override the parsed values."""
    from batch_shipyard_tpu.config.settings import serving_slo_settings
    if not args.slo_config:
        return None
    if args.slo_config == "default":
        slo = serving_slo_settings(None)
    else:
        with open(args.slo_config, encoding="utf-8") as fh:
            slo = serving_slo_settings(json.load(fh))
    if args.shed_grace_ms is not None:
        slo = dataclasses.replace(slo,
                                  shed_grace_ms=args.shed_grace_ms)
    if args.tpot_stall_factor is not None:
        slo = dataclasses.replace(
            slo, tpot_stall_factor=args.tpot_stall_factor)
    return slo


def build_engine(args, config=None, params=None,
                 speculative=None, slo=None,
                 device=None) -> serving.ContinuousBatcher:
    if config is None:
        config = build_config(args)
    if params is None:
        params = build_params(args, config)
    if speculative is None and args.speculative:
        speculative = build_draft(args)
    return serving.ContinuousBatcher(
        config, params, num_slots=args.num_slots,
        max_decode_len=args.max_decode_len,
        sampling=inf.SamplingConfig(temperature=args.temperature,
                                    top_k=args.top_k),
        seed=args.seed,
        kv_page_size=args.kv_page_size,
        kv_num_pages=args.kv_num_pages,
        overcommit=args.overcommit,
        prefill_chunk=args.prefill_chunk,
        prefix_cache=not args.no_prefix_cache,
        slo_shed_grace_ms=slo.shed_grace_ms if slo else None,
        tpot_stall_factor=(slo.tpot_stall_factor if slo else 4.0),
        speculative=speculative, device=device)


def paged_decode_impl(config: tfm.TransformerConfig) -> str:
    """What the engine's decode attention runs (one token a slot, a
    verify block of 1 + mtp_modules where the model carries its own
    drafter, or a whole block where it generates by diffusion over
    blocks), as the dispatch itself decides it
    (ops/paged_attention.paged_decode_road) from the pool's grouping,
    each attention layer's window, the pages' type and the query
    positions a slot: one name, or one a kind of layer joined by "+"
    where full and window layers take different ones."""
    return "+".join(sorted({
        paged_attention.paged_decode_road(
            config.paged_attention_impl,
            grouped=config.kv_heads != config.n_heads, window=window,
            int8=config.kv_cache_dtype == "int8",
            positions=config.block_diffusion.block
            if config.block_diffusion else 1 + config.mtp_modules)
        for window in tfm.attention_windows(config)}))


def build_parser() -> argparse.ArgumentParser:
    parser = argparse.ArgumentParser()
    parser.add_argument("--d-model", type=int, default=256)
    parser.add_argument("--n-layers", type=int, default=4)
    parser.add_argument("--n-heads", type=int, default=4)
    parser.add_argument("--d-ff", type=int, default=1024)
    parser.add_argument("--vocab", type=int, default=32000)
    parser.add_argument("--num-slots", type=int, default=8)
    parser.add_argument("--max-decode-len", type=int, default=512)
    parser.add_argument("--temperature", type=float, default=0.0)
    parser.add_argument("--top-k", type=int, default=0)
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--kv-page-size", type=int, default=None)
    parser.add_argument("--kv-cache-dtype", default=None,
                        choices=["int8"],
                        help="Quantize the decode KV cache (dense "
                        "or paged pool) to int8: half the HBM per "
                        "token -> 2x slots/context")
    parser.add_argument("--kv-num-pages", type=int, default=None)
    parser.add_argument("--prefill-chunk", type=int, default=None,
                        help="Chunked prefill segment length (bounds "
                        "long-prompt prefill memory; power of two)")
    parser.add_argument("--overcommit", action="store_true")
    parser.add_argument("--no-prefix-cache", action="store_true",
                        help="Disable cross-request prefix/KV-cache "
                        "reuse in the paged pool (the control arm of "
                        "tests/test_prefix_cache.py)")
    parser.add_argument("--slo-config", default=None,
                        help="SLO scheduling config: 'default' for "
                        "the built-in class table, or a JSON config "
                        "file with a serving.slo section "
                        "(config/settings.py serving_slo_settings)")
    parser.add_argument("--shed-grace-ms", type=float, default=None,
                        help="Arm overload shedding: queued requests "
                        "past their TTFT deadline by this grace are "
                        "rejected 503 (requires --slo-config)")
    parser.add_argument("--tpot-stall-factor", type=float,
                        default=None,
                        help="Admission defers prefills that would "
                        "stall active decodes past this multiple of "
                        "the tightest TPOT target")
    # Speculative decoding inside the engine: a small draft model
    # proposes gamma tokens per slot per step; ONE batched target
    # forward verifies every slot's block; commits are per-slot
    # ragged. Greedy-exact — requires --temperature 0.
    parser.add_argument("--speculative", action="store_true",
                        help="Enable engine-integrated speculative "
                        "decoding (draft/verify per engine step; "
                        "greedy-exact)")
    parser.add_argument("--gamma", type=int, default=4,
                        help="Draft tokens proposed per slot per "
                        "engine step")
    parser.add_argument("--draft-d-model", type=int, default=256)
    parser.add_argument("--draft-n-layers", type=int, default=2)
    parser.add_argument("--draft-d-ff", type=int, default=None,
                        help="Draft MLP width (default 3x "
                        "draft-d-model)")
    parser.add_argument("--draft-checkpoint-dir", default=None,
                        help="Serve draft params from an Orbax "
                        "checkpoint (random init otherwise — the "
                        "worst-case acceptance demo)")
    parser.add_argument("--host", default="127.0.0.1")
    parser.add_argument("--port", type=int, default=8900)
    # Front-door hardening + drain (37-serving-resilience.md).
    parser.add_argument("--max-inflight", type=int, default=None,
                        help="Cap accepted-but-unfinished requests "
                        "per replica; excess gets 429 back-pressure "
                        "(resumes are exempt)")
    parser.add_argument("--io-timeout-s", type=float, default=None,
                        help="Per-connection socket read/write "
                        "deadline (a wedged client cannot pin a "
                        "handler thread)")
    parser.add_argument("--drain-grace-s", type=float, default=30.0,
                        help="On a preempt/evict notice, let "
                        "in-flight decodes finish for this long "
                        "before abandoning them to sibling resume")
    # Benchmark mode
    parser.add_argument("--loadgen", type=int, default=0,
                        help="Run N benchmark requests then exit")
    parser.add_argument("--rate", type=float, default=8.0,
                        help="Arrival rate (req/s; diurnal peak)")
    parser.add_argument("--arrival", choices=("poisson", "diurnal"),
                        default="poisson",
                        help="Loadgen arrival process (diurnal "
                        "replays the fleet simulator's day/night "
                        "curve)")
    parser.add_argument("--shared-prefix-groups", type=int,
                        default=0,
                        help="Loadgen shared prompt-prefix groups "
                        "(exercises the prefix cache and affinity "
                        "routing)")
    parser.add_argument("--shared-prefix-len", type=int, default=0)
    parser.add_argument("--prompt-len", type=int, nargs=2,
                        default=(4, 32), metavar=("MIN", "MAX"))
    parser.add_argument("--gen-tokens", type=int, nargs=2,
                        default=(8, 32), metavar=("MIN", "MAX"))
    parser.add_argument("--report", default="latency_report.json")
    parser.add_argument("--checkpoint-dir", default=None,
                        help="Serve params from the latest Orbax "
                             "checkpoint (train_transformer output)")
    parser.add_argument("--replicas", type=int, default=1,
                        help="Run N replica engines behind the "
                             "queue-depth-aware fleet router "
                             "(models/router.py); the router binds "
                             "--host/--port")
    compilecache.add_compile_cache_args(parser)
    return parser


def main(argv=None) -> int:
    args = build_parser().parse_args(argv)
    # Persistent compile cache before any engine construction: the
    # engine __init__ compiles nothing, but warm-up / precompile and
    # the first requests do, and pool restarts should hit warm.
    compilecache.enable_from_args(
        args, model_digest=compilecache.config_digest(
            build_config(args)))

    fronts = []
    router = None
    slo = build_slo(args)
    slo_classes = slo.class_targets() if slo else None
    if args.replicas > 1:
        # Fleet mode: replicas bind ephemeral loopback ports; the
        # router is the public surface (same wire API).
        from batch_shipyard_tpu.models.router import ServingRouter
        config = build_config(args)
        params = build_params(args, config)
        # Like the target params, the draft tree is built once and
        # shared across every replica engine.
        speculative = build_draft(args) if args.speculative else None
        # One engine per device, round-robin: each gets its own copy
        # of the weights and its own KV pool on its own chip (more
        # replicas than devices share).
        devices = jax.devices()
        engines = [build_engine(args, config, params, speculative,
                                slo=slo,
                                device=devices[i % len(devices)])
                   for i in range(args.replicas)]
        # Warm every replica BEFORE it starts taking traffic (jit
        # compiles recorded as engine warm-up goodput; must run before
        # the front's engine thread owns the stepping). Same-config
        # replicas share the module-level jits, so replica 1 pays and
        # the rest reuse.
        for e in engines:
            warm_engine(args, e)
        fronts = [ServingFrontEnd(e, port=0,
                                  slo_classes=slo_classes,
                                  max_inflight=args.max_inflight,
                                  io_timeout_s=args.io_timeout_s,
                                  drain_grace_s=args.drain_grace_s
                                  ).start()
                  for e in engines]
        router = ServingRouter([f.url for f in fronts],
                               host=args.host,
                               port=args.port).start()
        url = router.url
        print(f"fleet router on {url} over {len(fronts)} "
              f"replica(s)", flush=True)
    else:
        engine = build_engine(args, slo=slo)
        warm_engine(args, engine)
        fronts = [ServingFrontEnd(engine, host=args.host,
                                  port=args.port,
                                  slo_classes=slo_classes,
                                  max_inflight=args.max_inflight,
                                  io_timeout_s=args.io_timeout_s,
                                  drain_grace_s=args.drain_grace_s
                                  ).start()]
        url = fronts[0].url
        print(f"serving on {url}", flush=True)
    # A preempt/evict notice (agent/preemption.py) drains every
    # replica: no new admissions, in-flight decodes finish within
    # the grace, the router resumes the rest on siblings.
    for front in fronts:
        front.arm_preempt_drain(grace_s=args.drain_grace_s)

    def _shutdown():
        if router is not None:
            router.shutdown()
        for f in fronts:
            f.shutdown()

    if not args.loadgen:
        try:
            fronts[0]._http_thread.join()
        except KeyboardInterrupt:
            pass
        finally:
            _shutdown()
        return 0
    from batch_shipyard_tpu.models.loadgen import run_load
    # Engines were warmed before their fronts started, so jit
    # compilation never pollutes TTFT; one tiny request per front
    # still warms the HTTP dispatch path itself.
    for front in fronts:
        front.generate({"prompt": [1, 2, 3], "max_new_tokens": 2})
    report = run_load(
        url, args.loadgen, rate_hz=args.rate,
        prompt_len=tuple(args.prompt_len),
        max_new_tokens=tuple(args.gen_tokens),
        vocab_size=args.vocab, seed=args.seed,
        arrival=args.arrival,
        shared_prefix_groups=args.shared_prefix_groups,
        shared_prefix_len=args.shared_prefix_len,
        slo_classes=slo_classes)
    report["device"] = distributed.device_info()
    report["replica_devices"] = [
        str(f.engine.device or jax.devices()[0]) for f in fronts]
    report["hbm_in_use_mib"] = distributed.device_memory_mib()
    if args.kv_page_size:
        report["paged_decode_impl"] = paged_decode_impl(
            fronts[0].engine.config)
    # the model's own drafter: multi-token-prediction modules verified
    # in every decode step (0: one token a slot a step)
    report["mtp_modules"] = fronts[0].engine.config.mtp_modules
    # generation by diffusion over blocks: the block a step denoises
    # or commits a slot (0: one token a slot a step)
    report["diffusion_block"] = fronts[0].engine.block
    if router is not None:
        report["router"] = router.stats()
    prefix = [f.engine.prefix_stats() for f in fronts]
    if any(prefix):
        hits = sum(p["hit_tokens"] for p in prefix if p)
        total = sum(p["total_prompt_tokens"] for p in prefix if p)
        report["prefix_cache"] = {
            "hit_tokens": hits,
            "total_prompt_tokens": total,
            "hit_rate": hits / total if total else 0.0,
        }
    if args.speculative:
        spec = [f.engine.spec_stats() for f in fronts]
        proposed = sum(s["proposed"] for s in spec)
        accepted = sum(s["accepted"] for s in spec)
        report["speculative"] = {
            "gamma": args.gamma,
            "proposed": proposed,
            "accepted": accepted,
            "acceptance_rate": (accepted / proposed
                                if proposed else 0.0),
        }
    _shutdown()
    with open(args.report, "w", encoding="utf-8") as fh:
        json.dump(report, fh, indent=2)
    print(json.dumps(report), flush=True)
    return 1 if report["failed"] else 0


if __name__ == "__main__":
    sys.exit(main())
