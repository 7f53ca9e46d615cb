#!/usr/bin/env python3
"""Chip smoke: the quickest proof that the system still starts on the TPU.

Drives the two main paths once, in ONE process that owns the chip,
through the entry points a user calls, at the full width the repo
benchmarks (d_model 1024, 12 layers, 16 heads x 64, d_ff 2816, vocab
32000, bf16; random weights from a seed):

  serve  workloads/serve.py's own calls (build_config / build_params /
         build_engine / warm_engine, ServingFrontEnd, loadgen.run_load):
         8 slots, max_decode_len 512, paged KV (page 64), prefix cache
         on, greedy. A load of requests over HTTP must all complete,
         and one fixed prompt's tokens — served cold, then again
         through the prefix cache — must be the dense-cache reference
         model's own choices (models/inference.py, on the chip).
  train  workloads/train_transformer.py's own build (seq 2048, batch
         8): warm-up plus three steps, finite loss.

Nothing is caught and downgraded: any failure is a traceback and a
non-zero exit with no result line. Without a TPU backend (this
sandbox), or without the rest of the repo beside it, it exits non-zero.
On success the last two stdout lines are one JSON object each: the
summary of the run, {"ok": true, "device": {...}, ..., "claim": null},
then the result the driver reads, exactly
{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}
with the device as JAX reports it.

    python chip_smoke.py            # on the chip
    python chip_smoke.py --cpu-tiny # control-flow dry run, tiny widths
"""

from __future__ import annotations

import argparse
import json
import os
import pathlib
import sys
import time

REPO_ROOT = pathlib.Path(__file__).resolve().parent
if str(REPO_ROOT) not in sys.path:
    sys.path.insert(0, str(REPO_ROOT))

# The width train_transformer.py defaults to.
FULL = {"d_model": 1024, "n_layers": 12, "n_heads": 16, "d_ff": 2816,
        "vocab": 32000, "max_decode_len": 512, "page": 64,
        "seq_len": 2048, "batch": 8}
# --cpu-tiny: same control flow, sizes a CPU finishes in seconds.
TINY = {"d_model": 128, "n_layers": 2, "n_heads": 2, "d_ff": 256,
        "vocab": 512, "max_decode_len": 128, "page": 16,
        "seq_len": 128, "batch": 2}

# What each dispatch must resolve to on the chip: the Pallas kernels
# compiled by Mosaic — not the interpreter, not an XLA twin.
EXPECTED_IMPLS = {"attention": "flash", "paged_decode": "kernel",
                  "chunked_loss": "pallas"}


def cache_entries(root: str) -> int:
    """Compile-cache entries under ``root``: its own (a cache placed
    flat by JAX_COMPILATION_CACHE_DIR) plus every identity subdir's."""
    from batch_shipyard_tpu.compilecache import manager
    dirs = [root, *manager.list_identity_dirs(root).values()]
    return sum(len(manager.snapshot(d)) for d in dirs)


# One bfloat16 step, relative: 8 bits of precision.
BF16_STEP = 2.0 ** -7


def reference_judge(config, params, max_decode_len: int, prompt: list):
    """judge(tokens) -> dict: served ``tokens`` for ``prompt``, judged
    by the dense-cache reference model.

    Teacher-forced: the reference (decode-mode model, dense KV rows —
    models/inference.py) reads prompt + tokens in one pass and yields
    its own logits at every generated position. Each served token must
    sit within two bf16 steps of the reference's best logit there. A
    kernel that compiles but attends wrongly picks tokens whose
    reference logit is far below the max and fails; a near-tie that
    two correct bf16 implementations resolve differently does not.
    Also reports how far the tokens follow the reference's own greedy
    continuation."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from batch_shipyard_tpu.models import inference as inf

    run, model = inf.make_decoder(config, params, max_decode_len)

    @jax.jit
    def teacher_forced(params, seq):
        cache = inf.init_cache(model, params, 1)
        hidden, _ = model.apply(
            {"params": params, "cache": cache}, seq,
            return_hidden=True, mutable=["cache"])
        embedding = params["embed"]["embedding"].astype(jnp.float32)
        return jnp.dot(hidden[0, len(prompt) - 1:].astype(jnp.float32),
                       embedding.T)

    def judge(tokens: list) -> dict:
        greedy, _cache = run(jnp.asarray([prompt], jnp.int32),
                             len(tokens), jax.random.PRNGKey(0))
        greedy = [int(t) for t in np.asarray(greedy)[0, len(prompt):]]
        logits = np.asarray(teacher_forced(
            params, jnp.asarray([prompt + tokens[:-1]], jnp.int32)))
        assert logits.shape[0] == len(tokens)
        assert np.isfinite(logits).all()
        best = logits.max(axis=-1)
        gaps = best - logits[np.arange(len(tokens)), tokens]
        worst = int(np.argmax(gaps))
        tolerance = 2.0 * BF16_STEP * abs(float(best[worst]))
        if gaps[worst] > tolerance:
            raise AssertionError(
                f"served token {tokens[worst]} at position {worst} "
                f"scores {gaps[worst]:.4f} below the dense-cache "
                f"reference's best logit (tolerance {tolerance:.4f}): "
                f"served {tokens} vs reference greedy {greedy}")
        agree = next((i for i, (a, b) in enumerate(zip(tokens, greedy))
                      if a != b), len(tokens))
        return {"greedy_exact": tokens == greedy,
                "greedy_agrees_for": f"{agree}/{len(tokens)}",
                "max_logit_gap": round(float(gaps[worst]), 5)}

    return judge


def serve_leg(size: dict, on_chip: bool) -> dict:
    """workloads/serve.py:main's own sequence, with the reference
    check spliced in while the server is up."""
    from batch_shipyard_tpu import compilecache
    from batch_shipyard_tpu.compilecache import aot
    from batch_shipyard_tpu.models import serving
    from batch_shipyard_tpu.models.loadgen import post_generate, run_load
    from batch_shipyard_tpu.models.server import ServingFrontEnd
    from batch_shipyard_tpu.workloads import serve

    started = time.perf_counter()
    args = serve.build_parser().parse_args([
        "--d-model", str(size["d_model"]),
        "--n-layers", str(size["n_layers"]),
        "--n-heads", str(size["n_heads"]),
        "--d-ff", str(size["d_ff"]), "--vocab", str(size["vocab"]),
        "--num-slots", "8",
        "--max-decode-len", str(size["max_decode_len"]),
        "--kv-page-size", str(size["page"]),
        "--temperature", "0", "--port", "0"])
    compilecache.enable_from_args(
        args, model_digest=compilecache.config_digest(
            serve.build_config(args)))
    config = serve.build_config(args)
    params = serve.build_params(args, config)
    engine = serve.build_engine(args, config, params)
    assert engine.paged and engine.prefix_cache
    serve.warm_engine(args, engine)
    warm_seconds = time.perf_counter() - started

    if on_chip:
        # The compiled decode step itself (a cache hit by now) must
        # hold the Mosaic custom call.
        hlo = serving._decode_step.lower(
            engine.model, engine.sampling, aot.abstractify(engine.params),
            aot.abstractify(engine.cache),
            aot.abstractify(engine._tokens),
            aot.abstractify(engine._positions),
            aot.abstractify(engine._active),
            aot.abstractify(engine._key)).compile().as_text()
        assert "tpu_custom_call" in hlo, (
            "the paged decode step compiled without a Mosaic kernel")

    front = ServingFrontEnd(engine, host=args.host,
                            port=args.port).start()
    try:
        front.generate({"prompt": [1, 2, 3], "max_new_tokens": 2})
        page, limit = size["page"], size["max_decode_len"]
        report = run_load(
            front.url, 12, rate_hz=8.0,
            prompt_len=(8, limit // 2),
            max_new_tokens=(8, limit // 8),
            vocab_size=size["vocab"], seed=args.seed,
            shared_prefix_groups=2, shared_prefix_len=page)
        assert report["completed"] == 12 and report["failed"] == 0, \
            report
        # The fixed prompt: two full pages and a tail, generation
        # long enough to grow into a fresh page mid-decode.
        prompt = [(7 * i + 3) % size["vocab"]
                  for i in range(2 * page + page // 8)]
        payload = {"prompt": prompt, "max_new_tokens": page}
        hits_before = engine.prefix_stats()["hit_tokens"]
        cold = post_generate(front.url, payload)["tokens"]
        shared = post_generate(front.url, payload)["tokens"]
        prefix_hit = engine.prefix_stats()["hit_tokens"] - hits_before
        assert len(cold) == len(shared) == page
        assert prefix_hit == 2 * page, (
            f"second request reused {prefix_hit} prompt tokens, "
            f"expected {2 * page}")
    finally:
        front.shutdown()
    judge = reference_judge(config, params, limit, prompt)
    checks = {"cold": judge(cold), "prefix_shared": judge(shared)}
    return {
        "requests_completed": report["completed"] + 2,
        "failed": report["failed"],
        "generated_tokens": report["generated_tokens"] + 2 * page,
        "prefix_hit_tokens": engine.prefix_stats()["hit_tokens"],
        "reference": checks,
        "warm_seconds": round(warm_seconds, 1),
        "seconds": round(time.perf_counter() - started, 1),
    }


def train_leg(size: dict) -> dict:
    """workloads/train_transformer.py's own build, warm-up plus three
    steps."""
    import math
    from batch_shipyard_tpu.workloads import train_transformer

    started = time.perf_counter()
    args = train_transformer.build_parser().parse_args([
        "--d-model", str(size["d_model"]),
        "--n-layers", str(size["n_layers"]),
        "--n-heads", str(size["n_heads"]),
        "--d-ff", str(size["d_ff"]), "--vocab", str(size["vocab"]),
        "--seq-len", str(size["seq_len"]),
        "--batch", str(size["batch"]),
        "--steps", "3", "--warmup", "1"])
    mesh, _config, harness = train_transformer.build(args)
    batch = train_transformer.synthetic_batch(args, harness)
    params, opt_state = harness.params, harness.opt_state
    losses = []
    for _ in range(args.warmup + args.steps):
        params, opt_state, metrics = harness.step(params, opt_state,
                                                  batch)
        losses.append(float(metrics["loss"]))  # host read = hard sync
    assert all(math.isfinite(loss) for loss in losses), losses
    return {"mesh": {k: v for k, v in mesh.shape.items() if v > 1},
            "losses": [round(loss, 4) for loss in losses],
            "seconds": round(time.perf_counter() - started, 1)}


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument(
        "--cpu-tiny", action="store_true",
        help="control-flow dry run for the sandbox: tiny widths, any "
             "backend, TPU-only assertions off. Not a chip result.")
    opts = parser.parse_args(argv)
    started = time.perf_counter()

    import jax
    on_chip = jax.default_backend() == "tpu"
    if not on_chip and not opts.cpu_tiny:
        print(f"chip_smoke: no TPU — jax.default_backend() is "
              f"{jax.default_backend()!r}", file=sys.stderr)
        return 2
    from batch_shipyard_tpu.compilecache import manager
    from batch_shipyard_tpu.ops import attention as attn_ops
    from batch_shipyard_tpu.ops import chunked_loss
    from batch_shipyard_tpu.ops import paged_attention as paged_ops
    from batch_shipyard_tpu.workloads import distributed

    size = TINY if opts.cpu_tiny else FULL
    device = distributed.device_info()
    cache_root, placed_by_env = manager.resolve_root(
        os.environ.get(manager.CACHE_DIR_ENV))
    entries_before = cache_entries(cache_root)
    print(f"chip_smoke: jax {jax.__version__} device_kind="
          f"{device['kind']!r} devices={device['count']} "
          f"compile_cache={cache_root} ({entries_before} entries)",
          flush=True)

    impls = {
        "attention": attn_ops.resolve_attention_impl(
            None, size["seq_len"], size["seq_len"]),
        "paged_decode": paged_ops.resolve_paged_impl(None),
        "chunked_loss": chunked_loss.resolve_xent_impl(
            "auto", size["d_model"]),
    }
    print(f"chip_smoke: implementations {impls}", flush=True)
    if on_chip:
        assert impls == EXPECTED_IMPLS, impls

    serve = serve_leg(size, on_chip)
    print(f"chip_smoke: serve {serve}", flush=True)
    train = train_leg(size)
    print(f"chip_smoke: train {train}", flush=True)

    print(json.dumps({
        "ok": True,
        "device": device,
        "jax": jax.__version__,
        "size": "tiny (cpu dry run)" if opts.cpu_tiny else "full",
        "wall_seconds": round(time.perf_counter() - started, 1),
        "compile_cache": {
            "dir": cache_root,
            "placed_by_env": placed_by_env,
            "entries_before": entries_before,
            "entries_after": cache_entries(cache_root)},
        "implementations": impls,
        "serve": serve,
        "train": train,
        "claim": None,
    }), flush=True)
    # The result line: these keys and no others, last on stdout.
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
