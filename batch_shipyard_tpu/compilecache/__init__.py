"""Warm-start compilation: persistent compile cache, AOT precompile,
and pool-wide cache seeding.

Compile time is a first-class badput category in the ML Productivity
Goodput decomposition (goodput/accounting.py, arxiv 2502.06982), and
on real pods it is minutes per task multiplied by pool width and
restart count. This package removes it three ways:

  * **manager** — configure JAX's persistent XLA compilation cache
    (``jax_compilation_cache_dir`` + entry-size/compile-time knobs),
    compute a stable *cache identity key* (jax/jaxlib versions, device
    kind, topology, model-config digest), and measure hit/miss/
    saved-seconds by diffing cache-dir contents around a compile so
    goodput can report ``compile_saved_seconds`` honestly.
  * **aot** — opt-in ``--aot-precompile``: ``jit(...).lower(...)
    .compile()`` the train step / serving prefill+decode functions
    against ``jax.ShapeDtypeStruct`` abstract inputs, so compilation
    overlaps data-pipeline startup instead of blocking the first step.
  * **seeding** — the node agent exports the cache dir as a tar
    artifact to the state store after a task (lease-guarded, one
    uploader) and seeds it before the next — first node compiles, the
    other N-1 and every restart hit warm (the image-prefetch pattern,
    agent/cascade.py).

Surfacing: ``shipyard pool cache stats|seed|prune`` (cli/main.py)
and ``goodput_compile_saved_seconds`` gauges (monitor/heimdall.py). See
docs/29-compile-cache.md.
"""

from batch_shipyard_tpu.compilecache import aot  # noqa: F401
from batch_shipyard_tpu.compilecache import seeding  # noqa: F401
from batch_shipyard_tpu.compilecache.manager import (  # noqa: F401
    CACHE_DIR_ENV, CompileCacheManager, add_compile_cache_args,
    config_digest, current, enable, enable_from_args, identity_key,
    tracked)
