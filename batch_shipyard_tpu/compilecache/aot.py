"""AOT precompile helpers: compile before the data shows up.

``jit`` compiles lazily on first call, so the first train step (or
the first real serving request of a new length bucket) pays the whole
XLA compile on the critical path. ``--aot-precompile`` flips that:
``jit(...).lower(abstract...).compile()`` runs against
``jax.ShapeDtypeStruct`` inputs — no data, no execution — so the
compile overlaps data-pipeline/loader startup (train) or happens
before the front end accepts traffic (serving), and with the
persistent cache enabled the result is durable across restarts.

Train harnesses (parallel/train.py) expose ``TrainHarness.precompile``
which swaps the AOT executable into the step hot path — the first
step then runs the SAME compiled program as the steady state, so
there is no cold-compile spike at all. ``precompile_async`` runs that
in a background thread under a goodput compile phase and returns a
join callable the workload invokes before its warm-up loop.
"""

from __future__ import annotations

import threading
from typing import Any, Callable, Optional

from batch_shipyard_tpu.compilecache import manager
from batch_shipyard_tpu.utils import util

logger = util.get_logger(__name__)


def abstractify(tree: Any) -> Any:
    """Concrete array tree -> ShapeDtypeStruct tree, for lowering
    without touching data. A COMMITTED array's sharding is kept; an
    uncommitted one (made on the default device and never placed)
    gives none, as ``jit`` treats it when it is called with the
    array: a sharding named for it here would lower to another
    program than the real call's, under another cache key."""
    import jax

    def leaf(x):
        if hasattr(x, "shape") and hasattr(x, "dtype"):
            sharding = (getattr(x, "sharding", None)
                        if getattr(x, "committed", True) else None)
            return jax.ShapeDtypeStruct(x.shape, x.dtype,
                                        sharding=sharding)
        return x

    return jax.tree_util.tree_map(leaf, tree)


def precompile_async(harness,
                     label: str = "train_step_aot"
                     ) -> Optional[Callable[[], None]]:
    """Start ``harness.precompile()`` on a background thread so the
    compile overlaps the caller's data/loader setup; the returned
    join callable blocks until it finishes. Failures degrade to the
    normal jit-on-first-step path (logged, never raised) — AOT is an
    optimization, not a correctness surface. Returns None when the
    harness has no precompile path."""
    precompile = getattr(harness, "precompile", None)
    if precompile is None:
        return None
    from batch_shipyard_tpu.goodput import events as goodput_events

    from batch_shipyard_tpu.trace import spans as trace_spans

    def _run() -> None:
        try:
            with goodput_events.phase(
                    goodput_events.PROGRAM_COMPILE,
                    what="aot_precompile") as attrs, \
                    manager.tracked(attrs, label), \
                    trace_spans.phase(trace_spans.SPAN_COMPILE,
                                      what="aot_precompile",
                                      label=label):
                precompile()
        except Exception:  # noqa: BLE001 - jit path still works
            logger.warning("AOT precompile failed; falling back to "
                           "jit-on-first-step", exc_info=True)

    thread = threading.Thread(target=_run, daemon=True,
                              name="aot-precompile")
    thread.start()
    return thread.join
