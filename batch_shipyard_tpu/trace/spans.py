"""Span-kind registry + span recorders (store-backed and
process-local).

The twin of goodput/events.py, but identity-first: every span carries
(trace_id, span_id, parent_span_id) so export.py can rebuild the
causal chain of one submission. Two producer surfaces feed one log:

  * **Store-backed** (`emit` / `span` / `query`): components holding a
    StateStore handle — the jobs manager (submit span), the node agent
    (claim/backoff/requeue/rendezvous/run/cache-seed spans). Spans
    land in TABLE_TRACE partitioned by pool.
  * **Process-local** (`record` / `phase`): workload code inside a
    task subprocess appends JSONL to $SHIPYARD_TRACE_FILE; the agent
    ingests the file post-task with the task's identity attached
    (`ingest_local_spans`), exactly like the goodput recorder. The
    trace/parent ids default to the task context the agent exported
    ($SHIPYARD_TRACE_* — context.TraceContext.from_env), so program
    spans parent under the task's run span with zero plumbing in the
    workloads. With no sink configured the recorder is a no-op. Rows
    are buffered in memory and appended in batches (``flush``): a
    span after a quiet spell is written at once, a stream of them
    costs one file open per FLUSH_INTERVAL_S.

Span dict schema (what export.py consumes)::

    {"kind": str, "trace_id": str, "span_id": str,
     "parent_span_id": Optional[str], "start": float, "end": float,
     "pool_id"/"job_id"/"task_id"/"node_id": Optional[str],
     "attrs": {...}}

Every kind emitted anywhere must be declared here: the registry is
enforced by an AST scan in tests/test_names_consistency.py, so a
typo'd kind cannot silently produce spans the export drops. Emission
is best-effort by design — a failed trace write must never fail the
work being traced.
"""

from __future__ import annotations

import atexit
import contextlib
import json
import os
import threading
import time
import uuid
from typing import Any, Iterator, Optional

from batch_shipyard_tpu.state import names
from batch_shipyard_tpu.state.base import StateStore
from batch_shipyard_tpu.trace import context as trace_ctx
from batch_shipyard_tpu.utils import util

logger = util.get_logger(__name__)

# ----------------------------- span kinds ------------------------------

# Submission / scheduling (store-backed emitters)
SPAN_SUBMIT = "submit"                   # jobs add -> entities+queued
SPAN_QUEUE_WAIT = "queue_wait"           # submit/requeue -> claim
SPAN_CLAIM = "claim"                     # instantaneous claim marker
SPAN_BACKOFF_WAIT = "backoff_wait"       # retry supervisor delay
SPAN_REQUEUE = "requeue"                 # instantaneous requeue marker
SPAN_RENDEZVOUS = "gang_rendezvous"      # gang join -> full formation
SPAN_TASK_RUN = "task_run"               # task process start -> exit
SPAN_CACHE_SEED = "compile_cache_seed"   # pre-task pool-cache seed
SPAN_PREEMPT = "preempt"                 # preempt notice -> drained
                                         # exit (cooperative window)
SPAN_EVICT = "evict"                     # preempt notice -> hard
                                         # kill (the escalation
                                         # window a victim burned by
                                         # ignoring its notice)
SPAN_GANG_RESIZE = "gang_resize"         # instantaneous: broken gang
                                         # re-formed at a new size
SPAN_AGENT_RESTART = "agent_restart"     # crashed agent's last
#                                          heartbeat -> restarted
#                                          agent re-adopted the
#                                          still-running task (the
#                                          crash-restart adoption
#                                          recovery leg)
SPAN_GANG_MIGRATE = "gang_migrate"       # starved in source pool ->
                                         # re-targeted on the sibling
                                         # pool (one trace spans the
                                         # migration)

# Program phases (process-local emitters inside the task)
SPAN_COMPILE = "compile"                 # jit warm-up / AOT precompile
SPAN_CKPT_SNAPSHOT = "checkpoint_snapshot"   # step-boundary device_get
SPAN_CKPT_PERSIST = "checkpoint_persist"     # write-out (sync or
                                             # overlapped; attrs carry
                                             # overlapped=True/False)
SPAN_CKPT_RESTORE = "checkpoint_restore"
SPAN_PROFILE = "profile"                 # jax.profiler capture window

# Serving per-request spans (recorded by the front end)
SPAN_SERVE_REQUEST = "serve_request"     # admit -> completion (parent)
SPAN_SERVE_QUEUED = "serve_queued"       # submit -> engine admission
SPAN_SERVE_PREFILL = "serve_prefill"     # admission -> first token
SPAN_SERVE_DECODE = "serve_decode"       # first token -> last token;
                                         # speculative accept/rewind
                                         # counters annotated in attrs
# Serving per-step rows (recorded by the engine itself)
SPAN_SERVE_STEP = "serve_step"           # one ContinuousBatcher.step:
                                         # per-phase milliseconds, the
                                         # requests it admitted and the
                                         # engine's occupancy as it
                                         # began (docs/32-tracing.md)
SPAN_SERVE_STALL = "serve_stall"         # a launch whose result the
                                         # engine got seconds late, or
                                         # a step() call the host was
                                         # late in: what the host did
                                         # meanwhile and the last 64
                                         # launches (docs/32-tracing.md)

SPAN_KINDS = frozenset({
    SPAN_SUBMIT, SPAN_QUEUE_WAIT, SPAN_CLAIM, SPAN_BACKOFF_WAIT,
    SPAN_REQUEUE, SPAN_RENDEZVOUS, SPAN_TASK_RUN,
    SPAN_CACHE_SEED, SPAN_PREEMPT, SPAN_EVICT, SPAN_GANG_RESIZE,
    SPAN_GANG_MIGRATE, SPAN_AGENT_RESTART,
    SPAN_COMPILE, SPAN_CKPT_SNAPSHOT,
    SPAN_CKPT_PERSIST, SPAN_CKPT_RESTORE, SPAN_PROFILE,
    SPAN_SERVE_REQUEST, SPAN_SERVE_QUEUED, SPAN_SERVE_PREFILL,
    SPAN_SERVE_DECODE, SPAN_SERVE_STEP, SPAN_SERVE_STALL,
})


# ----------------------------- store-backed ----------------------------

def emit(store: StateStore, pool_id: str, kind: str,
         ctx: Optional[trace_ctx.TraceContext], *,
         job_id: Optional[str] = None, task_id: Optional[str] = None,
         node_id: Optional[str] = None,
         start: Optional[float] = None, end: Optional[float] = None,
         attrs: Optional[dict] = None,
         self_span: bool = False) -> Optional[str]:
    """Append one span under ``ctx`` (a NEW child span id is minted;
    the span's parent is ctx.span_id). ``self_span=True`` instead
    records ctx's OWN span (id = ctx.span_id, parent =
    ctx.parent_span_id) — used for spans whose id must be known in
    advance, like the submit root every task row parents under. No-op
    for ctx=None (legacy untraced tasks) or an undeclared kind.
    Returns the span id written, or None when nothing was. Never
    raises: tracing is an observer, not a participant."""
    if ctx is None:
        return None
    if kind not in SPAN_KINDS:
        logger.warning("unknown span kind %r dropped", kind)
        return None
    if self_span:
        span_id, parent = ctx.span_id, ctx.parent_span_id
    else:
        span_id, parent = trace_ctx.new_span_id(), ctx.span_id
    try:
        ts = time.time() if start is None else float(start)
        entity = {
            "kind": kind, "trace_id": ctx.trace_id,
            "span_id": span_id, "parent_span_id": parent,
            "job_id": job_id, "task_id": task_id, "node_id": node_id,
            "start": ts, "end": ts if end is None else float(end),
            "attrs": dict(attrs or {}),
        }
        row_key = f"{ts:017.6f}${uuid.uuid4().hex[:8]}"
        store.insert_entity(names.TABLE_TRACE, pool_id, row_key,
                            entity)
        return span_id
    except Exception:  # noqa: BLE001 - observer must not fail work
        logger.debug("trace emit failed", exc_info=True)
        return None


@contextlib.contextmanager
def span(store: StateStore, pool_id: str, kind: str,
         ctx: Optional[trace_ctx.TraceContext], *,
         job_id: Optional[str] = None, task_id: Optional[str] = None,
         node_id: Optional[str] = None,
         attrs: Optional[dict] = None) -> Iterator[dict]:
    """Time a block as one span; yields the attrs dict so the body
    can add counters before the span is emitted."""
    out_attrs = dict(attrs or {})
    start = time.time()
    try:
        yield out_attrs
    finally:
        emit(store, pool_id, kind, ctx, job_id=job_id, task_id=task_id,
             node_id=node_id, start=start, end=time.time(),
             attrs=out_attrs)


def query(store: StateStore, pool_id: str,
          trace_id: Optional[str] = None,
          job_id: Optional[str] = None) -> list[dict]:
    """Spans of a pool (optionally one trace/job), sorted by start."""
    out = []
    for row in store.query_entities(names.TABLE_TRACE,
                                    partition_key=pool_id):
        if trace_id is not None and row.get("trace_id") != trace_id:
            continue
        if job_id is not None and row.get("job_id") != job_id:
            continue
        out.append(row)
    return sorted(out, key=lambda e: (e.get("start", 0.0),
                                      e.get("end", 0.0)))


def prune(store: StateStore, pool_id: str,
          older_than_seconds: float) -> int:
    """Retention sweep (the goodput-log rule): drop spans that ENDED
    more than ``older_than_seconds`` ago."""
    cutoff = time.time() - older_than_seconds
    removed = 0
    for row in list(store.query_entities(names.TABLE_TRACE,
                                         partition_key=pool_id)):
        if float(row.get("end", row.get("start", 0.0))) < cutoff:
            try:
                store.delete_entity(names.TABLE_TRACE, pool_id,
                                    row["_rk"])
                removed += 1
            except Exception:  # noqa: BLE001 - best effort
                logger.debug("trace prune failed", exc_info=True)
    return removed


# ---------------------------- process-local ----------------------------

def local_spans_path() -> Optional[str]:
    """The JSONL sink for THIS process, or None (recorder disabled)."""
    return os.environ.get(trace_ctx.TRACE_FILE_ENV) or None


# The recorder keeps rows in memory and appends them in batches: a
# serving engine writes a row per step (twenty a second) from the
# thread that feeds the chip, which cannot afford a file open each.
# A row that arrives FLUSH_INTERVAL_S or more after the last write
# goes out at once with whatever is waiting (so low-rate spans —
# compile, checkpoint — still land immediately), and so does a
# buffer that has reached MAX_BUFFERED_ROWS; otherwise rows wait for
# the next such row, for flush(), or for the process to exit. Each
# batch is one open-append-close, so the agent's rename-drain of a
# live file (docs/32-tracing.md) keeps working: the next batch
# re-creates the path.
FLUSH_INTERVAL_S = 2.0
MAX_BUFFERED_ROWS = 8192

_buffer: list[tuple[str, dict]] = []     # (sink path, span dict)
_buffer_lock = threading.Lock()
_write_lock = threading.Lock()
_last_flush = float("-inf")              # time.monotonic() of it
_exit_hooked = False


def flush() -> int:
    """Append every buffered row to the sink it was recorded for.
    Returns the rows written. Never raises: rows a failed write held
    are dropped (the recorder's loss-over-duplication bias)."""
    global _last_flush
    with _buffer_lock:
        rows = list(_buffer)
        _buffer.clear()
        _last_flush = time.monotonic()
    if not rows:
        return 0
    by_path: dict[str, list[str]] = {}
    for path, event in rows:
        by_path.setdefault(path, []).append(json.dumps(event) + "\n")
    written = 0
    with _write_lock:
        for path, lines in by_path.items():
            try:
                os.makedirs(os.path.dirname(path) or ".",
                            exist_ok=True)
                with open(path, "a", encoding="utf-8") as fh:
                    fh.write("".join(lines))
                written += len(lines)
            except OSError:
                logger.debug("trace local flush failed",
                             exc_info=True)
    return written


def record(kind: str, start: float, /,
           end: Optional[float] = None,
           parent_span_id: Optional[str] = None,
           span_id: Optional[str] = None,
           **attrs: Any) -> Optional[str]:
    """Process-local emit: one JSONL span for $SHIPYARD_TRACE_FILE
    (buffered, see above). ``kind`` and ``start`` are positional
    only, so an attr may be called either. The trace id comes from the task context
    the agent exported; ``parent_span_id`` defaults to the task's own
    span (the run span), so flat program phases chain correctly with
    no caller plumbing. No-op when no sink or no context is
    configured; never raises. Returns the span id recorded (for
    parenting child spans), or None."""
    return _record(kind, start, end, attrs,
                   parent_span_id=parent_span_id, span_id=span_id)


def _record(kind: str, start: float, end: Optional[float],
            attrs: dict,
            parent_span_id: Optional[str] = None,
            span_id: Optional[str] = None) -> Optional[str]:
    """Dict-attrs core of record(): attr keys can never collide with
    the positional parameters (a phase() body writing
    attrs["start"]/["end"] must degrade to data, not raise a
    TypeError out of the finally block into the traced work)."""
    global _exit_hooked
    path = local_spans_path()
    ctx = trace_ctx.TraceContext.from_env()
    if path is None or ctx is None:
        return None
    if kind not in SPAN_KINDS:
        logger.warning("unknown span kind %r dropped", kind)
        return None
    sid = span_id or trace_ctx.new_span_id()
    event = {
        "kind": kind, "trace_id": ctx.trace_id, "span_id": sid,
        "parent_span_id": parent_span_id or ctx.span_id,
        "start": float(start),
        "end": float(start if end is None else end),
        "attrs": dict(attrs),
    }
    with _buffer_lock:
        _buffer.append((path, event))
        due = (len(_buffer) >= MAX_BUFFERED_ROWS or
               time.monotonic() - _last_flush >= FLUSH_INTERVAL_S)
        if not _exit_hooked:
            atexit.register(flush)
            _exit_hooked = True
    if due:
        flush()
    return sid


@contextlib.contextmanager
def phase(kind: str, **attrs: Any) -> Iterator[dict]:
    """Time a block as a process-local span; yields the attrs dict
    (mutable — counters get filled in by the body; any key is safe,
    including "start"/"end")."""
    out_attrs = dict(attrs)
    start = time.time()
    try:
        yield out_attrs
    finally:
        _record(kind, start, time.time(), out_attrs)


class PhaseTimer:
    """Times the leaf phases of a host loop on two clocks at once.

    ``with timer("admit"):`` opens a jax.profiler.TraceAnnotation
    named ``<prefix>admit`` — a fraction of a microsecond with no
    profiler session, and with one the phase lies on the device
    trace's clock, so a device-idle gap can be put down to it — and
    adds the block's time.monotonic() seconds to ``step`` (since the
    last reset(), what a per-step row keeps) and to ``total`` (since
    construction, what /stats reports). Phases are leaves: do not
    nest one in another, or its seconds count twice. ``total`` holds
    every name in ``phases`` from the start, so another thread may
    copy it while this one adds."""

    def __init__(self, prefix: str, phases: tuple) -> None:
        # jax is imported here, not at module scope: the agent and
        # the CLI import this module and never import jax.
        from jax.profiler import TraceAnnotation
        self._annotate = TraceAnnotation
        self.prefix = prefix
        self.step: dict[str, float] = {}
        self.total: dict[str, float] = dict.fromkeys(phases, 0.0)

    def reset(self) -> None:
        self.step.clear()

    @contextlib.contextmanager
    def __call__(self, name: str) -> Iterator[None]:
        with self._annotate(self.prefix + name):
            t0 = time.monotonic()
            try:
                yield
            finally:
                dt = time.monotonic() - t0
                self.step[name] = self.step.get(name, 0.0) + dt
                self.total[name] += dt


class _CompileCounter:
    """Programs the backend compiled (or loaded from the persistent
    cache) in this process, and the seconds that took: jax reports
    each through jax.monitoring as it happens, on whichever thread
    asked for the program."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self) -> None:
        from jax import monitoring
        self._lock = threading.Lock()
        self._count = 0
        self._seconds = 0.0
        monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, seconds: float, **_kwargs) -> None:
        if event == self.EVENT:
            with self._lock:
                self._count += 1
                self._seconds += seconds

    def read(self) -> tuple[int, float]:
        with self._lock:
            return self._count, self._seconds


_compile_counter: Optional[_CompileCounter] = None
_compile_counter_lock = threading.Lock()


def compile_counter() -> _CompileCounter:
    """The process-wide compile counter; the first call installs its
    listener (what compiled before that is not counted)."""
    global _compile_counter
    with _compile_counter_lock:
        if _compile_counter is None:
            _compile_counter = _CompileCounter()
    return _compile_counter


def ingest_local_spans(store: StateStore, pool_id: str, path: str, *,
                       job_id: Optional[str] = None,
                       task_id: Optional[str] = None,
                       node_id: Optional[str] = None) -> int:
    """Fold a task's process-local JSONL spans into the store with the
    task's identity attached. The file's contents are task-controlled:
    junk lines are skipped, never raised into the agent's task flow.
    The file is removed on success so retries don't double-count."""
    if not os.path.exists(path):
        return 0
    count = 0
    rows: list[tuple[str, str, dict]] = []
    try:
        with open(path, encoding="utf-8") as fh:
            for line in fh:
                line = line.strip()
                if not line:
                    continue
                try:
                    event = json.loads(line)
                except ValueError:
                    continue
                if not isinstance(event, dict) or \
                        event.get("kind") not in SPAN_KINDS:
                    continue
                trace_id = event.get("trace_id")
                span_id = event.get("span_id")
                if not trace_id or not span_id:
                    continue
                try:
                    start = float(event.get("start"))
                    end = float(event.get("end", start))
                except (TypeError, ValueError):
                    continue
                attrs = event.get("attrs")
                if not isinstance(attrs, dict):
                    attrs = {}
                row_key = f"{start:017.6f}${uuid.uuid4().hex[:8]}"
                rows.append((pool_id, row_key, {
                    "kind": event["kind"],
                    "trace_id": str(trace_id),
                    "span_id": str(span_id),
                    "parent_span_id": event.get("parent_span_id"),
                    "job_id": job_id, "task_id": task_id,
                    "node_id": node_id,
                    "start": start, "end": end,
                    "attrs": attrs,
                }))
        # One batched insert for the whole file (a task can emit
        # thousands of spans; per-row writes made ingestion a
        # round-trip storm on the heartbeat path). Best effort with
        # the same loss-over-duplication bias as the old per-row
        # loop: the file is removed either way, so a partial batch
        # failure drops spans rather than double-counting them on
        # the next ingest pass.
        try:
            store.insert_entities(names.TABLE_TRACE, rows)
            count = len(rows)
        except Exception:  # noqa: BLE001 - best effort
            logger.debug("trace ingest insert failed", exc_info=True)
        os.remove(path)
    except OSError:
        logger.debug("trace ingest failed for %s", path, exc_info=True)
    return count
