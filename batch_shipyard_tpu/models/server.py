"""HTTP serving front end over the continuous-batching engine.

The reference has no serving story; SURVEY.md treats recipes as the
acceptance surface, and an Orca/vLLM-class engine is judged by
TTFT/TPOT under load — which needs an ingress path. This front end is
deliberately stdlib-only (http.server): the engine's throughput comes
from the jitted decode step, not the socket layer, and one thread per
in-flight request is plenty for a per-replica slot count.

Architecture:
  - HTTP handlers parse/validate and enqueue (request, Event) pairs;
  - ONE engine thread owns the ContinuousBatcher: it drains the
    submission queue, calls engine.step() while work is active, and
    completes waiters — the engine is never touched from two threads;
  - the engine's on_tokens hook (one call a landed step) timestamps
    each request's first token, giving true TTFT (time-to-first-token)
    rather than time-to-completion, and hands the step's tokens to
    ONE stream-writer thread (_StreamWriter) that sends every
    stream's lines: a step wakes one thread, however many streams.

Endpoints:
  POST /v1/generate   {"prompt": [ids], "max_new_tokens": n,
                       "request_id"?: str, "eos_id"?: int,
                       "stream"?: bool}
      -> {"request_id", "tokens", "num_tokens", "ttft_ms",
          "tpot_ms", "latency_ms"}
      With "stream": true the response is newline-delimited JSON
      (chunked transfer): one {"token": t, "index": i} line per
      generated token as it decodes, then a final line with the full
      result object — the client observes TTFT directly.
  DELETE /v1/requests/<request_id>   abort a queued/decoding request
      (202 accepted; the waiter completes with a 'cancelled' error;
      404 for ids this front end does not currently own — a fleet
      router's broadcast cancel probes replicas by that signal)
  GET  /v1/stats      aggregate counters + latency percentiles
  GET  /healthz       liveness
"""

from __future__ import annotations

import collections
import json
import math
import queue
import selectors
import socket
import threading
import time
import uuid
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from typing import Optional

from batch_shipyard_tpu.models.serving import (
    BLOCK_COUNTERS, PHASE_PREFIX, ContinuousBatcher, Request)
from batch_shipyard_tpu.trace import spans as trace_spans
from batch_shipyard_tpu.trace.histogram import LatencyHistogram
from batch_shipyard_tpu.utils import util

logger = util.get_logger(__name__)


class RequestCancelled(Exception):
    """The request was aborted via the cancel API."""


class RequestShed(Exception):
    """The engine dropped the request under overload (its TTFT
    deadline was blown past the shed grace) — surfaced as 503 so
    clients/routers treat it as back-pressure, not failure."""


class RequestDraining(Exception):
    """This replica is draining (preempt/evict notice): the request
    was refused, or its decode was abandoned at the grace deadline.
    Surfaced as 503 + Retry-After with a "draining" marker so the
    router fails over (and, mid-stream, resumes on a sibling) instead
    of treating the replica as failed."""


class TooManyRequests(Exception):
    """Front-door concurrency cap exceeded — 429 back-pressure; the
    router backs off and retries a sibling."""


class CompletedReplay(Exception):
    """A resume landed for a request this replica already finished:
    serve the cached result instead of decoding again (exactly-once
    across a router failover that raced completion)."""

    def __init__(self, result: dict) -> None:
        super().__init__(result["request_id"])
        self.result = result


class JsonRequestHandler(BaseHTTPRequestHandler):
    """Shared handler base for the serving HTTP surfaces (this front
    end and models/router.py): HTTP/1.1 (required for chunked
    streaming; all non-streaming replies carry Content-Length so
    keep-alive is safe), silenced per-request logging, and the JSON
    reply helper."""

    protocol_version = "HTTP/1.1"

    def log_message(self, fmt, *args):  # noqa: N802
        pass

    def _reply(self, code: int, payload: dict,
               headers: Optional[dict] = None) -> None:
        body = json.dumps(payload).encode()
        self.send_response(code)
        self.send_header("Content-Type", "application/json")
        self.send_header("Content-Length", str(len(body)))
        for name, value in (headers or {}).items():
            self.send_header(name, str(value))
        self.end_headers()
        self.wfile.write(body)

    def _delete_request_id(self) -> Optional[str]:
        """Parse /v1/requests/<id> from a DELETE path; None (and a
        404 reply) otherwise."""
        prefix = "/v1/requests/"
        if not self.path.startswith(prefix):
            self._reply(404, {"error": "not found"})
            return None
        return self.path[len(prefix):]

    def _reply_metrics(self, lines: list[str]) -> None:
        """Prometheus text exposition (the monitoring stack's scrape
        format — docs/09-monitoring.md)."""
        body = ("\n".join(lines) + "\n").encode()
        self.send_response(200)
        self.send_header("Content-Type",
                         "text/plain; version=0.0.4")
        self.send_header("Content-Length", str(len(body)))
        self.end_headers()
        self.wfile.write(body)


def _escape_label(value) -> str:
    """Prometheus exposition label escaping (\\, \", newline) — one
    odd replica URL must not invalidate the whole scrape."""
    return (str(value).replace("\\", "\\\\")
            .replace('"', '\\"').replace("\n", "\\n"))


def prometheus_lines(prefix: str, values: dict,
                     labels: Optional[dict] = None) -> list[str]:
    """Render {name: number} as Prometheus gauges with optional
    labels; None values are skipped (absent metric, not zero).
    Values render at full float64 precision — ':g' would quantize
    counters past 1e6 and break rate()/increase()."""
    label_str = ""
    if labels:
        inner = ",".join(
            f'{k}="{_escape_label(v)}"'
            for k, v in sorted(labels.items()))
        label_str = "{" + inner + "}"
    out = []
    for name, value in values.items():
        if value is None:
            continue
        out.append(f"{prefix}_{name}{label_str} "
                   f"{float(value):.17g}")
    return out


_STREAM_END = b"0\r\n\r\n"


def _chunk(line: bytes) -> bytes:
    """One NDJSON line in chunked-transfer framing."""
    return b"%x\r\n%b\n\r\n" % (len(line) + 1, line)


def _json_chunk(obj: dict) -> bytes:
    return _chunk(json.dumps(obj).encode())


class _Stream:
    """What the stream writer keeps of one streaming reply. Only the
    writer thread touches sock, unsent and stalled_at; the handler
    thread of the connection waits on wake (the run is over, or the
    stream was dropped) and then on done (everything it handed to
    the writer's close() is on the socket, or the stream was
    dropped)."""

    __slots__ = ("sock", "unsent", "stalled_at", "dropped", "closing",
                 "wake", "done")

    def __init__(self) -> None:
        self.sock: Optional[socket.socket] = None   # until registered
        # Bytes not on the socket yet, in order: lines that came
        # before the handler registered its connection, and the rest
        # of a send the client's window did not take whole.
        self.unsent = bytearray()
        # When the socket last took nothing of what was owed to it.
        self.stalled_at: Optional[float] = None
        self.dropped: Optional[str] = None          # why, once dropped
        self.closing = False                        # its last bytes are in
        self.wake = threading.Event()
        self.done = threading.Event()


class _StreamWriter:
    """ONE thread that writes every stream's lines.

    The engine thread hands over a landed step's tokens with one
    append and one byte on a wake-up socket (write), so a step wakes
    one thread however many streams it feeds, and the writer sends
    each stream its chunk-framed NDJSON line in the order handed over:
    one ordered channel a stream, token lines, then whatever the
    handler thread hands to close(). Sends never block: the
    connection is non-blocking while it is registered here, what a
    send leaves over is kept on the stream and sent when the socket
    turns writable (the selector says when: no timer), and later
    lines queue behind it. A stream that owes more than
    BACKLOG_LIMIT bytes, or whose socket has taken nothing for
    io_timeout_s where that is set, is dropped as a client that went
    away is: the engine finishes the run, the handler retires the
    registration and closes the connection."""

    BACKLOG_LIMIT = 1 << 20

    def __init__(self, io_timeout_s: Optional[float]) -> None:
        self.io_timeout_s = io_timeout_s
        self._inbox: collections.deque = collections.deque()
        self._wake_r, self._wake_w = socket.socketpair()
        self._wake_r.setblocking(False)
        self._wake_w.setblocking(False)
        self._selector = selectors.DefaultSelector()
        self._selector.register(self._wake_r, selectors.EVENT_READ)
        self._stalled: set[_Stream] = set()     # in the selector
        self._running = True
        self._thread = threading.Thread(
            target=self._run, name="serving-stream-writer", daemon=True)
        # Written by the writer thread alone.
        self.handovers = 0          # token batches taken
        self.tokens_written = 0     # token lines sent or kept to send
        self.sends_deferred = 0     # sends the socket took only part of
        self.dropped_backlog = 0    # streams dropped for what they owed

    def start(self) -> None:
        self._thread.start()

    def stop(self) -> None:
        self._hand(self._stop)
        if self._thread.is_alive():
            self._thread.join(timeout=10.0)

    # ---- called from the engine thread and the handler threads

    def write(self, lines: list) -> None:
        """A step's [(stream, token, index), ...]: one hand-over."""
        self._hand(self._tokens, lines)

    def register(self, stream: _Stream, sock: socket.socket) -> None:
        """The reply's headers are out: from here on the writer owns
        the connection's writes, until close() returns."""
        sock.setblocking(False)
        self._hand(self._register, stream, sock)

    def close(self, stream: _Stream, data: bytes) -> bool:
        """Send the reply's last bytes (final line, end of the
        chunked body) behind its token lines, wait until they are on
        the socket, and give the connection back to its handler
        thread as it was. -> whether the whole reply got there
        (False: the stream was dropped)."""
        self._hand(self._finish, stream, data)
        stream.done.wait()
        if stream.dropped is not None:
            return False    # the handler closes the connection
        try:
            stream.sock.settimeout(self.io_timeout_s)
        except OSError:
            return False    # severed under us (kill())
        return True

    def discard(self, stream: _Stream) -> None:
        """The client went away before its headers: keep nothing."""
        self._hand(self._drop, stream,
                   "client went away before its headers")

    def _hand(self, take, *args) -> None:
        self._inbox.append((take, args))
        try:
            self._wake_w.send(b"\0")
        except OSError:
            pass    # full: wake-ups enough are pending; or stopped

    # ---- the writer thread

    def _run(self) -> None:
        while self._running:
            try:
                self._turn()
            except Exception:  # one stream's fault is not all's
                logger.exception("stream writer")

    def _turn(self) -> None:
        for key, _events in self._selector.select(self._patience()):
            if key.data is None:
                try:
                    self._wake_r.recv(4096)
                except BlockingIOError:
                    pass
            else:
                self._flush(key.data)
        while self._inbox:
            take, args = self._inbox.popleft()
            take(*args)
        if self._stalled and self.io_timeout_s is not None:
            late = time.monotonic() - self.io_timeout_s
            for stream in [s for s in self._stalled
                           if s.stalled_at <= late]:
                self._drop(stream, "client stopped reading",
                           backlog=True)

    def _patience(self) -> Optional[float]:
        """Seconds until the longest-stalled stream is dropped; None
        where nothing is stalled or no deadline is set."""
        if not self._stalled or self.io_timeout_s is None:
            return None
        oldest = min(s.stalled_at for s in self._stalled)
        return max(0.0, oldest + self.io_timeout_s - time.monotonic())

    def _tokens(self, lines: list) -> None:
        self.handovers += 1
        for stream, token, index in lines:
            # not after the reply's last bytes (a run that outlived
            # its reply's timeout): the connection is the handler's
            if stream.dropped is None and not stream.closing:
                self.tokens_written += 1
                self._send(stream, _chunk(
                    b'{"token": %d, "index": %d}' % (token, index)))

    def _register(self, stream: _Stream, sock: socket.socket) -> None:
        stream.sock = sock
        if stream.dropped is None:
            self._flush(stream)

    def _finish(self, stream: _Stream, data: bytes) -> None:
        if stream.dropped is None:
            stream.closing = True
            self._send(stream, data)

    def _stop(self) -> None:
        for stream in list(self._stalled):
            self._drop(stream, "front end stopped")
        self._selector.close()
        self._wake_r.close()
        self._wake_w.close()
        self._running = False

    def _send(self, stream: _Stream, data: bytes) -> None:
        """Send, or queue behind what the stream already owes."""
        stream.unsent += data
        if stream.sock is None or stream in self._stalled:
            if len(stream.unsent) > self.BACKLOG_LIMIT:
                self._drop(stream, "backlog over the bound",
                           backlog=True)
            return
        self._flush(stream)

    def _flush(self, stream: _Stream) -> None:
        unsent = stream.unsent
        sent = 0
        try:
            if unsent:
                try:
                    sent = stream.sock.send(unsent)
                except (BlockingIOError, InterruptedError):
                    pass
                del unsent[:sent]
            if unsent:
                self.sends_deferred += 1
                if sent or stream.stalled_at is None:
                    stream.stalled_at = time.monotonic()
                if stream not in self._stalled:
                    self._selector.register(
                        stream.sock, selectors.EVENT_WRITE, stream)
                    self._stalled.add(stream)
                return
            self._settle(stream)
        except (OSError, ValueError) as exc:
            # a reset; ValueError: closed under the writer (kill())
            self._drop(stream, f"client went away: {exc}")
            return
        if stream.closing:
            stream.done.set()

    def _settle(self, stream: _Stream) -> None:
        """Nothing is owed (or the stream is gone): out of the
        selector."""
        stream.stalled_at = None
        if stream in self._stalled:
            self._stalled.discard(stream)
            self._selector.unregister(stream.sock)

    def _drop(self, stream: _Stream, why: str,
              backlog: bool = False) -> None:
        if stream.dropped is not None:
            return
        self._settle(stream)
        stream.dropped = why
        stream.unsent.clear()
        if backlog:
            self.dropped_backlog += 1
        stream.wake.set()
        stream.done.set()


class _Pending:
    __slots__ = ("request", "event", "submitted_at", "submitted_wall",
                 "admitted_at", "first_token_at",
                 "finished_at", "tokens", "error", "stream",
                 "cancelled", "shed", "draining", "resumed",
                 "emitted")

    def __init__(self, request: Request, stream: bool = False,
                 resumed: Optional[list[int]] = None) -> None:
        self.request = request
        self.event = threading.Event()
        self.submitted_at = time.perf_counter()
        # Wall-clock arrival: the anchor the request's trace spans
        # are placed at (perf_counter deltas give the durations).
        self.submitted_wall = time.time()
        # Slot admission (the engine's on_admit hook): the
        # queued -> prefill boundary.
        self.admitted_at: Optional[float] = None
        self.first_token_at: Optional[float] = None
        self.finished_at: Optional[float] = None
        self.tokens: Optional[list[int]] = None
        self.error: Optional[str] = None
        self.cancelled = False
        self.shed = False
        # Drain: the replica abandoned/refused this request while
        # shutting down — the waiter surfaces RequestDraining and the
        # router resumes elsewhere.
        self.draining = False
        # Router recovery: tokens a prior replica already emitted
        # (the engine re-prefills them; on_token indexes continue
        # globally from len(resumed)).
        self.resumed: Optional[list[int]] = resumed
        # Highest emitted-token count (global index + 1): the
        # /v1/requests/<id> phase probe's progress source of truth.
        self.emitted = len(resumed) if resumed else 0
        # Streaming mode: what the stream writer keeps of this
        # request's connection; the handler thread waits on it.
        self.stream: Optional[_Stream] = _Stream() if stream else None

    def finish(self) -> None:
        """The run is over (tokens or an error are set): wake whoever
        waits for it."""
        self.event.set()
        if self.stream is not None:
            self.stream.wake.set()


def percentile(values: list[float], pct: float) -> float:
    """Nearest-rank percentile (no numpy dependency in the serving
    path)."""
    if not values:
        return 0.0
    ordered = sorted(values)
    k = max(1, min(len(ordered),
                   math.ceil(pct / 100.0 * len(ordered))))
    return ordered[k - 1]


class ServingFrontEnd:
    """Owns the engine thread + HTTP server around a
    ContinuousBatcher."""

    def __init__(self, engine: ContinuousBatcher,
                 host: str = "127.0.0.1", port: int = 0,
                 slo_classes: Optional[dict] = None,
                 max_inflight: Optional[int] = None,
                 io_timeout_s: Optional[float] = None,
                 drain_grace_s: float = 30.0) -> None:
        """slo_classes maps class name ->
        {"ttft_ms": float|None, "tpot_ms": float|None}
        (config/settings.ServingSloSettings.class_targets()). A
        request's "slo_class" resolves to those targets at admission;
        explicit "ttft_target_ms"/"tpot_target_ms" in the request
        body override its class. With no classes configured, class
        names pass through untargeted.

        Front-door hardening: max_inflight caps accepted-but-
        unfinished requests (excess gets 429 back-pressure; resumes
        are exempt — a recovery must not bounce), io_timeout_s sets a
        per-connection socket read/write deadline so one wedged
        client cannot pin a handler thread forever, drain_grace_s is
        the default budget drain() gives in-flight decodes before
        abandoning them."""
        self.engine = engine
        self.slo_classes = dict(slo_classes or {})
        self.max_inflight = max_inflight
        self.drain_grace_s = drain_grace_s
        # Drain ladder state: _draining flips once (preempt/evict
        # notice or explicit drain()); handlers refuse new work with
        # 503+Retry-After, healthz reports draining so the router
        # stops routing here, and the engine thread lets active
        # decodes run until _drain_deadline.
        self._draining = threading.Event()
        self._drain_deadline: Optional[float] = None
        self._drain_reason = ""
        self._drain_engine_done = False
        self.drain_rejections = 0
        # Both observers: an engine that hands over a step's tokens
        # at once calls on_tokens, one that only knows on_token calls
        # that; either way the lines go through the one writer.
        engine.on_token = self._on_token
        engine.on_tokens = self._on_tokens
        self._streams = _StreamWriter(io_timeout_s)
        engine.on_admit = self._on_admit
        engine.on_shed = self._on_shed
        # The engine's serve_step rows are head-sampled like the
        # request spans below: the head starts over with this front
        # end.
        engine.traced_steps = 0
        # ... and so does what shutdown() logs of the engine's launch
        # counters (a warm-up's compiles are not this front end's;
        # an engine that keeps no counters has none to log).
        self._launches_before = getattr(engine, "step_stats", dict)()
        self._submit_q: "queue.Queue[_Pending]" = queue.Queue()
        self._inflight: dict[str, _Pending] = {}
        self._inflight_lock = threading.Lock()
        # Engine-side run ownership: request_id -> the _Pending whose
        # submission the engine is actually decoding. Written ONLY by
        # the engine thread; _engine_active mirrors its keys under
        # _inflight_lock so _make_pending can reject an id that is
        # still decoding (a client that timed out/disconnected and
        # retried must not receive the stale run's completion).
        self._active_runs: dict[str, _Pending] = {}
        self._engine_active: set[str] = set()
        # Cancellations cross onto the engine thread here (the engine
        # is single-threaded by design; cancel mutates slot state).
        self._cancel_q: "queue.Queue[str]" = queue.Queue()
        self._stop = threading.Event()
        # The engine thread's wait for work, in the engine's own
        # annotation family (serve:no_work): on a device trace's
        # clock the engine's dry spells have a name from inside the
        # program. No phase of a step; the untraced twin is the
        # engine's no_work_seconds.
        self._waiting = trace_spans.PhaseTimer(PHASE_PREFIX,
                                               ("no_work",))
        # Live client sockets (handler setup/finish): kill() severs
        # them all to reproduce the SIGKILL failure shape — streams
        # end in a reset/bare EOF with no drain marker and no final
        # line, exactly what the router's recovery path must absorb.
        self._conns: set = set()
        self._conns_lock = threading.Lock()
        self._stats_lock = threading.Lock()
        # Recent-request detail only (bounded): totals and
        # percentiles come from the running counters + histograms
        # below, so a replica's memory/stats cost never grows with
        # lifetime traffic.
        self._completed: "collections.deque" = collections.deque(
            maxlen=2048)
        # Finished-result replay cache (bounded), written atomically
        # with the _inflight pop under _inflight_lock: a resume that
        # races completion finds the cached result here instead of
        # being admitted as a fresh (duplicate) decode.
        self._recent_results: "collections.OrderedDict[str, dict]" = \
            collections.OrderedDict()
        self._recent_results_cap = 2048
        self._total_completed = 0
        self._total_tokens = 0
        # Mergeable fixed-log-bucket latency histograms
        # (trace/histogram.py): the shape the router can aggregate
        # fleet-wide and Prometheus can histogram_quantile() over —
        # exact per-request lists stay only for this replica's own
        # recent detail.
        self._ttft_hist = LatencyHistogram()
        self._tpot_hist = LatencyHistogram()
        # Per-SLO-class attainment accounting (under _stats_lock):
        # class -> {requests, ttft_ok, tpot_ok, shed}.
        self._class_stats: dict[str, dict] = {}
        self._started_at = time.perf_counter()
        self._engine_thread = threading.Thread(
            target=self._engine_loop, name="serving-engine", daemon=True)
        front = self

        class Handler(JsonRequestHandler):
            def setup(self):
                super().setup()
                with front._conns_lock:
                    front._conns.add(self.connection)

            def finish(self):
                try:
                    super().finish()
                finally:
                    with front._conns_lock:
                        front._conns.discard(self.connection)

            def do_DELETE(self):  # noqa: N802
                request_id = self._delete_request_id()
                if request_id is None:
                    return
                # Unknown ids 404 so a fleet router's broadcast
                # cancel can keep probing replicas for the owner.
                if not front.knows(request_id):
                    self._reply(404, {"error": f"unknown request_id "
                                               f"{request_id}"})
                    return
                front.cancel(request_id)
                self._reply(202, {"request_id": request_id,
                                  "cancelling": True})

            def do_GET(self):  # noqa: N802
                if self.path == "/healthz":
                    # Draining replicas answer 503 so the router's
                    # status==200 health check pulls them from
                    # rotation before the kill lands.
                    if front.draining:
                        self._reply(503, {"ok": False,
                                          "draining": True})
                    else:
                        self._reply(200, {"ok": True})
                elif self.path == "/metrics":
                    self._reply_metrics(front.prometheus_metrics())
                elif self.path == "/v1/stats":
                    self._reply(200, front.stats())
                elif self.path.startswith("/v1/requests/"):
                    # Liveness + progress of one request id (the
                    # fleet router's orphan reconciliation AND its
                    # mid-stream recovery probe this — one source of
                    # truth): 200 while the run is in flight here,
                    # 404 once finished or never seen.
                    request_id = self.path[len("/v1/requests/"):]
                    status = front.request_status(request_id)
                    if status is not None:
                        self._reply(200, status)
                    else:
                        self._reply(404, {"request_id": request_id,
                                          "in_flight": False})
                else:
                    self._reply(404, {"error": "not found"})

            def do_POST(self):  # noqa: N802
                if self.path != "/v1/generate":
                    self._reply(404, {"error": "not found"})
                    return
                try:
                    length = int(self.headers.get("Content-Length", 0))
                    spec = json.loads(self.rfile.read(length))
                except (ValueError, OSError) as exc:
                    self._reply(400, {"error": str(exc)})
                    return
                if not isinstance(spec, dict):
                    self._reply(400, {"error": "body must be a JSON "
                                               "object"})
                    return
                if spec.get("stream"):
                    # Owns its response lifecycle end-to-end; nothing
                    # here may write a second reply after its headers.
                    self._stream_generate(spec)
                    return
                try:
                    result = front.generate(spec)
                except CompletedReplay as exc:
                    # Resume of an already-finished run: exactly-once
                    # means replaying the cached result, not decoding
                    # a duplicate.
                    self._reply(200, dict(exc.result, cached=True))
                    return
                except RequestDraining as exc:
                    self._reply(503, {"error": str(exc),
                                      "draining": True},
                                headers={"Retry-After": "1"})
                    return
                except TooManyRequests as exc:
                    self._reply(429, {"error": str(exc),
                                      "backpressure": True},
                                headers={"Retry-After": "1"})
                    return
                except RequestCancelled as exc:
                    self._reply(409, {"error": str(exc)})
                    return
                except RequestShed as exc:
                    # Overload back-pressure, not failure: clients
                    # should retry elsewhere/later.
                    self._reply(503, {"error": str(exc),
                                      "shed": True})
                    return
                except ValueError as exc:
                    self._reply(400, {"error": str(exc)})
                    return
                except Exception as exc:  # defensive: keep serving
                    logger.exception("generate failed")
                    self._reply(500, {"error": str(exc)})
                    return
                self._reply(200, result)

            def _stream_generate(self, spec: dict) -> None:
                """Newline-delimited JSON token stream over chunked
                transfer: the client sees each token the engine step
                that produced it, then the final result object. This
                thread sends the headers and then waits; the lines
                are the stream writer's to send (serve_stream).
                Validation errors before headers -> plain 400; errors
                AFTER the 200/chunked headers are emitted as a final
                {"error": ...} NDJSON line + clean terminating chunk
                (a second HTTP response inside the open stream would
                corrupt the framing)."""
                pending = None
                try:
                    pending = front.submit_stream(spec)
                except CompletedReplay as exc:
                    # Replay the cached run as a stream: the router's
                    # index dedupe drops what the client already saw.
                    result = exc.result
                except RequestDraining as exc:
                    self._reply(503, {"error": str(exc),
                                      "draining": True},
                                headers={"Retry-After": "1"})
                    return
                except TooManyRequests as exc:
                    self._reply(429, {"error": str(exc),
                                      "backpressure": True},
                                headers={"Retry-After": "1"})
                    return
                except ValueError as exc:
                    self._reply(400, {"error": str(exc)})
                    return
                except Exception as exc:  # defensive, like do_POST
                    logger.exception("stream setup failed")
                    self._reply(500, {"error": str(exc)})
                    return
                try:
                    self.send_response(200)
                    self.send_header("Content-Type",
                                     "application/x-ndjson")
                    self.send_header("Transfer-Encoding", "chunked")
                    self.end_headers()
                except OSError:
                    # Client vanished before headers: drop the
                    # front-end registration explicitly (the
                    # engine-side guard still protects the id until
                    # decode completes).
                    if pending is not None:
                        front.abandon(pending)
                    return
                if pending is None:
                    # CompletedReplay: token lines then the cached
                    # final result, same framing as a live stream.
                    whole = front.replay_stream(result,
                                                self.connection)
                else:
                    whole = front.serve_stream(pending,
                                               self.connection)
                if not whole:
                    # Client went away (or stopped reading) mid-
                    # stream: the framing is cut, the connection
                    # serves nothing more. The engine finishes the
                    # run on its own.
                    self.close_connection = True

        if io_timeout_s is not None:
            # socketserver applies Handler.timeout as the connection
            # socket timeout (settimeout) — per-request read/write
            # deadlines so a wedged client can't pin a thread.
            Handler.timeout = io_timeout_s
        self._httpd = ThreadingHTTPServer((host, port), Handler)
        self._http_thread = threading.Thread(
            target=self._httpd.serve_forever, name="serving-http",
            daemon=True)

    # ------------------------------ lifecycle --------------------------

    @property
    def address(self) -> tuple[str, int]:
        return self._httpd.server_address[:2]

    @property
    def url(self) -> str:
        host, port = self.address
        return f"http://{host}:{port}"

    def start(self) -> "ServingFrontEnd":
        self._streams.start()
        self._engine_thread.start()
        self._http_thread.start()
        return self

    def shutdown(self) -> None:
        self._stop.set()
        self._httpd.shutdown()
        self._httpd.server_close()
        self._engine_thread.join(timeout=10.0)
        self._streams.stop()
        writer = self._streams
        logger.info(
            "stream writer: %d handovers, %d token lines, %d sends "
            "deferred, %d streams dropped for their backlog",
            writer.handovers, writer.tokens_written,
            writer.sends_deferred, writer.dropped_backlog)
        # The device's timeline as the engine's landings gave it,
        # since this front end took the engine over: an untraced
        # run's stderr keeps it (docs/32-tracing.md).
        before = self._launches_before
        if "launches" in before:
            self._log_launches(self.engine.step_stats(), before)
        # Spans still buffered (trace/spans.py) reach the file before
        # whoever shut this down reads it.
        trace_spans.flush()

    @staticmethod
    def _log_launches(now: dict, before: dict) -> None:
        def since(name, kind=None):
            if kind is None:
                return now[name] - before[name]
            return now[name][kind] - before[name][kind]

        # a model that carries its own drafter (absent otherwise)
        drafts = "; mtp_drafted %d, mtp_accepted %d" % (
            since("mtp_drafted"), since("mtp_accepted")) \
            if "mtp_drafted" in now else ""
        # a model that generates by diffusion over blocks (absent
        # otherwise)
        blocks = ("; block passes: %d denoise, %d commit, %d positions "
                  "unmasked, %d tokens landed, %d commits fused" % tuple(
                      since(name) for name in BLOCK_COUNTERS)) \
            if BLOCK_COUNTERS[0] in now else ""
        logger.info(
            "engine launches: %s; prefills_grouped %d; prefill tokens "
            "%d of %d padded; no work %.3f s; %d stalls" + drafts
            + blocks,
            ", ".join(
                f"{kind} {since('launches', kind)} "
                f"({since('launch_seconds', kind):.3f} s, "
                f"{since('landings_ready', kind)} found ready)"
                for kind in now["launches"]),
            since("prefills_grouped"),
            since("prefill_tokens"), since("prefill_bucket_tokens"),
            since("no_work_seconds"), since("stalls"))

    def kill(self) -> None:
        """The SIGKILL failure shape (chaos drills): stop the engine,
        close the listening socket, AND sever every live client
        connection mid-write — no drain ladder, no draining markers,
        no final stream lines. Downstream (the fleet router) sees a
        reset or a bare EOF without a final line, which is exactly
        the signal its mid-stream recovery keys on."""
        self._stop.set()
        try:
            self._httpd.shutdown()
            self._httpd.server_close()
        except OSError:
            pass
        with self._conns_lock:
            conns, self._conns = list(self._conns), set()
        for conn in conns:
            try:
                conn.shutdown(socket.SHUT_RDWR)
            except OSError:
                pass
            try:
                conn.close()
            except OSError:
                pass
        self._engine_thread.join(timeout=10.0)
        self._streams.stop()

    # ------------------------------ draining ---------------------------

    @property
    def draining(self) -> bool:
        return self._draining.is_set()

    def drain(self, grace_s: Optional[float] = None,
              reason: str = "drain requested") -> None:
        """Flip this replica into the drain ladder: healthz turns
        503/draining (the router stops routing here), new admissions
        get 503+Retry-After, the engine stops seating queued work,
        and in-flight decodes get ``grace_s`` seconds to finish
        before they are abandoned with a draining marker (the router
        resumes them on a sibling). Idempotent."""
        if self._draining.is_set():
            return
        grace = self.drain_grace_s if grace_s is None else grace_s
        self._drain_deadline = time.perf_counter() + max(0.0, grace)
        self._drain_reason = reason
        self._draining.set()
        logger.info("serving front end draining (%s): grace %.1fs",
                    reason, grace)

    def arm_preempt_drain(self, path: Optional[str] = None,
                          grace_s: Optional[float] = None,
                          poll_interval: float = 0.2) -> bool:
        """Watch the node agent's preempt/evict notice file
        (agent/preemption.py: $SHIPYARD_PREEMPT_REQUEST_FILE) and
        drain when it lands — the serving analog of the training
        checkpoint-on-notice path. Returns False (unarmed) when no
        notice channel is configured."""
        from batch_shipyard_tpu.agent.preemption import PreemptWatcher
        watcher = PreemptWatcher(path)
        if not watcher.armed:
            return False

        def _watch() -> None:
            while not self._stop.is_set():
                notice = watcher.poll()
                if notice:
                    self.drain(
                        grace_s,
                        reason="preempt notice: "
                        f"{notice.get('reason') or 'unspecified'}")
                    return
                time.sleep(poll_interval)

        threading.Thread(target=_watch, name="serving-drain-watch",
                         daemon=True).start()
        return True

    # ------------------------------ serving ----------------------------

    def _make_pending(self, spec: dict,
                      stream: bool = False) -> _Pending:
        prompt = spec.get("prompt")
        if not isinstance(prompt, list) or not all(
                isinstance(t, int) for t in prompt):
            raise ValueError("prompt must be a list of token ids")
        resume = spec.get("resume_tokens")
        if resume is not None and (
                not isinstance(resume, list) or not all(
                    isinstance(t, int) for t in resume)):
            raise ValueError(
                "resume_tokens must be a list of token ids")
        request_id = str(spec.get("request_id") or uuid.uuid4().hex[:12])
        try:
            max_new_tokens = int(spec.get("max_new_tokens", 16))
            priority = int(spec.get("priority") or 0)
        except (TypeError, ValueError) as exc:
            raise ValueError(
                f"max_new_tokens/priority must be integers: {exc}")
        slo_class = str(spec.get("slo_class") or "standard")
        if self.slo_classes and "slo_class" in spec and \
                slo_class not in self.slo_classes:
            raise ValueError(
                f"unknown slo_class {slo_class!r}; configured: "
                f"{sorted(self.slo_classes)}")
        targets = self.slo_classes.get(slo_class, {})

        def _target(key):
            value = spec.get(key, targets.get(
                key.replace("_target", "")))
            if value is None:
                return None
            try:
                return float(value)
            except (TypeError, ValueError) as exc:
                raise ValueError(f"{key} must be a number: {exc}")

        request = Request(
            request_id=request_id, prompt=prompt,
            max_new_tokens=max_new_tokens,
            eos_id=spec.get("eos_id"),
            priority=priority,
            ttft_target_ms=_target("ttft_target_ms"),
            tpot_target_ms=_target("tpot_target_ms"),
            slo_class=slo_class)
        pending = _Pending(request, stream=stream, resumed=resume)
        with self._inflight_lock:
            if resume is not None and \
                    request_id in self._recent_results:
                # The prior replica's run actually finished here (the
                # failover raced completion): replay, don't re-decode.
                raise CompletedReplay(
                    self._recent_results[request_id])
            if (request_id in self._inflight or
                    request_id in self._engine_active):
                raise ValueError(f"request_id {request_id} in flight")
            if self._draining.is_set():
                self.drain_rejections += 1
                raise RequestDraining(
                    f"request {request_id} refused: replica draining"
                    f" ({self._drain_reason})")
            if (self.max_inflight is not None and resume is None and
                    len(self._inflight) >= self.max_inflight):
                raise TooManyRequests(
                    f"request {request_id} refused: "
                    f"{len(self._inflight)} in flight >= cap "
                    f"{self.max_inflight}")
            self._inflight[request_id] = pending
        if resume and (
                len(resume) >= request.max_new_tokens or
                (request.eos_id is not None and
                 resume[-1] == request.eos_id)):
            # The resumed progress already satisfies the request:
            # complete without touching the engine (callers skip
            # submission when the event is pre-set).
            pending.tokens = list(resume)
            pending.finished_at = time.perf_counter()
            pending.first_token_at = pending.finished_at
            pending.finish()
        return pending

    def _result(self, pending: _Pending) -> dict:
        request_id = pending.request.request_id
        n = len(pending.tokens)
        ttft = (pending.first_token_at or pending.finished_at) - \
            pending.submitted_at
        decode = pending.finished_at - (pending.first_token_at or
                                        pending.submitted_at)
        tpot = decode / max(1, n - 1)
        result = {
            "request_id": request_id,
            "tokens": pending.tokens,
            "num_tokens": n,
            "ttft_ms": ttft * 1e3,
            "tpot_ms": tpot * 1e3,
            "latency_ms": (pending.finished_at -
                           pending.submitted_at) * 1e3,
            "slo_class": pending.request.slo_class,
        }
        req = pending.request
        with self._stats_lock:
            cls = self._class_stats.setdefault(
                req.slo_class,
                {"requests": 0, "ttft_ok": 0, "tpot_ok": 0,
                 "shed": 0})
            cls["requests"] += 1
            if req.ttft_target_ms is None or \
                    result["ttft_ms"] <= req.ttft_target_ms:
                cls["ttft_ok"] += 1
            if req.tpot_target_ms is None or \
                    result["tpot_ms"] <= req.tpot_target_ms:
                cls["tpot_ok"] += 1
            self._completed.append({
                "ttft_ms": result["ttft_ms"],
                "tpot_ms": result["tpot_ms"],
                "latency_ms": result["latency_ms"],
                "num_tokens": n,
            })
            self._total_completed += 1
            self._total_tokens += n
            self._ttft_hist.observe(result["ttft_ms"])
            self._tpot_hist.observe(result["tpot_ms"])
            seq = self._total_completed
        # Retire the registration and publish the replay-cache entry
        # under ONE lock hold: a resume landing between "popped from
        # _inflight" and "result visible" would otherwise be admitted
        # as a duplicate decode.
        with self._inflight_lock:
            self._recent_results[request_id] = result
            while len(self._recent_results) > self._recent_results_cap:
                self._recent_results.popitem(last=False)
            self._inflight.pop(request_id, None)
        self._record_request_spans(pending, result, seq)
        return result

    # Span head-sampling: the first _SPAN_HEAD requests record full
    # span chains, then 1-in-_SPAN_SAMPLE_EVERY. The HISTOGRAMS see
    # every request (percentiles are exact); only the per-request
    # span detail is sampled — a long-lived replica at high rate must
    # not grow its JSONL sink and TABLE_TRACE by 4 rows per request
    # forever (the goodput recorder this mirrors is low-rate by
    # nature; serving traffic is not).
    _SPAN_HEAD = 512
    _SPAN_SAMPLE_EVERY = 16

    def _record_request_spans(self, pending: _Pending,
                              result: dict, seq: int) -> None:
        """Per-request trace spans (admit -> queued -> prefill ->
        decode), recorded through the process-local JSONL recorder —
        a no-op outside pool tasks (no $SHIPYARD_TRACE_* context), so
        standalone servers pay nothing."""
        if trace_spans.local_spans_path() is None:
            return
        if seq > self._SPAN_HEAD and seq % self._SPAN_SAMPLE_EVERY:
            return
        request_id = pending.request.request_id
        t0 = pending.submitted_wall

        def wall(perf: Optional[float]) -> float:
            return (t0 if perf is None
                    else t0 + perf - pending.submitted_at)

        parent = trace_spans.record(
            trace_spans.SPAN_SERVE_REQUEST, t0,
            wall(pending.finished_at), request_id=request_id,
            num_tokens=result["num_tokens"],
            ttft_ms=result["ttft_ms"], tpot_ms=result["tpot_ms"])
        if parent is None:
            return
        admitted = wall(pending.admitted_at)
        trace_spans.record(
            trace_spans.SPAN_SERVE_QUEUED, t0, admitted,
            parent_span_id=parent, request_id=request_id)
        first = wall(pending.first_token_at)
        trace_spans.record(
            trace_spans.SPAN_SERVE_PREFILL, admitted, first,
            parent_span_id=parent, request_id=request_id,
            prompt_len=len(pending.request.prompt))
        decode_attrs = {"request_id": request_id,
                        "num_tokens": result["num_tokens"],
                        "tpot_ms": result["tpot_ms"]}
        # Speculative accept/rewind detail rides the decode span
        # (engine-level counters: acceptance is not tracked per
        # request, so this is the engine's running view at
        # completion).
        spec = self.engine.spec_stats()
        if spec is not None:
            decode_attrs["spec_gamma"] = spec["gamma"]
            decode_attrs["spec_acceptance_rate"] = \
                spec["acceptance_rate"]
            decode_attrs["spec_rewinds"] = (
                spec["proposed"] - spec["accepted"])
        trace_spans.record(
            trace_spans.SPAN_SERVE_DECODE, first,
            wall(pending.finished_at), parent_span_id=parent,
            **decode_attrs)

    def submit_stream(self, spec: dict) -> _Pending:
        """Streaming generate, first half: validation happens HERE
        (before any bytes hit the wire) and the request goes to the
        engine; serve_stream() is the reply."""
        pending = self._make_pending(spec, stream=True)
        if not pending.event.is_set():  # pre-satisfied resumes skip
            self._submit_q.put(pending)
        return pending

    def abandon(self, pending: _Pending) -> None:
        """Drop the front-end registration of a stream whose client
        went away before its headers (the engine keeps decoding;
        _engine_active still blocks id reuse meanwhile) and what the
        writer has kept for it."""
        with self._inflight_lock:
            self._inflight.pop(pending.request.request_id, None)
        self._streams.discard(pending.stream)

    def serve_stream(self, pending: _Pending, conn: socket.socket,
                     timeout: float = 300.0) -> bool:
        """The reply of a streaming generate, on the handler thread
        of ``conn`` after its headers: hand the connection to the
        stream writer (which sends a {"token", "index"} line per
        decoded token, the step it is decoded in), wait for the end
        of the run, and hand the writer the last line: the final
        result object (generate()'s payload) or the error / draining
        / shed marker. -> whether the whole reply reached the socket
        (False: the client went away or stopped reading, and the
        stream was dropped)."""
        stream = pending.stream
        request_id = pending.request.request_id
        self._streams.register(stream, conn)
        try:
            try:
                # ``timeout`` bounds the wait for the NEXT token, as
                # the per-line wait did: a run that still emits is
                # alive however long it is.
                emitted = None
                while not stream.wake.wait(timeout):
                    if emitted == pending.emitted:
                        raise TimeoutError(
                            f"request {request_id} timed out after "
                            f"{timeout}s")
                    emitted = pending.emitted
                if stream.dropped is not None:
                    raise BrokenPipeError(stream.dropped)
                self._raise_outcome(pending)
            except BaseException:
                # Error/cancel/vanished-client path retires the
                # registration here; the success path retires it
                # inside _result, atomically with the replay-cache
                # publish (racing-resume guard).
                with self._inflight_lock:
                    self._inflight.pop(request_id, None)
                raise
            last = self._result(pending)
        except BrokenPipeError:
            return False
        except RequestDraining as exc:
            # Mid-stream drain-abandon: the marker tells the router
            # to resume on a sibling rather than surface a failure.
            last = {"error": str(exc), "draining": True}
        except RequestShed as exc:
            last = {"error": str(exc), "shed": True}
        except (ValueError, TimeoutError, RequestCancelled) as exc:
            last = {"error": str(exc)}
        except Exception as exc:  # defensive
            logger.exception("stream failed")
            last = {"error": str(exc)}
        return self._streams.close(stream,
                                   _json_chunk(last) + _STREAM_END)

    def replay_stream(self, result: dict,
                      conn: socket.socket) -> bool:
        """A finished run replayed as a stream (CompletedReplay):
        its token lines and the cached final result, through the
        same writer."""
        stream = _Stream()
        self._streams.register(stream, conn)
        lines = [_json_chunk({"token": token, "index": i})
                 for i, token in enumerate(result["tokens"])]
        lines += [_json_chunk(dict(result, cached=True)), _STREAM_END]
        return self._streams.close(stream, b"".join(lines))

    def _wait_complete(self, pending: _Pending,
                       timeout: float) -> None:
        """Shared completion protocol: wait for the engine to finish
        the run, surface engine-side errors."""
        if not pending.event.wait(timeout):
            raise TimeoutError(
                f"request {pending.request.request_id} timed out "
                f"after {timeout}s")
        self._raise_outcome(pending)

    @staticmethod
    def _raise_outcome(pending: _Pending) -> None:
        """A finished run's engine-side error, as its exception."""
        if pending.draining:
            raise RequestDraining(pending.error)
        if pending.cancelled:
            raise RequestCancelled(pending.error)
        if pending.shed:
            raise RequestShed(pending.error)
        if pending.error is not None:
            raise ValueError(pending.error)

    def prometheus_metrics(self) -> list[str]:
        """Serving metrics in Prometheus exposition format — add this
        front end (or the fleet router) as a scrape target of the
        monitoring stack (docs/09-monitoring.md) to chart TTFT/TPOT
        next to the node-exporter panels."""
        stats = self.stats()
        lines = prometheus_lines("shipyard_serving", {
            "completed_requests_total": stats["completed_requests"],
            "generated_tokens_total": stats["generated_tokens"],
            "tokens_per_second": stats["tokens_per_second"],
            "uptime_seconds": stats["uptime_seconds"],
            "inflight": stats["inflight"],
            "engine_backlog": stats["engine_backlog"],
            "draining": 1.0 if stats["draining"] else 0.0,
            "drain_rejections_total": stats["drain_rejections"],
            "stream_handovers_total": stats["stream_handovers"],
            "stream_tokens_written_total":
                stats["stream_tokens_written"],
            "stream_sends_deferred_total":
                stats["stream_sends_deferred"],
            "streams_dropped_backlog_total":
                stats["streams_dropped_backlog"],
        })
        engine = stats["engine"]
        lines.extend(prometheus_lines("shipyard_serving", {
            "slots_active": engine["slots_active"],
            "queue_depth": engine["queued"],
            "kv_pages_in_use": engine.get("kv_pages_in_use"),
            "kv_pages_total": engine.get("kv_pages_total"),
            # the first chunks a layer's next paged decode call
            # fetches behind another slot's last (a paged engine)
            "kv_first_chunks_prefetched":
                engine.get("kv_first_chunks_prefetched"),
            # the window layers' page group (a model with such layers)
            "window_pages_in_use": engine.get("window_pages_in_use"),
            "window_pages_total": engine.get("window_pages_total"),
            "steps_total": engine["steps"],
            "compiles_total": engine["compiles"],
        }))
        for phase, seconds in engine["phase_seconds"].items():
            lines.extend(prometheus_lines(
                "shipyard_serving",
                {"step_phase_seconds_total": seconds},
                labels={"phase": phase}))
        # The device's timeline beside the host's phases: launches
        # landed, the seconds they held the device's queue and the
        # landings that found the device ahead of the host, by kind.
        for kind, count in engine["launches"].items():
            lines.extend(prometheus_lines("shipyard_serving", {
                "launches_total": count,
                "launch_seconds_total":
                    engine["launch_seconds"][kind],
                "landings_ready_total":
                    engine["landings_ready"][kind],
            }, labels={"kind": kind}))
        lines.extend(prometheus_lines("shipyard_serving", {
            "prefill_bucket_tokens_total":
                engine["prefill_bucket_tokens"],
            "prefill_tokens_total": engine["prefill_tokens"],
            "prefills_total": engine["prefills"],
            "prefills_grouped_total": engine["prefills_grouped"],
            "no_work_seconds_total": engine["no_work_seconds"],
            "stalls_total": engine["stalls"],
            # a model that carries its own drafter, or generates by
            # diffusion over blocks (absent otherwise)
            **{f"{name}_total": engine[name] for name in (
                "mtp_drafted", "mtp_accepted") + BLOCK_COUNTERS
               if name in engine},
        }))
        for metric in ("ttft_ms", "tpot_ms"):
            for pct, value in stats[metric].items():
                lines.extend(prometheus_lines(
                    "shipyard_serving", {metric: value},
                    labels={"quantile": f"0.{pct}"}))
        # Native histogram exposition (cumulative _bucket/_sum/_count)
        # so histogram_quantile() works on the scrape and fleet-level
        # aggregation is sound.
        with self._stats_lock:
            for metric, hist in (("ttft_ms", self._ttft_hist),
                                 ("tpot_ms", self._tpot_hist)):
                lines.extend(hist.prometheus_bucket_lines(
                    f"shipyard_serving_{metric}"))
        spec = stats.get("speculative")
        if spec:
            lines.extend(prometheus_lines("shipyard_serving", {
                "spec_rounds_total": spec["rounds"],
                "spec_proposed_tokens_total": spec["proposed"],
                "spec_accepted_tokens_total": spec["accepted"],
                "spec_acceptance_rate": spec["acceptance_rate"],
            }))
        prefix = stats.get("prefix_cache")
        if prefix:
            lines.extend(prometheus_lines("shipyard_serving", {
                "prefix_hit_rate": prefix["hit_rate"],
                "prefix_hit_tokens_total": prefix["hit_tokens"],
                "prefix_prompt_tokens_total":
                    prefix["total_prompt_tokens"],
                "prefix_indexed_pages": prefix["indexed_pages"],
                "prefix_published_pages_total":
                    prefix["published_pages"],
                "prefix_evictions_total": prefix["evictions"],
            }))
        slo = stats.get("slo") or {}
        lines.extend(prometheus_lines("shipyard_serving", {
            "slo_sheds_total": slo.get("sheds"),
            "slo_deferrals_total": slo.get("deferrals"),
        }))
        for name, counters in (slo.get("classes") or {}).items():
            lines.extend(prometheus_lines(
                "shipyard_serving", {
                    "slo_class_requests_total": counters["requests"],
                    "slo_class_ttft_ok_total": counters["ttft_ok"],
                    "slo_class_tpot_ok_total": counters["tpot_ok"],
                    "slo_class_shed_total": counters["shed"],
                }, labels={"slo_class": name}))
        return lines

    def knows(self, request_id: str) -> bool:
        """Whether this front end currently owns the request (in
        flight or actively decoding)."""
        with self._inflight_lock:
            return (request_id in self._inflight or
                    request_id in self._engine_active)

    def request_status(self, request_id: str) -> Optional[dict]:
        """Progress of one in-flight request — the shared source of
        truth for the router's resubmit probe and its mid-stream
        recovery: phase (queued/prefill/decode/draining) and the
        emitted-token count. None once finished or never seen (the
        404 the router's orphan reconciliation keys on)."""
        with self._inflight_lock:
            pending = self._inflight.get(request_id)
            if pending is None and request_id in self._engine_active:
                # Abandoned stream still decoding: the engine-side
                # run holds the progress.
                pending = self._active_runs.get(request_id)
        if pending is None:
            return None
        if self._draining.is_set():
            phase = "draining"
        elif pending.admitted_at is None:
            phase = "queued"
        elif pending.emitted <= len(pending.resumed or []):
            phase = "prefill"
        else:
            phase = "decode"
        return {"request_id": request_id, "in_flight": True,
                "phase": phase,
                "emitted_tokens": int(pending.emitted)}

    def cancel(self, request_id: str) -> None:
        """Request an abort; the engine thread performs it and the
        waiting client completes with a 'cancelled' error."""
        self._cancel_q.put(request_id)

    def generate(self, spec: dict, timeout: float = 300.0) -> dict:
        """Blocking generate: enqueue to the engine thread, wait for
        completion, return tokens + latency breakdown."""
        pending = self._make_pending(spec)
        if not pending.event.is_set():  # pre-satisfied resumes skip
            self._submit_q.put(pending)
        try:
            self._wait_complete(pending, timeout)
        except BaseException:
            with self._inflight_lock:
                self._inflight.pop(pending.request.request_id, None)
            raise
        return self._result(pending)

    def stats(self) -> dict:
        with self._stats_lock:
            completed = self._total_completed
            tokens = self._total_tokens
            ttft_hist = self._ttft_hist.to_dict()
            tpot_hist = self._tpot_hist.to_dict()
            ttft_pcts = self._ttft_hist.percentiles((50, 90, 99))
            tpot_pcts = self._tpot_hist.percentiles((50, 90, 99))
            class_stats = {name: dict(counters) for name, counters
                           in self._class_stats.items()}
        elapsed = time.perf_counter() - self._started_at
        with self._inflight_lock:
            inflight = len(self._inflight)
        out = {
            "completed_requests": completed,
            "generated_tokens": tokens,
            "uptime_seconds": elapsed,
            "tokens_per_second": tokens / elapsed if elapsed else 0.0,
            # Percentiles come from the fixed-bucket histograms (the
            # same numbers any fleet-level merge reproduces), keyed
            # p50/p90/p99; the raw bucket counts ride along so the
            # router can merge replicas losslessly.
            "ttft_ms": {p: ttft_pcts[f"p{p}"] for p in (50, 90, 99)},
            "tpot_ms": {p: tpot_pcts[f"p{p}"] for p in (50, 90, 99)},
            "ttft_hist": ttft_hist,
            "tpot_hist": tpot_hist,
            # Router observability (models/router.py polls these):
            # requests this front end has accepted but not completed,
            # and the engine's queued+active total.
            "inflight": inflight,
            "engine_backlog": self.engine.pending(),
            # The engine from inside: slots and KV pages in use now
            # (size --kv-num-pages by pages-in-use against
            # slots-active, docs/15-serving.md), and where a step's
            # time has gone since the engine was built.
            "engine": self._engine_stats(),
            # Drain ladder visibility: the router's probe reads
            # "draining" to distinguish cooperative shutdown from
            # failure.
            "draining": self._draining.is_set(),
            "drain_rejections": self.drain_rejections,
            # The stream writer: token batches it took (one a landed
            # step, one a first token), token lines it wrote, sends a
            # client's window did not take whole, streams dropped for
            # what they owed. tokens / handovers = streams fed a
            # wake-up.
            "stream_handovers": self._streams.handovers,
            "stream_tokens_written": self._streams.tokens_written,
            "stream_sends_deferred": self._streams.sends_deferred,
            "streams_dropped_backlog": self._streams.dropped_backlog,
        }
        # Speculative-decode counters when the engine runs a draft
        # model (the measured acceptance rate is the tuning signal
        # for gamma and draft sizing; the router aggregates these
        # fleet-wide).
        spec = self.engine.spec_stats()
        if spec is not None:
            out["speculative"] = spec
        # Request-level SLO scheduling: per-class attainment plus the
        # engine's shed/deferral counters and live cost estimates.
        engine_slo = self.engine.slo_stats()
        out["slo"] = {
            "classes": {
                name: dict(
                    counters,
                    targets=self.slo_classes.get(name),
                    ttft_attainment=(
                        counters["ttft_ok"] / counters["requests"]
                        if counters["requests"] else None),
                    tpot_attainment=(
                        counters["tpot_ok"] / counters["requests"]
                        if counters["requests"] else None))
                for name, counters in class_stats.items()},
            **engine_slo,
        }
        # Prefix-cache effectiveness (None when the engine runs
        # dense or with the cache disabled); the router aggregates
        # hit_tokens/total_prompt_tokens fleet-wide.
        prefix = self.engine.prefix_stats()
        if prefix is not None:
            out["prefix_cache"] = prefix
        return out

    def _engine_stats(self) -> dict:
        occupancy = self.engine.occupancy()
        steps = self.engine.step_stats()
        count = steps["steps"]
        per_step = 1e3 / count if count else 0.0
        return {
            **occupancy,
            "steps": count,
            "decode_steps": steps["decode_steps"],
            "steps_overlapped": steps["steps_overlapped"],
            "prefills": steps["prefills"],
            "prefills_overlapped": steps["prefills_overlapped"],
            "settles": steps["settles"],
            "overshoot_tokens": steps["overshoot_tokens"],
            # the device's timeline from the engine's own landings
            # (ContinuousBatcher._launch_counts)
            **{name: steps[name] for name in (
                "launches", "launch_seconds", "landings_ready",
                "prefill_bucket_tokens", "prefill_tokens",
                "prefills_grouped", "no_work_seconds", "stalls")},
            # a routed model's expert counters (absent otherwise)
            **{name: steps[name] for name in (
                "expert_pairs_here", "expert_pairs_chosen",
                "experts_hit") if name in steps},
            # a drafting model's counters, a block-diffusion model's
            # (absent otherwise)
            **{name: steps[name] for name in (
                "mtp_drafted", "mtp_accepted") + BLOCK_COUNTERS
               if name in steps},
            "step_ms_mean": steps["step_seconds"] * per_step,
            "phase_ms_mean": {
                name: seconds * per_step
                for name, seconds in steps["phase_seconds"].items()},
            "phase_seconds": steps["phase_seconds"],
            "compiles": steps["compiles"],
            "compile_seconds": steps["compile_seconds"],
        }

    # --------------------------- engine thread -------------------------

    def _on_admit(self, request_id: str) -> None:
        # Engine-thread hook (inside engine.step's _admit): stamps
        # the queued -> prefill boundary of the request's span chain.
        pending = self._active_runs.get(request_id)
        if pending is not None and pending.admitted_at is None:
            pending.admitted_at = time.perf_counter()

    def _on_shed(self, request_id: str, reason: str) -> None:
        # Engine-thread hook (inside engine.step's _shed_expired):
        # the engine dropped a queued request under overload —
        # complete its waiter as shed (503) and count it against its
        # class's attainment.
        pending = self._active_runs.pop(request_id, None)
        with self._inflight_lock:
            self._engine_active.discard(request_id)
        if pending is None:
            return
        with self._stats_lock:
            cls = self._class_stats.setdefault(
                pending.request.slo_class,
                {"requests": 0, "ttft_ok": 0, "tpot_ok": 0,
                 "shed": 0})
            cls["shed"] += 1
        pending.error = f"request {request_id} shed: {reason}"
        pending.shed = True
        pending.finished_at = time.perf_counter()
        pending.finish()

    def _on_token(self, request_id: str, token: int, index: int) -> None:
        self._on_tokens([(request_id, token, index)])

    def _on_tokens(self, batch: list) -> None:
        # One call a landed step (or a prefill's first token) with
        # its (request_id, token, index) triples. _active_runs is
        # engine-thread-owned and this hook runs on the engine thread
        # (inside engine.step) — no lock needed, and completions can
        # never be attributed to a retried request's NEW pending
        # while the old run still decodes.
        runs = self._active_runs
        lines = []
        now = None
        for request_id, token, index in batch:
            pending = runs.get(request_id)
            if pending is None:
                continue
            if pending.first_token_at is None:
                # First token THIS replica produced — for a resumed
                # run that is the re-prefill completion (index > 0),
                # still the TTFT that matters here.
                if now is None:
                    now = time.perf_counter()
                pending.first_token_at = now
            if index >= pending.emitted:
                pending.emitted = index + 1
            stream = pending.stream
            if stream is not None and stream.dropped is None:
                lines.append((stream, token, index))
        if lines:
            # ONE wake-up for all the step's streams.
            self._streams.write(lines)

    def _engine_loop(self) -> None:
        while not self._stop.is_set():
            # Park only when fully idle; with active slots the loop
            # must spin at full decode rate — a blocking get here
            # would throttle every active request's TPOT.
            if not self.engine.pending():
                try:
                    with self._waiting("no_work"):
                        item = self._submit_q.get(timeout=0.2)
                    self._submit(item)
                except queue.Empty:
                    # Idle: the last rows of a burst would otherwise
                    # sit in the recorder's buffer until the next one.
                    trace_spans.flush()
            while True:
                try:
                    self._submit(self._submit_q.get_nowait())
                except queue.Empty:
                    break
            while True:
                try:
                    self._cancel(self._cancel_q.get_nowait())
                except queue.Empty:
                    break
            if self._draining.is_set():
                self._drain_tick()
            if not self.engine.pending():
                continue
            try:
                finished = self.engine.step()
            except Exception:
                logger.exception("engine step failed")
                if self.engine.cache_lost():
                    # The step programs consume the cache they are
                    # given: a step that raised after that leaves
                    # nothing to decode from, and every further step
                    # would fail the same way. Leave rotation as a
                    # draining replica does, with no grace: healthz
                    # 503, the queue evicted and the active decodes
                    # abandoned with the marker the router resumes
                    # from on a sibling.
                    self.drain(grace_s=0.0,
                               reason="KV cache lost in a failed step")
                    # An earlier drain's grace is void as well.
                    self._drain_deadline = time.perf_counter()
                    self._drain_tick()
                continue
            now = time.perf_counter()
            for request_id, tokens in finished:
                pending = self._active_runs.pop(request_id, None)
                with self._inflight_lock:
                    self._engine_active.discard(request_id)
                if pending is None:
                    continue
                pending.tokens = tokens
                pending.finished_at = now
                pending.finish()

    def _drain_tick(self) -> None:
        # Engine-thread side of the drain ladder: evict the queue
        # once (those waiters fail over immediately — they hold no
        # pages and no progress), then let active decodes run until
        # the grace deadline, after which they are abandoned with a
        # draining marker the router resumes from.
        if not self._drain_engine_done:
            for request_id in self.engine.drain():
                self._complete_draining(
                    request_id, "queued work evicted at drain")
            self._drain_engine_done = True
        if self._drain_deadline is not None and \
                time.perf_counter() >= self._drain_deadline:
            for request_id in self.engine.active_request_ids():
                self._cancel(request_id, draining=True)

    def _complete_draining(self, request_id: str, why: str) -> None:
        pending = self._active_runs.pop(request_id, None)
        with self._inflight_lock:
            self._engine_active.discard(request_id)
        if pending is None:
            return
        pending.error = f"request {request_id} draining: {why}"
        pending.draining = True
        pending.finished_at = time.perf_counter()
        pending.finish()

    def _cancel(self, request_id: str,
                draining: bool = False) -> None:
        if not self.engine.cancel(request_id):
            return  # unknown/already finished
        pending = self._active_runs.pop(request_id, None)
        with self._inflight_lock:
            self._engine_active.discard(request_id)
        if pending is None:
            return
        if draining:
            pending.error = (f"request {request_id} draining "
                             f"({self._drain_reason}): grace "
                             f"deadline, decode abandoned")
            pending.draining = True
        else:
            pending.error = f"request {request_id} cancelled"
            pending.cancelled = True
        pending.finished_at = time.perf_counter()
        pending.finish()

    def _submit(self, pending: _Pending) -> None:
        if self._draining.is_set() or self.engine.draining:
            # Drain ladder: requests already queued toward the engine
            # when the notice landed must not be admitted — complete
            # their waiters as draining so the router fails over.
            request_id = pending.request.request_id
            pending.error = (f"request {request_id} draining: not "
                             f"admitted, replica shutting down")
            pending.draining = True
            pending.finished_at = time.perf_counter()
            pending.finish()
            return
        try:
            self.engine.submit(pending.request,
                               resumed=pending.resumed)
        except ValueError as exc:
            pending.error = str(exc)
            pending.finished_at = time.perf_counter()
            pending.finish()
            return
        request_id = pending.request.request_id
        self._active_runs[request_id] = pending
        with self._inflight_lock:
            self._engine_active.add(request_id)
