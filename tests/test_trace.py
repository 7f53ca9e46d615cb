"""End-to-end distributed tracing + on-demand profiling (trace/):
context propagation from `jobs add` through claim/backoff/rendezvous
to program spans, Perfetto export with consistent parent links,
mergeable latency histograms behind the serving percentiles, heimdall
bucket export with the node-staleness rule, and the `jobs profile`
store-flag flow."""

import json
import os
import time
import types

import pytest

from batch_shipyard_tpu.config import settings as settings_mod
from batch_shipyard_tpu.goodput import accounting
from batch_shipyard_tpu.goodput import events as gp
from batch_shipyard_tpu.jobs import manager as jobs_mgr
from batch_shipyard_tpu.pool import manager as pool_mgr
from batch_shipyard_tpu.state import names
from batch_shipyard_tpu.state.memory import MemoryStateStore
from batch_shipyard_tpu.trace import context as trace_ctx
from batch_shipyard_tpu.trace import export as trace_export
from batch_shipyard_tpu.trace import profiling as trace_prof
from batch_shipyard_tpu.trace import spans as trace_spans
from batch_shipyard_tpu.trace.histogram import (BUCKET_EDGES_MS,
                                                LatencyHistogram)

GLOBAL = settings_mod.global_settings({})
REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---------------------------- histograms -------------------------------

def test_histogram_percentiles_monotone_and_clamped():
    hist = LatencyHistogram.of([1.0, 2.0, 4.0, 8.0, 50.0, 400.0])
    p50, p90, p99 = (hist.percentile(p) for p in (50, 90, 99))
    assert p50 <= p90 <= p99
    assert hist.min <= p50 and p99 <= hist.max
    assert hist.count == 6
    assert hist.mean() == pytest.approx(465.0 / 6)
    assert LatencyHistogram().percentile(50) == 0.0


def test_histogram_merge_is_lossless_and_order_free():
    a = LatencyHistogram.of([1, 5, 9, 100])
    b = LatencyHistogram.of([2000.0, 3.0])
    ab = LatencyHistogram.merged([a, b])
    ba = LatencyHistogram.merged([b, a])
    direct = LatencyHistogram.of([1, 5, 9, 100, 2000.0, 3.0])
    assert ab.counts == ba.counts == direct.counts
    assert ab.count == 6 and ab.total == direct.total
    assert ab.min == direct.min and ab.max == direct.max
    for p in (50, 90, 99):
        assert ab.percentile(p) == direct.percentile(p)


def test_histogram_wire_round_trip_and_junk_rejection():
    hist = LatencyHistogram.of([0.1, 77.0, 3e6])
    assert hist.overflow == 1  # 3e6 ms is past the ~35min ladder top
    back = LatencyHistogram.from_dict(hist.to_dict())
    assert back.counts == hist.counts
    assert back.overflow == 1 and back.count == 3
    assert LatencyHistogram.from_dict(None) is None
    assert LatencyHistogram.from_dict({"counts": [1, 2]}) is None
    foreign = hist.to_dict()
    foreign["edges_ms"] = [1.0, 2.0]
    assert LatencyHistogram.from_dict(foreign) is None


def test_histogram_prometheus_bucket_lines_cumulative():
    hist = LatencyHistogram.of([0.2, 0.2, 3.0])
    lines = hist.prometheus_bucket_lines("m", {"pool": "p"})
    assert f'm_bucket{{pool="p",le="{BUCKET_EDGES_MS[0]:g}"}} 2' \
        in lines
    assert 'm_bucket{pool="p",le="+Inf"} 3' in lines
    assert 'm_count{pool="p"} 3' in lines
    # Cumulative counts never decrease.
    counts = [int(line.rsplit(" ", 1)[1]) for line in lines
              if "_bucket" in line]
    assert counts == sorted(counts)


# ------------------------- context + recorders -------------------------

def test_context_child_entity_and_env_round_trips(monkeypatch):
    root = trace_ctx.TraceContext.new()
    child = root.child()
    assert child.trace_id == root.trace_id
    assert child.parent_span_id == root.span_id
    entity = dict(child.entity_columns())
    again = trace_ctx.TraceContext.from_entity(entity)
    assert again == child
    assert trace_ctx.TraceContext.from_entity({"state": "x"}) is None
    for key, value in child.env().items():
        monkeypatch.setenv(key, value)
    from_env = trace_ctx.TraceContext.from_env()
    assert from_env.trace_id == child.trace_id
    assert from_env.span_id == child.span_id
    monkeypatch.delenv(trace_ctx.TRACE_ID_ENV)
    assert trace_ctx.TraceContext.from_env() is None


def test_store_emit_query_and_prune():
    store = MemoryStateStore()
    ctx = trace_ctx.TraceContext.new()
    sid = trace_spans.emit(store, "p1", trace_spans.SPAN_SUBMIT, ctx,
                           job_id="j1", start=10.0, end=11.0,
                           self_span=True)
    assert sid == ctx.span_id
    child = trace_spans.emit(store, "p1", trace_spans.SPAN_CLAIM, ctx,
                             job_id="j1", start=12.0, end=12.0)
    assert child is not None and child != ctx.span_id
    # Unknown kinds and missing contexts are dropped, never raised.
    assert trace_spans.emit(store, "p1", "nope", ctx) is None
    assert trace_spans.emit(store, "p1", trace_spans.SPAN_CLAIM,
                            None) is None
    rows = trace_spans.query(store, "p1", trace_id=ctx.trace_id)
    assert [r["kind"] for r in rows] == ["submit", "claim"]
    assert rows[1]["parent_span_id"] == ctx.span_id
    assert trace_spans.query(store, "p1", trace_id="other") == []
    removed = trace_spans.prune(store, "p1",
                                older_than_seconds=0.0)
    assert removed == 2
    assert trace_spans.query(store, "p1") == []


def test_local_recorder_and_ingest(tmp_path, monkeypatch):
    path = str(tmp_path / "spans.jsonl")
    ctx = trace_ctx.TraceContext.new()
    # No env -> no-op.
    assert trace_spans.record(trace_spans.SPAN_COMPILE, 1.0) is None
    monkeypatch.setenv(trace_ctx.TRACE_FILE_ENV, path)
    for key, value in ctx.env().items():
        monkeypatch.setenv(key, value)
    sid = trace_spans.record(trace_spans.SPAN_COMPILE, 1.0, 2.0,
                             what="warmup")
    assert sid is not None
    with trace_spans.phase(trace_spans.SPAN_CKPT_SNAPSHOT,
                           step=4) as attrs:
        attrs["extra"] = 1
    trace_spans.flush()     # the second span waited in the buffer
    # Junk lines must be skipped by the ingest, not raised.
    with open(path, "a", encoding="utf-8") as fh:
        fh.write("not json\n")
        fh.write(json.dumps({"kind": "undeclared", "trace_id": "x",
                             "span_id": "y", "start": 1}) + "\n")
        fh.write(json.dumps({"kind": "compile"}) + "\n")
    store = MemoryStateStore()
    count = trace_spans.ingest_local_spans(
        store, "p1", path, job_id="j1", task_id="t1", node_id="n1")
    assert count == 2
    assert not os.path.exists(path)
    rows = trace_spans.query(store, "p1", trace_id=ctx.trace_id)
    assert {r["kind"] for r in rows} == {"compile",
                                         "checkpoint_snapshot"}
    for row in rows:
        assert row["parent_span_id"] == ctx.span_id
        assert row["task_id"] == "t1" and row["node_id"] == "n1"
    snap = next(r for r in rows
                if r["kind"] == "checkpoint_snapshot")
    assert snap["attrs"]["step"] == 4 and snap["attrs"]["extra"] == 1


def _spans_env(monkeypatch, path):
    ctx = trace_ctx.TraceContext.new()
    monkeypatch.setenv(trace_ctx.TRACE_FILE_ENV, str(path))
    for key, value in ctx.env().items():
        monkeypatch.setenv(key, value)
    trace_spans.flush()         # nothing of an earlier test waiting
    return ctx


def _lines(path):
    if not os.path.exists(path):
        return []
    with open(path, encoding="utf-8") as fh:
        return [json.loads(line) for line in fh]


def test_local_recorder_buffers_and_writes_in_batches(tmp_path,
                                                      monkeypatch):
    """A span after a quiet spell goes out at once; a stream of them
    waits for the interval, the row cap or flush(), and a batch is
    ONE append however many rows it holds."""
    path = tmp_path / "spans.jsonl"
    _spans_env(monkeypatch, path)
    monkeypatch.setattr(trace_spans, "_last_flush", float("-inf"))
    opens = []
    real_open = open

    def counting_open(file, *args, **kwargs):
        if str(file) == str(path) and args and args[0] == "a":
            opens.append(file)
        return real_open(file, *args, **kwargs)

    monkeypatch.setattr("builtins.open", counting_open)
    trace_spans.record(trace_spans.SPAN_COMPILE, 1.0, 2.0)
    assert len(_lines(path)) == 1 and len(opens) == 1
    for k in range(50):
        trace_spans.record(trace_spans.SPAN_SERVE_STEP, 2.0 + k,
                           3.0 + k, k=k)
    assert len(_lines(path)) == 1 and len(opens) == 1   # buffered
    assert trace_spans.flush() == 50
    assert len(opens) == 2
    assert [r["attrs"]["k"] for r in _lines(path)[1:]] == \
        list(range(50))
    assert trace_spans.flush() == 0 and len(opens) == 2
    # the interval has passed: the next span takes the waiting ones
    trace_spans.record(trace_spans.SPAN_SERVE_STEP, 60.0, k=50)
    monkeypatch.setattr(trace_spans, "_last_flush", float("-inf"))
    trace_spans.record(trace_spans.SPAN_SERVE_STEP, 61.0, k=51)
    assert len(_lines(path)) == 53 and len(opens) == 3
    # bounded memory: the cap flushes whatever the clock says
    monkeypatch.setattr(trace_spans, "MAX_BUFFERED_ROWS", 8)
    for k in range(8):
        trace_spans.record(trace_spans.SPAN_SERVE_STEP, 70.0 + k)
    assert len(_lines(path)) == 61 and len(opens) == 4
    assert not trace_spans._buffer


def test_buffered_rows_survive_the_agents_rename_drain(tmp_path,
                                                       monkeypatch):
    """The heartbeat drain renames the live file away between two
    batches: both batches are ingested, none twice, and the second
    re-creates the path."""
    from batch_shipyard_tpu.agent.node_agent import NodeAgent
    path = tmp_path / "trace_spans.jsonl"
    ctx = _spans_env(monkeypatch, path)
    store = MemoryStateStore()
    agent = types.SimpleNamespace(
        store=store, identity=types.SimpleNamespace(
            pool_id="p1", node_id="n1"))
    for k in range(3):
        trace_spans.record(trace_spans.SPAN_SERVE_STEP, 1.0 + k, k=k)
    trace_spans.flush()
    assert NodeAgent._drain_trace_file(agent, str(path), "j", "t") == 3
    assert not path.exists()
    for k in range(3, 5):
        trace_spans.record(trace_spans.SPAN_SERVE_STEP, 1.0 + k, k=k)
    assert NodeAgent._drain_trace_file(agent, str(path), "j", "t") \
        in (0, 1)               # at most the write-through row yet
    trace_spans.flush()
    NodeAgent._drain_trace_file(agent, str(path), "j", "t")
    rows = trace_spans.query(store, "p1", trace_id=ctx.trace_id)
    assert sorted(r["attrs"]["k"] for r in rows) == [0, 1, 2, 3, 4]


def test_buffered_rows_are_written_when_the_process_exits(tmp_path):
    import subprocess
    import sys
    path = tmp_path / "spans.jsonl"
    code = (
        "from batch_shipyard_tpu.trace import spans\n"
        "for k in range(20):\n"
        "    spans.record(spans.SPAN_SERVE_STEP, 1.0 + k, k=k)\n"
        "assert len(spans._buffer) == 19\n")
    env = dict(os.environ, SHIPYARD_TRACE_FILE=str(path),
               SHIPYARD_TRACE_ID="t1", SHIPYARD_TRACE_SPAN_ID="s1",
               PYTHONPATH=REPO_ROOT)
    done = subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=120)
    assert done.returncode == 0, done.stderr[-2000:]
    assert [r["attrs"]["k"] for r in _lines(path)] == list(range(20))


def test_phase_timer_sums_leaf_phases_per_step_and_in_total():
    timer = trace_spans.PhaseTimer("test:", ("a", "b"))
    assert timer.total == {"a": 0.0, "b": 0.0}
    t0 = time.monotonic()
    with timer("a"):
        time.sleep(0.01)
    with timer("b"):
        pass
    with timer("a"):
        time.sleep(0.01)
    wall = time.monotonic() - t0
    assert set(timer.step) == {"a", "b"}
    assert 0.02 <= timer.step["a"] and sum(timer.step.values()) <= wall
    first = dict(timer.step)
    timer.reset()
    assert timer.step == {} and timer.total == first
    with pytest.raises(KeyError):       # an undeclared phase is a bug
        with timer("c"):
            pass


def test_serve_step_rows_export_on_a_track_of_the_task():
    rows = {"spans": [
        {"kind": "task_run", "trace_id": "t", "span_id": "run",
         "parent_span_id": None, "start": 0.0, "end": 9.0,
         "task_id": "serve-0", "node_id": "n1", "attrs": {}},
        {"kind": "serve_step", "trace_id": "t", "span_id": "s1",
         "parent_span_id": "run", "start": 1.0, "end": 1.05,
         "task_id": "serve-0", "node_id": "n1",
         "attrs": {"prefill_ms": 30.0, "slots_active": 3}},
        {"kind": "serve_prefill", "trace_id": "t", "span_id": "p1",
         "parent_span_id": "run", "start": 1.0, "end": 1.04,
         "task_id": "serve-0", "node_id": "n1",
         "attrs": {"request_id": "r1"}}], "goodput": []}
    chrome = trace_export.to_chrome_trace(rows, "t")
    assert trace_export.validate_parent_links(chrome) == []
    by_name = {e["name"]: e for e in chrome["traceEvents"]}
    assert by_name["serve_step"]["tid"] == "serve-0 engine steps"
    assert by_name["serve_step"]["args"]["prefill_ms"] == 30.0
    assert by_name["serve_prefill"]["tid"] == "request r1"
    assert "serve_step" in trace_export.render_tree(rows)


def test_goodput_record_attaches_trace_ids(tmp_path, monkeypatch):
    ctx = trace_ctx.TraceContext.new()
    gfile = str(tmp_path / "goodput.jsonl")
    monkeypatch.setenv(gp.GOODPUT_FILE_ENV, gfile)
    for key, value in ctx.env().items():
        monkeypatch.setenv(key, value)
    gp.record(gp.PROGRAM_STEP_WINDOW, 1.0, 2.0, step_start=0,
              step_end=4, tokens=32)
    store = MemoryStateStore()
    assert gp.ingest_local_events(store, "p1", gfile, job_id="j1",
                                  task_id="t1") == 1
    events = gp.query(store, "p1", trace_id=ctx.trace_id)
    assert len(events) == 1
    assert events[0]["span_id"] == ctx.span_id
    # Legacy rows (no trace id) don't match a trace filter.
    gp.emit(store, "p1", gp.TASK_QUEUED, job_id="j1", start=1.0,
            end=2.0)
    assert len(gp.query(store, "p1", trace_id=ctx.trace_id)) == 1
    assert len(gp.query(store, "p1")) == 2


# ------------------------------- export --------------------------------

def test_export_chrome_trace_and_parent_validation():
    store = MemoryStateStore()
    root = trace_ctx.TraceContext.new()
    trace_spans.emit(store, "p1", trace_spans.SPAN_SUBMIT, root,
                     job_id="j1", start=10.0, end=10.5,
                     self_span=True)
    task = root.child()
    trace_spans.emit(store, "p1", trace_spans.SPAN_TASK_RUN, task,
                     job_id="j1", task_id="t1", node_id="n1",
                     start=11.0, end=15.0, self_span=True)
    trace_spans.emit(store, "p1", trace_spans.SPAN_QUEUE_WAIT, task,
                     job_id="j1", task_id="t1", node_id="n1",
                     start=10.5, end=11.0)
    gp.emit(store, "p1", gp.PROGRAM_STEP_WINDOW, job_id="j1",
            task_id="t1", node_id="n1", start=12.0, end=14.0,
            attrs={"step_start": 0, "step_end": 8},
            trace_id=root.trace_id, span_id=task.span_id)
    chrome = trace_export.export_trace(store, "p1", root.trace_id)
    events = chrome["traceEvents"]
    assert {e["name"] for e in events} == {
        "submit", "task_run", "queue_wait", "step_window"}
    assert chrome["otherData"]["spans"] == 3
    assert chrome["otherData"]["goodput_events"] == 1
    # Microsecond complete events, sorted by ts, tracked per node.
    assert events == sorted(events, key=lambda e: e["ts"])
    run = next(e for e in events if e["name"] == "task_run")
    assert run["ph"] == "X" and run["pid"] == "n1"
    assert run["dur"] == pytest.approx(4e6)
    assert trace_export.validate_parent_links(chrome) == []
    # A dangling parent is flagged.
    orphan = trace_ctx.TraceContext(root.trace_id, "aaaa", "missing")
    trace_spans.emit(store, "p1", trace_spans.SPAN_CLAIM, orphan,
                     job_id="j1", start=11.0, self_span=True)
    chrome = trace_export.export_trace(store, "p1", root.trace_id)
    assert trace_export.validate_parent_links(chrome)
    tree = trace_export.render_tree(
        trace_export.trace_rows(store, "p1", root.trace_id))
    assert "submit" in tree and "task_run" in tree


# ------------------------------ profiling ------------------------------

def test_step_profiler_capture_flow(tmp_path, monkeypatch):
    calls = []
    import jax
    monkeypatch.setattr(jax.profiler, "start_trace",
                        lambda d: calls.append(("start", d)))
    monkeypatch.setattr(jax.profiler, "stop_trace",
                        lambda: calls.append(("stop",)))
    request = str(tmp_path / "req.json")
    profile_dir = str(tmp_path / "prof")
    spans_file = str(tmp_path / "spans.jsonl")
    ctx = trace_ctx.TraceContext.new()
    monkeypatch.setenv(trace_ctx.TRACE_FILE_ENV, spans_file)
    for key, value in ctx.env().items():
        monkeypatch.setenv(key, value)
    profiler = trace_prof.StepProfiler(request_path=request,
                                       profile_dir=profile_dir)
    profiler.tick(0)
    assert not profiler.active and not calls
    trace_prof.write_request(request, steps=2)
    profiler.tick(1)
    assert profiler.active
    assert not os.path.exists(request)  # consumed: one request, one
    profiler.tick(2)                    # capture
    assert profiler.active
    profiler.tick(3)
    assert not profiler.active
    assert calls == [("start", profile_dir), ("stop",)]
    trace_spans.flush()
    with open(spans_file, encoding="utf-8") as fh:
        spans = [json.loads(line) for line in fh]
    assert spans[-1]["kind"] == trace_spans.SPAN_PROFILE
    assert spans[-1]["attrs"]["step_start"] == 1
    assert spans[-1]["attrs"]["step_end"] == 3
    # close() stops a capture cut short by loop exit.
    trace_prof.write_request(request, steps=100)
    profiler.tick(4)
    assert profiler.active
    profiler.close()
    assert not profiler.active and calls[-1] == ("stop",)


def test_step_profiler_broken_profiler_disarms(tmp_path,
                                               monkeypatch):
    import jax

    def boom(_):
        raise RuntimeError("no profiler here")

    monkeypatch.setattr(jax.profiler, "start_trace", boom)
    request = str(tmp_path / "req.json")
    trace_prof.write_request(request, steps=3)
    profiler = trace_prof.StepProfiler(
        request_path=request, profile_dir=str(tmp_path / "p"))
    profiler.tick(0)  # must not raise into the step loop
    assert not profiler.active
    trace_prof.write_request(request, steps=3)
    profiler.tick(1)  # broken: stays disarmed, doesn't retry forever
    assert not profiler.active


# ---------------- serving percentiles + heimdall buckets ---------------

def test_serving_percentiles_merge_and_heimdall_buckets(tmp_path,
                                                        monkeypatch):
    """The serving acceptance run: loadgen against two replicas
    produces monotone p50 <= p90 <= p99 TTFT/TPOT from MERGED
    per-replica histograms (loadgen report, server stats, router
    aggregation agree on the rule), the fronts record per-request
    trace spans, and heimdall turns those spans into Prometheus
    ``_bucket`` lines — excluding spans from stale nodes."""
    import jax
    import jax.numpy as jnp

    from batch_shipyard_tpu.models import loadgen, serving
    from batch_shipyard_tpu.models import transformer as tfm
    from batch_shipyard_tpu.models.router import ServingRouter
    from batch_shipyard_tpu.models.server import ServingFrontEnd
    from batch_shipyard_tpu.monitor import heimdall

    ctx = trace_ctx.TraceContext.new()
    spans_file = str(tmp_path / "serve_spans.jsonl")
    monkeypatch.setenv(trace_ctx.TRACE_FILE_ENV, spans_file)
    for key, value in ctx.env().items():
        monkeypatch.setenv(key, value)

    cfg = tfm.TransformerConfig(
        vocab_size=97, d_model=32, n_layers=2, n_heads=2, d_head=16,
        d_ff=64, max_seq_len=64, dtype=jnp.float32,
        param_dtype=jnp.float32)
    params = tfm.TransformerLM(cfg).init(
        jax.random.PRNGKey(7), jnp.zeros((1, 8), jnp.int32))["params"]
    fronts = [ServingFrontEnd(
        serving.ContinuousBatcher(cfg, params, num_slots=2,
                                  max_decode_len=64),
        port=0).start() for _ in range(2)]
    router = None
    try:
        report = loadgen.run_load(
            [f.url for f in fronts], num_requests=12, rate_hz=50.0,
            prompt_len=(2, 8), max_new_tokens=(2, 6), vocab_size=97,
            seed=11)
        assert report["completed"] == 12 and report["failed"] == 0
        for metric in ("ttft_ms", "tpot_ms"):
            pcts = report[metric]
            assert pcts["p50"] <= pcts["p90"] <= pcts["p99"]
        assert report["ttft_hist"]["count"] == 12

        # Server-side per-replica histograms merge losslessly to the
        # same fleet totals.
        merged = LatencyHistogram.merged(
            LatencyHistogram.from_dict(f.stats()["ttft_hist"])
            for f in fronts)
        assert merged.count == 12
        assert merged.percentile(50) <= merged.percentile(90) <= \
            merged.percentile(99)
        # Each front exposes native _bucket exposition.
        front_text = "\n".join(fronts[0].prometheus_metrics())
        assert "shipyard_serving_ttft_ms_bucket{" in front_text
        assert "shipyard_serving_tpot_ms_count" in front_text

        # Router aggregation: merged-histogram percentiles fleet-wide.
        router = ServingRouter([f.url for f in fronts],
                               health_interval=0.2).start()
        deadline = time.monotonic() + 15
        stats = {}
        while time.monotonic() < deadline:
            stats = router.stats()
            if stats.get("ttft_ms"):
                break
            time.sleep(0.1)
        assert stats.get("ttft_hist", {}).get("count") == 12
        assert stats["ttft_ms"][50] <= stats["ttft_ms"][90] <= \
            stats["ttft_ms"][99]
        router_text = "\n".join(router.prometheus_metrics())
        assert "shipyard_router_ttft_ms_bucket{" in router_text
    finally:
        if router is not None:
            router.shutdown()
        for front in fronts:
            front.shutdown()

    # The fronts recorded per-request span chains through the
    # process-local recorder; heimdall rebuilds the pool's latency
    # histogram from them, honoring the node-staleness rule.
    store = MemoryStateStore()
    store.insert_entity(names.TABLE_POOLS, "pools", "spool",
                        {"state": "ready"})
    now = time.time()
    store.insert_entity(names.TABLE_NODES, "spool", "node-a",
                        {"state": "idle", "heartbeat_at": now})
    store.insert_entity(names.TABLE_NODES, "spool", "node-b",
                        {"state": "idle",
                         "heartbeat_at": now - 9999.0})
    count = trace_spans.ingest_local_spans(
        store, "spool", spans_file, job_id="jserve",
        task_id="t0", node_id="node-a")
    assert count >= 12 * 4  # request + queued + prefill + decode
    # A crashed replica's spans (stale node-b) must not export.
    trace_spans.emit(
        store, "spool", trace_spans.SPAN_SERVE_REQUEST, ctx,
        job_id="jserve", task_id="t1", node_id="node-b",
        start=now - 10, end=now,
        attrs={"request_id": "ghost", "ttft_ms": 1e6,
               "tpot_ms": 1e6, "num_tokens": 1})
    gp.emit(store, "spool", gp.PROGRAM_STEP_WINDOW, job_id="jserve",
            node_id="node-a", start=now - 8, end=now - 4,
            attrs={"step_start": 0, "step_end": 8})
    gp.emit(store, "spool", gp.PROGRAM_STEP_WINDOW, job_id="jserve",
            node_id="node-b", start=now - 8, end=now - 4,
            attrs={"step_start": 0, "step_end": 8})
    lines = heimdall.build_goodput_metrics(store)
    text = "\n".join(lines)
    assert 'shipyard_serving_ttft_ms_bucket{le=' not in text  # labeled
    assert 'shipyard_serving_ttft_ms_count{pool="spool"} 12' in text
    assert 'shipyard_serving_tpot_ms_bucket{' in text
    # node-a's last-step gauge exports; stale node-b's does not.
    assert 'node_last_step_seconds{node="node-a",pool="spool"} ' \
        '0.500000' in text
    assert 'node="node-b"' not in text


# ---------------------------- fakepod e2e ------------------------------

@pytest.fixture()
def fakepod_env():
    from batch_shipyard_tpu.substrate.fakepod import FakePodSubstrate
    conf = {"pool_specification": {
        "id": "pool1", "substrate": "fake",
        "tpu": {"accelerator_type": "v5litepod-16", "num_slices": 1},
        "task_slots_per_node": 1,
        "max_wait_time_seconds": 30,
    }}
    store = MemoryStateStore()
    substrate = FakePodSubstrate(store)
    # Keep the injected retry's backoff short so the e2e stays fast.
    substrate.agent_kwargs = {"retry_backoff_base": 0.4}
    pool = settings_mod.pool_settings(conf)
    pool_mgr.create_pool(store, substrate, pool, GLOBAL, conf)
    yield store, substrate, pool
    substrate.stop_all()


def _ctx_for(store, pool):
    """Minimal fleet.Context stand-in for actions that only read
    .store and .pool."""
    return types.SimpleNamespace(store=store, pool=pool)


def test_e2e_gang_submission_exports_consistent_trace(fakepod_env,
                                                      tmp_path):
    """The acceptance run: one `jobs add` gang submission with an
    injected retry yields ONE trace whose Chrome export covers
    submit -> claim -> backoff -> rendezvous -> train steps with
    consistent trace/parent ids, while the goodput partition on the
    same run stays exact."""
    store, substrate, pool = fakepod_env
    marker_dir = str(tmp_path / "markers")
    os.makedirs(marker_dir)
    # Attempt 0: every instance drops a marker and fails (the
    # injected chaos); the requeued attempt finds the markers and
    # records a train step window through the goodput recorder (trace
    # ids attach from the exported env).
    command = (
        'M="$MARKER_DIR/done.$SHIPYARD_TASK_INSTANCE"; '
        'if [ ! -e "$M" ]; then touch "$M"; exit 1; fi; '
        "python3 -c \"import time; "
        "from batch_shipyard_tpu.goodput import events; "
        "t = time.time(); "
        "events.record('step_window', t, t + 0.05, step_start=0, "
        "step_end=4, tokens=32)\"")
    jobs_mgr.add_jobs(store, pool, settings_mod.job_settings_list(
        {"job_specifications": [{
            "id": "jtrace",
            "tasks": [{
                "command": command,
                "max_task_retries": 2,
                "environment_variables": {
                    "MARKER_DIR": marker_dir,
                    "PYTHONPATH": REPO_ROOT,
                },
                "multi_instance": {
                    "num_instances": 2,
                    "jax_distributed": {"enabled": False},
                },
            }],
        }]}))
    tasks = jobs_mgr.wait_for_tasks(store, "pool1", "jtrace",
                                    timeout=60)
    assert tasks[0]["state"] == "completed"
    assert tasks[0]["retries"] == 1
    trace_id = tasks[0][trace_ctx.COL_TRACE_ID]
    assert trace_id
    # Job row carries the same trace.
    job = jobs_mgr.get_job(store, "pool1", "jtrace")
    assert job[trace_ctx.COL_TRACE_ID] == trace_id

    want = {trace_spans.SPAN_SUBMIT, trace_spans.SPAN_CLAIM,
            trace_spans.SPAN_QUEUE_WAIT, trace_spans.SPAN_REQUEUE,
            trace_spans.SPAN_BACKOFF_WAIT,
            trace_spans.SPAN_RENDEZVOUS, trace_spans.SPAN_TASK_RUN}
    deadline = time.monotonic() + 15
    while time.monotonic() < deadline:
        kinds = {r["kind"] for r in trace_spans.query(
            store, "pool1", trace_id=trace_id)}
        if want <= kinds:
            break
        time.sleep(0.1)
    assert want <= kinds, f"missing spans: {want - kinds}"

    chrome = trace_export.export_trace(store, "pool1", trace_id)
    assert trace_export.validate_parent_links(chrome) == []
    by_name = {}
    for event in chrome["traceEvents"]:
        by_name.setdefault(event["name"], []).append(event)
    # The train steps joined the trace through the goodput recorder.
    assert "step_window" in by_name
    assert by_name["step_window"][0]["args"]["trace_id"] == trace_id
    # Both instances rendezvoused (per-instance spans).
    assert {e["args"].get("instance")
            for e in by_name["gang_rendezvous"]} >= {0, 1}
    # Span rows all share the submission's trace id, and the task
    # chain parents under the submit root.
    submit = by_name["submit"][0]["args"]
    assert submit["parent_span_id"] is None
    run = by_name["task_run"][0]["args"]
    assert run["parent_span_id"] == submit["span_id"]

    # Goodput on the SAME run: trace-tagged events exist, the trace
    # filter scopes them, and the partition stays exact.
    events = gp.query(store, "pool1", trace_id=trace_id)
    kinds = {e["kind"] for e in events}
    assert {gp.TASK_QUEUED, gp.TASK_RUNNING, gp.TASK_BACKOFF,
            gp.PROGRAM_STEP_WINDOW} <= kinds
    assert gp.query(store, "pool1", trace_id="nosuchtrace") == []
    report = accounting.job_report(store, "pool1", "jtrace")
    total = report["productive_seconds"] + sum(
        report["badput_seconds"].values())
    assert total == pytest.approx(report["wall_seconds"], rel=0.01)
    scoped = accounting.job_report(store, "pool1", "jtrace",
                                   trace_id=trace_id)
    assert scoped["trace_id"] == trace_id
    assert scoped["events"] == len(events)
    scoped_total = scoped["productive_seconds"] + sum(
        scoped["badput_seconds"].values())
    assert scoped_total == pytest.approx(scoped["wall_seconds"],
                                         rel=0.01)

    # `jobs tasks list` surfaces the trace id.
    from batch_shipyard_tpu import fleet
    import io
    import sys as sys_mod
    out = io.StringIO()
    stdout, sys_mod.stdout = sys_mod.stdout, out
    try:
        fleet.action_jobs_tasks_list(_ctx_for(store, pool), "jtrace",
                                     raw=True)
    finally:
        sys_mod.stdout = stdout
    listed = json.loads(out.getvalue())
    assert listed["tasks"][0]["trace_id"] == trace_id


def test_cli_trace_surface(tmp_path):
    """CLI smoke: jobs add -> tasks list exposes the trace id ->
    trace show/export/prune and goodput --trace run end-to-end
    through click."""
    import yaml
    from click.testing import CliRunner

    from batch_shipyard_tpu.cli.main import cli
    confs = {
        "credentials": {"credentials": {
            "storage": {"backend": "localfs",
                        "root": str(tmp_path / "store")}}},
        "config": {"global_resources": {"docker_images": []}},
        "pool": {"pool_specification": {
            "id": "tpool", "substrate": "fake",
            "tpu": {"accelerator_type": "v5litepod-8"},
            "max_wait_time_seconds": 30}},
        "jobs": {"job_specifications": [{
            "id": "tjob",
            "tasks": [{"command": "echo traced"}]}]},
    }
    for name, data in confs.items():
        with open(tmp_path / f"{name}.yaml", "w") as fh:
            yaml.safe_dump(data, fh)
    configdir = str(tmp_path)
    runner = CliRunner()
    for argv in (["pool", "add"], ["jobs", "add"],
                 ["jobs", "wait", "--job-id", "tjob",
                  "--timeout", "30"]):
        result = runner.invoke(cli, ["--configdir", configdir] + argv,
                               catch_exceptions=False)
        assert result.exit_code == 0, result.output
    result = runner.invoke(
        cli, ["--configdir", configdir, "--raw", "jobs", "tasks",
              "list", "tjob"], catch_exceptions=False)
    trace_id = json.loads(result.output)["tasks"][0]["trace_id"]
    result = runner.invoke(
        cli, ["--configdir", configdir, "trace", "show", trace_id],
        catch_exceptions=False)
    assert result.exit_code == 0 and "submit" in result.output
    out_path = str(tmp_path / "chrome.json")
    result = runner.invoke(
        cli, ["--configdir", configdir, "trace", "export", trace_id,
              "-o", out_path], catch_exceptions=False)
    assert result.exit_code == 0
    with open(out_path, encoding="utf-8") as fh:
        chrome = json.load(fh)
    assert chrome["otherData"]["trace_id"] == trace_id
    assert {e["name"] for e in chrome["traceEvents"]} >= {
        "submit", "task_run"}
    assert trace_export.validate_parent_links(chrome) == []
    result = runner.invoke(
        cli, ["--configdir", configdir, "--raw", "goodput", "job",
              "tjob", "--trace", trace_id], catch_exceptions=False)
    assert result.exit_code == 0
    report = json.loads(result.output)
    assert report["trace_id"] == trace_id and report["events"] > 0
    result = runner.invoke(
        cli, ["--configdir", configdir, "trace", "prune",
              "--older-than-hours", "0"], catch_exceptions=False)
    assert result.exit_code == 0 and "pruned" in result.output


def test_e2e_profile_request_flow(fakepod_env):
    """`jobs profile` store flag -> agent forwards at launch -> task
    writes a capture into the profile dir -> agent uploads it and
    stamps profile_artifact, surfaced by `jobs tasks list`."""
    store, substrate, pool = fakepod_env
    from batch_shipyard_tpu import fleet
    # Stamp the flag BEFORE submitting: launch-time delivery.
    store.insert_entity(names.TABLE_JOBS, "pool1", "jprof-pre",
                        {"state": "active", "spec": {}})
    fleet.action_jobs_profile(_ctx_for(store, pool), "jprof-pre",
                              steps=3)
    job = jobs_mgr.get_job(store, "pool1", "jprof-pre")
    assert job[trace_prof.COL_PROFILE_REQUEST]["steps"] == 3
    store.delete_entity(names.TABLE_JOBS, "pool1", "jprof-pre")

    # The request may arrive at launch (fast path) or via the
    # heartbeat forwarding loop once the agent's short-TTL job cache
    # refreshes — poll briefly like a real step loop would.
    command = (
        'for _ in $(seq 1 150); do '
        'test -f "$SHIPYARD_PROFILE_REQUEST_FILE" && break; '
        'sleep 0.1; done; '
        'test -f "$SHIPYARD_PROFILE_REQUEST_FILE" && '
        'mkdir -p "$SHIPYARD_PROFILE_DIR" && '
        'echo capture > "$SHIPYARD_PROFILE_DIR/trace.pb"')
    jobs_mgr.add_jobs(store, pool, settings_mod.job_settings_list(
        {"job_specifications": [{
            "id": "jprof", "tasks": [{"command": command}]}]}))
    fleet.action_jobs_profile(_ctx_for(store, pool), "jprof",
                              steps=2)
    tasks = jobs_mgr.wait_for_tasks(store, "pool1", "jprof",
                                    timeout=30)
    assert tasks[0]["state"] == "completed", tasks[0]
    deadline = time.monotonic() + 10
    task = tasks[0]
    while time.monotonic() < deadline:
        task = jobs_mgr.get_task(store, "pool1", "jprof",
                                 task["_rk"])
        if task.get(trace_prof.COL_PROFILE_ARTIFACT):
            break
        time.sleep(0.1)
    artifact = task[trace_prof.COL_PROFILE_ARTIFACT]
    assert artifact.endswith("/profile")
    data = store.get_object(artifact + "/trace.pb")
    assert data.strip() == b"capture"
    # Surfaced next to the diagnostics column.
    import io
    import sys as sys_mod
    out = io.StringIO()
    stdout, sys_mod.stdout = sys_mod.stdout, out
    try:
        fleet.action_jobs_tasks_list(_ctx_for(store, pool), "jprof",
                                     raw=True)
    finally:
        sys_mod.stdout = stdout
    listed = json.loads(out.getvalue())
    assert listed["tasks"][0]["profile_artifact"] == artifact
