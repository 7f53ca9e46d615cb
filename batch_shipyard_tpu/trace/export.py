"""Assemble one trace into Chrome trace-event JSON (Perfetto-loadable).

Inputs are the two logs a submission writes:

  * TABLE_TRACE spans (trace/spans.py) — the causal chain: submit,
    queue wait, claim, backoff, rendezvous, run, program phases,
    serving requests; every span carries trace/span/parent ids.
  * TABLE_GOODPUT intervals (goodput/events.py) — the accounting
    view; events emitted since this PR carry the same trace/span id
    fields, so a trace's waterfall context (image pull, step windows,
    checkpoint phases) rides along without double instrumentation.

Output is the Chrome trace-event JSON array format (the one format
both chrome://tracing and https://ui.perfetto.dev load directly):
complete ("ph": "X") events with microsecond timestamps, one PROCESS
track per node (pid) and one THREAD track per task-instance / serving
request (tid), span/parent ids preserved under ``args`` so the causal
chain survives into the UI's flow queries.
"""

from __future__ import annotations

import json
from typing import Any, Optional

from batch_shipyard_tpu.goodput import events as goodput_events
from batch_shipyard_tpu.state.base import StateStore
from batch_shipyard_tpu.trace import spans as trace_spans


def trace_rows(store: StateStore, pool_id: str,
               trace_id: str) -> dict[str, list[dict]]:
    """Every row of one trace: {"spans": [...], "goodput": [...]},
    each sorted by start."""
    span_rows = trace_spans.query(store, pool_id, trace_id=trace_id)
    goodput_rows = goodput_events.query(store, pool_id,
                                        trace_id=trace_id)
    return {"spans": span_rows, "goodput": goodput_rows}


def _track(row: dict) -> tuple[str, str]:
    """(pid, tid) for a row: one process track per node, one thread
    track per task instance / serving request, and one for a serving
    task's engine steps."""
    pid = row.get("node_id") or "client"
    attrs = row.get("attrs") or {}
    if row.get("kind") == trace_spans.SPAN_SERVE_STEP:
        tid = f"{row.get('task_id') or '-'} engine steps"
    elif row.get("kind", "").startswith("serve_"):
        tid = f"request {attrs.get('request_id', '?')}"
    else:
        tid = row.get("task_id") or row.get("job_id") or "-"
        instance = attrs.get("instance")
        if instance is not None:
            tid = f"{tid} i{instance}"
    return str(pid), str(tid)


def _launch_events(row: dict, step_event: dict) -> list[dict]:
    """The launches a serve_step row landed (its ``landed`` list), on
    a track of their own beside the task's engine steps: each from
    its landing back over its period, so the launches lie back to
    back where the device was kept busy, as the engine saw it, with
    no profiler capture. A landing's time.monotonic() is put on the
    row's time.time() through the row's ``mono_start``."""
    attrs = row.get("attrs") or {}
    if "mono_start" not in attrs:
        return []
    offset = float(row.get("start", 0.0)) - attrs["mono_start"]
    events = []
    for launch in attrs.get("landed", ()):
        name = launch["kind"] if launch["kind"] == "decode" else \
            f"prefill {launch['path']} {launch['bucket']}"
        events.append({
            **step_event, "name": name,
            "ts": (launch["landed_at"] + offset) * 1e6
            - launch["period_ms"] * 1e3,
            "dur": launch["period_ms"] * 1e3,
            "tid": f"{row.get('task_id') or '-'} device (as the "
                   f"engine saw it)",
            "args": {"trace_id": row.get("trace_id"),
                     "span_id": f"{row.get('span_id')}.{len(events)}",
                     "parent_span_id": row.get("span_id"),
                     **launch}})
    return events


def to_chrome_trace(rows: dict[str, list[dict]],
                    trace_id: str) -> dict[str, Any]:
    """Chrome trace-event JSON object for one trace."""
    events: list[dict] = []
    for source, cat in (("spans", "trace"), ("goodput", "goodput")):
        for row in rows.get(source, ()):
            start = float(row.get("start", 0.0))
            end = float(row.get("end", start))
            pid, tid = _track(row)
            event = {
                "name": row.get("kind", "?"),
                "cat": cat,
                "ph": "X",
                "ts": start * 1e6,
                "dur": max(0.0, end - start) * 1e6,
                "pid": pid,
                "tid": tid,
                "args": {
                    "trace_id": row.get("trace_id"),
                    "span_id": row.get("span_id"),
                    "parent_span_id": row.get("parent_span_id"),
                    "job_id": row.get("job_id"),
                    "task_id": row.get("task_id"),
                    **(row.get("attrs") or {}),
                },
            }
            events.append(event)
            if row.get("kind") == trace_spans.SPAN_SERVE_STEP:
                events.extend(_launch_events(row, event))
    events.sort(key=lambda e: e["ts"])
    return {
        "traceEvents": events,
        "displayTimeUnit": "ms",
        "otherData": {"trace_id": trace_id,
                      "spans": len(rows.get("spans", ())),
                      "goodput_events": len(rows.get("goodput", ()))},
    }


def export_trace(store: StateStore, pool_id: str,
                 trace_id: str) -> dict[str, Any]:
    """One-call assemble: rows -> Chrome trace JSON object."""
    return to_chrome_trace(trace_rows(store, pool_id, trace_id),
                           trace_id)


def validate_parent_links(chrome_trace: dict[str, Any]) -> list[str]:
    """Every span-sourced event's parent_span_id must resolve to
    another span of the SAME trace (or be absent at the root), and
    every event must carry the trace id. Returns the list of
    problems (empty = consistent) — the e2e acceptance check."""
    problems: list[str] = []
    events = chrome_trace.get("traceEvents", [])
    trace_id = (chrome_trace.get("otherData") or {}).get("trace_id")
    span_ids = {e["args"].get("span_id") for e in events
                if e.get("cat") == "trace"}
    for event in events:
        args = event.get("args", {})
        if args.get("trace_id") != trace_id:
            problems.append(
                f"{event.get('name')}: trace_id "
                f"{args.get('trace_id')!r} != {trace_id!r}")
        if event.get("cat") != "trace":
            continue
        parent = args.get("parent_span_id")
        if parent is not None and parent not in span_ids:
            problems.append(
                f"{event.get('name')}: parent span {parent!r} not in "
                f"this trace")
    return problems


def render_tree(rows: dict[str, list[dict]]) -> str:
    """Terminal waterfall for ``shipyard trace show``: spans indented
    under their parents, goodput intervals listed after, all with
    millisecond offsets from the trace's first event."""
    span_rows = rows.get("spans", [])
    goodput_rows = rows.get("goodput", [])
    if not span_rows and not goodput_rows:
        return "(no spans recorded for this trace)"
    all_rows = span_rows + goodput_rows
    t0 = min(float(r.get("start", 0.0)) for r in all_rows)

    def fmt(row: dict, depth: int) -> str:
        start = float(row.get("start", 0.0))
        end = float(row.get("end", start))
        where = row.get("node_id") or "-"
        task = row.get("task_id") or ""
        return (f"{(start - t0) * 1e3:>10.1f}ms "
                f"{(end - start) * 1e3:>9.1f}ms  "
                f"{'  ' * depth}{row.get('kind')}"
                f"  [{where}{' ' + task if task else ''}]")

    children: dict[Optional[str], list[dict]] = {}
    by_id = {r.get("span_id"): r for r in span_rows}
    for row in span_rows:
        parent = row.get("parent_span_id")
        if parent not in by_id:
            parent = None  # orphan/root: show at top level
        children.setdefault(parent, []).append(row)

    lines = [f"{'offset':>12} {'duration':>10}  span [node task]",
             "-" * 64]

    def walk(parent: Optional[str], depth: int) -> None:
        for row in sorted(children.get(parent, ()),
                          key=lambda r: r.get("start", 0.0)):
            lines.append(fmt(row, depth))
            walk(row.get("span_id"), depth + 1)

    walk(None, 0)
    if goodput_rows:
        lines.append("-" * 64)
        lines.append("goodput intervals on this trace:")
        for row in goodput_rows:
            lines.append(fmt(row, 0))
    return "\n".join(lines)


def write_chrome_trace(chrome_trace: dict[str, Any],
                       path: str) -> str:
    with open(path, "w", encoding="utf-8") as fh:
        json.dump(chrome_trace, fh, indent=2)
    return path
