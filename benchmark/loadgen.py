#!/usr/bin/env python3
"""The load generator: a child process of the benchmark that never
imports JAX (standard library only), so it neither needs the chip nor
shares the engine thread's GIL. One thread, one asyncio loop.

It makes its requests from (traffic file, seed) with traffic_gen, says
READY, waits for ``GO <t>`` on stdin (t on CLOCK_MONOTONIC, which the
parent shares), then drives the server:

  open loop    every request is sent when it is DUE (lead-in first,
               then the window), whatever the server is doing; after
               the window it waits up to the drain limit for replies.
  closed loop  ``clients`` callers each send their next request when
               the last one's reply has ended, until the window ends;
               what is in flight then is cut, not failed.

Every reply is streamed NDJSON: the arrival time of each token line is
taken on this process's clock. One JSON document goes to --out."""

from __future__ import annotations

import argparse
import asyncio
import json
import pathlib
import sys
import time
import urllib.parse

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent))
import traffic_gen  # noqa: E402


async def _one_request(host: str, port: int, request: dict,
                       record: dict) -> None:
    """POST one streaming generate; fill ``record`` in place (so a
    cancelled request keeps what it had seen)."""
    record["launched"] = time.monotonic()
    writer = None
    try:
        reader, writer = await asyncio.open_connection(host, port)
        body = json.dumps({
            "prompt": request["prompt"],
            "max_new_tokens": request["max_new_tokens"],
            "request_id": f"bench-{request['idx']}",
            "stream": True}).encode()
        writer.write(
            b"POST /v1/generate HTTP/1.1\r\nHost: bench\r\n"
            b"Content-Type: application/json\r\n"
            b"Connection: close\r\n"
            b"Content-Length: %d\r\n\r\n" % len(body) + body)
        record["sent"] = time.monotonic()
        await writer.drain()
        status = await reader.readline()
        parts = status.split()
        code = int(parts[1]) if len(parts) > 1 else 0
        while (await reader.readline()) not in (b"\r\n", b"\n", b""):
            pass
        if code != 200:
            rest = await reader.read(4096)
            record["error"] = f"HTTP {code}: {rest[:200]!r}"
            return
        while True:
            line = await reader.readline()
            if not line:
                break
            now = time.monotonic()
            line = line.strip()
            if not line.startswith(b"{"):
                continue            # chunk-size and blank lines
            event = json.loads(line)
            if "tokens" in event:
                record["final"] = {
                    "tokens": event["tokens"],
                    "ttft_ms": event.get("ttft_ms"),
                    "tpot_ms": event.get("tpot_ms")}
                record["ended"] = now
            elif "error" in event:
                record["error"] = str(event["error"])
                record["ended"] = now
            elif "token" in event:
                record["tokens"].append(event["token"])
                record["token_times"].append(now)
        if "final" not in record and "error" not in record:
            record["error"] = "stream ended without a final line"
    except asyncio.CancelledError:
        record.setdefault("cut", True)
        raise
    except Exception as exc:  # noqa: BLE001 - every failure is counted
        record["error"] = f"{type(exc).__name__}: {exc}"
    finally:
        record.setdefault("ended", time.monotonic())
        if writer is not None:
            writer.close()


def _new_record(request: dict) -> dict:
    return {"idx": request["idx"], "phase": request.get("phase"),
            "tokens": [], "token_times": []}


async def _open_loop(host, port, plan, t_start, window_s, drain_s):
    window_start = t_start + plan["lead_in_s"]
    records, tasks = [], []
    for request in plan["requests"]:
        due = window_start + request["due_s"]
        delay = due - time.monotonic()
        if delay > 0:
            await asyncio.sleep(delay)
        record = _new_record(request)
        record["due"] = due
        records.append(record)
        tasks.append(asyncio.ensure_future(
            _one_request(host, port, request, record)))
    deadline = window_start + window_s + drain_s
    if tasks:
        _done, late = await asyncio.wait(
            tasks, timeout=max(0.0, deadline - time.monotonic()))
        for task in late:
            task.cancel()
        await asyncio.gather(*tasks, return_exceptions=True)
    for record in records:
        if record.pop("cut", False):
            record["error"] = "unfinished at the drain limit"
    return records


async def _closed_loop(host, port, plan, t_start, window_s, stagger_s):
    end = t_start + plan["lead_in_s"] + window_s
    records = []
    todo = iter(plan["requests"])

    async def client(number):
        # Callers join one by one: all at once they overflow the
        # server's listen queue and enter in whatever order TCP's
        # retries let them, which no two runs repeat.
        await asyncio.sleep(number * stagger_s)
        for request in todo:
            if time.monotonic() >= end:
                return
            record = _new_record(request)
            records.append(record)
            await _one_request(host, port, request, record)

    delay = t_start - time.monotonic()
    if delay > 0:
        await asyncio.sleep(delay)
    tasks = [asyncio.ensure_future(client(number))
             for number in range(plan["clients"])]
    _done, running = await asyncio.wait(
        tasks, timeout=max(0.0, end - time.monotonic()))
    for task in running:
        task.cancel()
    await asyncio.gather(*tasks, return_exceptions=True)
    return records


def main(argv=None) -> int:
    parser = argparse.ArgumentParser()
    parser.add_argument("--traffic", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--vocab", type=int, required=True)
    parser.add_argument("--url", required=True)
    parser.add_argument("--out", required=True)
    args = parser.parse_args(argv)
    with open(args.traffic, encoding="utf-8") as fh:
        traffic = json.load(fh)
    plan = traffic_gen.generate(traffic, args.seed, args.seconds,
                                args.vocab)
    url = urllib.parse.urlparse(args.url)
    print("READY", flush=True)
    word, _, value = sys.stdin.readline().strip().partition(" ")
    if word != "GO":
        return 2
    t_start = float(value)
    if plan["mode"] == "open":
        records = asyncio.run(_open_loop(
            url.hostname, url.port, plan, t_start, args.seconds,
            float(traffic.get("drain_limit_s", 30))))
    else:
        records = asyncio.run(_closed_loop(
            url.hostname, url.port, plan, t_start, args.seconds,
            float(traffic.get("client_stagger_s", 0.0))))
    with open(args.out, "w", encoding="utf-8") as fh:
        json.dump({"t_start": t_start,
                   "window_start": t_start + plan["lead_in_s"],
                   "window_s": args.seconds,
                   "records": records}, fh)
    return 0


if __name__ == "__main__":
    sys.exit(main())
