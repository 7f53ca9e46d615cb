"""Serving front end + load generator: HTTP ingress over the
continuous-batching engine, TTFT/TPOT measurement, Poisson load
report (VERDICT r3 order #4 — an Orca/vLLM-class engine is judged by
TTFT/TPOT under load, which needs an ingress path)."""

import json
import time
import urllib.request

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from batch_shipyard_tpu.models import inference as inf
from batch_shipyard_tpu.models import loadgen, serving
from batch_shipyard_tpu.models import transformer as tfm
from batch_shipyard_tpu.models.server import ServingFrontEnd, percentile

CFG = tfm.TransformerConfig(
    vocab_size=97, d_model=32, n_layers=2, n_heads=2, d_head=16,
    d_ff=64, max_seq_len=64, dtype=jnp.float32,
    param_dtype=jnp.float32)


@pytest.fixture(scope="module")
def params():
    model = tfm.TransformerLM(CFG)
    return model.init(jax.random.PRNGKey(7),
                      jnp.zeros((1, 8), jnp.int32))["params"]


@pytest.fixture()
def front(params):
    engine = serving.ContinuousBatcher(CFG, params, num_slots=2,
                                       max_decode_len=64)
    fe = ServingFrontEnd(engine, port=0).start()
    yield fe
    fe.shutdown()


def _post(url, payload):
    req = urllib.request.Request(
        f"{url}/v1/generate", data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json"}, method="POST")
    with urllib.request.urlopen(req, timeout=120) as resp:
        return json.loads(resp.read())


def test_generate_over_http_matches_engine_greedy(front, params):
    prompt = [5, 17, 31, 2]
    out = _post(front.url, {"prompt": prompt, "max_new_tokens": 6})
    assert len(out["tokens"]) == 6
    assert out["num_tokens"] == 6
    assert out["ttft_ms"] > 0 and out["tpot_ms"] >= 0
    assert out["latency_ms"] >= out["ttft_ms"]
    # Greedy equivalence with the lockstep decoder.
    run, _ = inf.make_decoder(CFG, params, max_decode_len=64)
    ref, _ = run(jnp.asarray([prompt], jnp.int32), 6,
                 jax.random.PRNGKey(0))
    assert out["tokens"] == list(
        np.asarray(ref[0, len(prompt):]).tolist())


def test_health_stats_and_errors(front):
    with urllib.request.urlopen(f"{front.url}/healthz",
                                timeout=30) as resp:
        assert json.loads(resp.read())["ok"] is True
    _post(front.url, {"prompt": [1, 2], "max_new_tokens": 3})
    with urllib.request.urlopen(f"{front.url}/v1/stats",
                                timeout=30) as resp:
        stats = json.loads(resp.read())
    assert stats["completed_requests"] >= 1
    assert stats["generated_tokens"] >= 3
    assert set(stats["ttft_ms"]) == {"50", "90", "99"} or set(
        stats["ttft_ms"]) == {50, 90, 99}
    # Mergeable fixed-bucket histograms ride along for fleet
    # aggregation (router) — counts match the request totals.
    assert stats["ttft_hist"]["count"] == stats["completed_requests"]
    assert stats["tpot_hist"]["count"] == stats["completed_requests"]
    # Bad request -> 400, server keeps serving.
    bad = urllib.request.Request(
        f"{front.url}/v1/generate",
        data=json.dumps({"prompt": "nope"}).encode(), method="POST")
    try:
        urllib.request.urlopen(bad, timeout=30)
        assert False, "expected HTTPError"
    except urllib.error.HTTPError as exc:
        assert exc.code == 400
    out = _post(front.url, {"prompt": [3], "max_new_tokens": 2})
    assert len(out["tokens"]) == 2


def test_poisson_load_report(front):
    report = loadgen.run_load(
        front.url, num_requests=12, rate_hz=50.0,
        prompt_len=(2, 8), max_new_tokens=(2, 6), vocab_size=97,
        seed=3)
    assert report["completed"] == 12 and report["failed"] == 0
    assert report["generated_tokens"] >= 24
    assert report["tokens_per_second"] > 0
    for section in ("ttft_ms", "tpot_ms", "latency_ms"):
        assert set(report[section]) == {"p50", "p90", "p99"}
        assert report[section]["p50"] <= report[section]["p90"] <= \
            report[section]["p99"]
    hist = report["ttft_hist"]
    assert hist["count"] == 12
    assert sum(hist["counts"]) + hist["overflow"] == 12
    # Reproducible arrivals + prompts under the same seed.
    again = loadgen.run_load(
        front.url, num_requests=3, rate_hz=100.0, prompt_len=(2, 4),
        max_new_tokens=(2, 3), vocab_size=97, seed=9)
    once_more = loadgen.run_load(
        front.url, num_requests=3, rate_hz=100.0, prompt_len=(2, 4),
        max_new_tokens=(2, 3), vocab_size=97, seed=9)
    assert again["generated_tokens"] == once_more["generated_tokens"]


def test_diurnal_load_with_slo_attainment(front):
    """arrival="diurnal" replays the fleet simulator's day/night
    curve (sim/traces.diurnal_arrivals), deterministic per seed;
    slo_classes adds a per-class attainment table; shared prefix
    groups tag requests with prefix keys. Two runs at the same seed
    produce byte-identical outputs (the bench's equivalence check)."""
    from batch_shipyard_tpu.sim import traces as sim_traces

    classes = {"interactive": {"ttft_ms": 1e6, "tpot_ms": 1e6},
               "batch": {"ttft_ms": None, "tpot_ms": None}}
    kwargs = dict(num_requests=10, rate_hz=80.0, arrival="diurnal",
                  day_seconds=2.0, prompt_len=(2, 6),
                  max_new_tokens=(2, 4), vocab_size=97, seed=11,
                  shared_prefix_groups=2, shared_prefix_len=8,
                  slo_classes=classes)
    report = loadgen.run_load(front.url, **kwargs)
    assert report["completed"] == 10 and report["failed"] == 0
    assert report["shed"] == 0
    assert report["arrival"] == "diurnal"
    att = report["slo_attainment"]
    assert set(att) == {"interactive", "batch"}
    assert att["interactive"]["requests"] == 5
    # Generous targets attain fully; None targets always attain.
    assert att["interactive"]["ttft_attainment"] == 1.0
    assert att["batch"]["tpot_attainment"] == 1.0
    assert att["interactive"]["ttft_target_ms"] == 1e6
    # Deterministic replay: same seed => same arrivals, prompts, and
    # (greedy engine) token ids.
    again = loadgen.run_load(front.url, **kwargs)
    assert again["outputs_sha256"] == report["outputs_sha256"]
    assert sim_traces.diurnal_arrivals(11, 5, 2.0, 80.0, 20.0) == \
        sim_traces.diurnal_arrivals(11, 5, 2.0, 80.0, 20.0)
    with pytest.raises(ValueError):
        loadgen.run_load(front.url, num_requests=1,
                         arrival="lunar")


def test_paged_overcommit_engine_behind_front(params):
    engine = serving.ContinuousBatcher(
        CFG, params, num_slots=2, max_decode_len=64,
        kv_page_size=8, kv_num_pages=12, overcommit=True)
    fe = ServingFrontEnd(engine, port=0).start()
    try:
        report = loadgen.run_load(
            fe.url, num_requests=6, rate_hz=100.0,
            prompt_len=(2, 6), max_new_tokens=(2, 8), vocab_size=97,
            seed=1)
        assert report["completed"] == 6 and report["failed"] == 0
    finally:
        fe.shutdown()


def test_percentile_nearest_rank():
    assert percentile([], 99) == 0.0
    vals = [float(v) for v in range(1, 101)]
    assert percentile(vals, 50) == 50.0
    assert percentile(vals, 99) == 99.0


def test_streaming_generate_ndjson(front, params):
    """stream: true returns one NDJSON line per token as it decodes,
    then the final result object; tokens match the blocking path."""
    import http.client
    host, port = front.address
    conn = http.client.HTTPConnection(host, port, timeout=120)
    body = json.dumps({"prompt": [5, 17, 31, 2],
                       "max_new_tokens": 5, "stream": True})
    conn.request("POST", "/v1/generate", body=body,
                 headers={"Content-Type": "application/json"})
    resp = conn.getresponse()
    assert resp.status == 200
    assert resp.getheader("Content-Type") == "application/x-ndjson"
    lines = [json.loads(ln) for ln in
             resp.read().decode().strip().split("\n")]
    conn.close()
    token_events = [e for e in lines if "token" in e]
    final = lines[-1]
    assert [e["index"] for e in token_events] == list(
        range(len(token_events)))
    assert final["tokens"] == [e["token"] for e in token_events]
    assert final["num_tokens"] == 5
    assert final["ttft_ms"] > 0
    # Same tokens as the blocking path (greedy, same prompt).
    blocking = _post(front.url, {"prompt": [5, 17, 31, 2],
                                 "max_new_tokens": 5})
    assert blocking["tokens"] == final["tokens"]
    # Bad streaming request -> clean 400 before any stream bytes.
    conn = http.client.HTTPConnection(host, port, timeout=30)
    conn.request("POST", "/v1/generate",
                 body=json.dumps({"prompt": "bad", "stream": True}),
                 headers={"Content-Type": "application/json"})
    resp = conn.getresponse()
    assert resp.status == 400
    conn.close()


def test_streaming_engine_error_emitted_as_ndjson_line(front):
    """An engine-side rejection surfacing AFTER the chunked headers
    (e.g. prompt+generation exceeding max_decode_len) arrives as an
    {"error": ...} NDJSON line with a clean stream termination — not
    a second HTTP response corrupting the framing."""
    import http.client
    host, port = front.address
    conn = http.client.HTTPConnection(host, port, timeout=60)
    conn.request("POST", "/v1/generate",
                 body=json.dumps({"prompt": [1, 2, 3],
                                  "max_new_tokens": 100000,
                                  "stream": True}),
                 headers={"Content-Type": "application/json"})
    resp = conn.getresponse()
    assert resp.status == 200  # headers already committed
    lines = [json.loads(ln) for ln in
             resp.read().decode().strip().split("\n")]
    conn.close()
    assert len(lines) == 1 and "error" in lines[0]
    assert "max_decode_len" in lines[0]["error"]
    # Server is still healthy afterwards.
    out = _post(front.url, {"prompt": [3], "max_new_tokens": 2})
    assert len(out["tokens"]) == 2


def test_cancel_queued_and_running_requests(params):
    """DELETE /v1/requests/<id> aborts both a decoding request and a
    queued one; waiters complete with a 'cancelled' error and the
    slot frees for new work (the vLLM-class abort operation)."""
    import threading
    import time as time_mod
    import urllib.error
    engine = serving.ContinuousBatcher(CFG, params, num_slots=1,
                                       max_decode_len=64)
    fe = ServingFrontEnd(engine, port=0).start()
    try:
        # Warm the compile, then throttle the engine step so the
        # running request decodes for seconds — the cancel race is
        # deterministic regardless of CPU speed.
        _post(fe.url, {"prompt": [1], "max_new_tokens": 2})
        orig_step = engine.step

        def slow_step():
            time_mod.sleep(0.05)
            return orig_step()

        engine.step = slow_step
        results = {}

        def _gen(rid):
            try:
                results[rid] = _post(fe.url, {
                    "request_id": rid, "prompt": [2, 3],
                    "max_new_tokens": 60})
            except urllib.error.HTTPError as exc:
                results[rid] = {"status": exc.code,
                                "body": json.loads(exc.read())}

        threads = [threading.Thread(target=_gen, args=(rid,),
                                    daemon=True)
                   for rid in ("running-r", "queued-r")]
        threads[0].start()
        time_mod.sleep(0.5)  # running-r holds the single slot
        threads[1].start()
        time_mod.sleep(0.3)  # queued-r sits in the engine queue
        for rid in ("queued-r", "running-r"):
            req = urllib.request.Request(
                f"{fe.url}/v1/requests/{rid}", method="DELETE")
            with urllib.request.urlopen(req, timeout=30) as resp:
                assert resp.status == 202
        for t in threads:
            t.join(60)
        for rid in ("running-r", "queued-r"):
            out = results[rid]
            assert out.get("status") == 409 and \
                "cancelled" in out["body"]["error"], out
        engine.step = orig_step
        # Slot is free again.
        out = _post(fe.url, {"prompt": [9], "max_new_tokens": 2})
        assert len(out["tokens"]) == 2
    finally:
        fe.shutdown()


def test_serve_checkpoint_restore_roundtrip(tmp_path):
    """workloads.serve --checkpoint-dir serves trained weights: save
    params via the checkpoint module, restore-params them, and check
    array equality through the serving build path."""
    import numpy as np_mod
    from batch_shipyard_tpu.workloads import checkpoint
    model = tfm.TransformerLM(CFG)
    params = model.init(jax.random.PRNGKey(3),
                        jnp.zeros((1, 8), jnp.int32))["params"]
    import optax
    opt_state = optax.adam(1e-3).init(params)
    checkpoint.save(str(tmp_path), 7, params, opt_state)
    restored = checkpoint.restore_params(str(tmp_path))
    assert restored is not None
    rparams, step = restored
    assert step == 7
    flat = jax.tree_util.tree_leaves(params)
    rflat = jax.tree_util.tree_leaves(rparams)
    assert len(flat) == len(rflat)
    for a, b in zip(flat, rflat):
        assert np_mod.allclose(np_mod.asarray(a), np_mod.asarray(b))


def test_serve_build_slo_config(tmp_path):
    """workloads.serve --slo-config plumbing: 'default' loads the
    built-in class table, a JSON config file parses through
    config/settings.serving_slo_settings, CLI overrides win, and no
    flag means SLO scheduling stays off."""
    import argparse

    from batch_shipyard_tpu.workloads import serve as serve_mod

    ns = argparse.Namespace(slo_config="default",
                            shed_grace_ms=250.0,
                            tpot_stall_factor=None)
    slo = serve_mod.build_slo(ns)
    assert slo.shed_grace_ms == 250.0
    targets = slo.class_targets()
    assert targets["interactive"]["ttft_ms"] == 500.0
    assert targets["batch"]["ttft_ms"] is None
    cfg_file = tmp_path / "slo.json"
    cfg_file.write_text(json.dumps({"serving": {"slo": {
        "classes": [{"name": "gold", "ttft_ms": 100.0,
                     "tpot_ms": 50.0}],
        "shed_grace_ms": 100.0, "tpot_stall_factor": 2.0}}}))
    slo2 = serve_mod.build_slo(argparse.Namespace(
        slo_config=str(cfg_file), shed_grace_ms=None,
        tpot_stall_factor=None))
    assert slo2.class_targets() == {
        "gold": {"ttft_ms": 100.0, "tpot_ms": 50.0}}
    assert slo2.shed_grace_ms == 100.0
    assert slo2.tpot_stall_factor == 2.0
    assert serve_mod.build_slo(argparse.Namespace(
        slo_config=None, shed_grace_ms=None,
        tpot_stall_factor=None)) is None


def test_slo_classes_stats_and_unknown_class(params):
    """A front configured with SLO classes: responses carry the
    class, /v1/stats grows per-class attainment + engine SLO
    counters, and an unknown class is a 400."""
    engine = serving.ContinuousBatcher(CFG, params, num_slots=2,
                                       max_decode_len=64)
    classes = {"interactive": {"ttft_ms": 1e6, "tpot_ms": 1e6},
               "batch": {"ttft_ms": None, "tpot_ms": None}}
    fe = ServingFrontEnd(engine, port=0, slo_classes=classes).start()
    try:
        out = _post(fe.url, {"prompt": [1, 2], "max_new_tokens": 3,
                             "slo_class": "interactive"})
        assert out["slo_class"] == "interactive"
        _post(fe.url, {"prompt": [4], "max_new_tokens": 2})  # default
        with urllib.request.urlopen(f"{fe.url}/v1/stats",
                                    timeout=30) as resp:
            stats = json.loads(resp.read())
        slo = stats["slo"]
        row = slo["classes"]["interactive"]
        assert row["requests"] == 1 and row["ttft_attainment"] == 1.0
        assert slo["sheds"] == 0 and slo["deferrals"] >= 0
        # "standard" is not configured here: the default-class request
        # still completes and is tracked untargeted.
        assert slo["classes"]["standard"]["requests"] == 1
        try:
            _post(fe.url, {"prompt": [1], "max_new_tokens": 1,
                           "slo_class": "platinum"})
            assert False, "expected HTTPError"
        except urllib.error.HTTPError as exc:
            assert exc.code == 400
        with urllib.request.urlopen(f"{fe.url}/metrics",
                                    timeout=30) as resp:
            text = resp.read().decode()
        assert 'slo_class="interactive"' in text
    finally:
        fe.shutdown()


def test_overloaded_queue_sheds_503(params):
    """Armed shedding: a queued request whose TTFT deadline expired
    past the grace is rejected 503 with shed=true while the slot is
    held by a long decode — deepest violation first, the waiter is
    completed promptly (not at its would-be turn)."""
    import threading

    engine = serving.ContinuousBatcher(
        CFG, params, num_slots=1, max_decode_len=64,
        slo_shed_grace_ms=0.0)
    fe = ServingFrontEnd(engine, port=0).start()
    result = {}

    def _long():
        result["r"] = _post(fe.url, {"request_id": "hog",
                                     "prompt": [7, 7],
                                     "max_new_tokens": 48})

    try:
        t = threading.Thread(target=_long, daemon=True)
        t.start()
        deadline = time.monotonic() + 20
        while time.monotonic() < deadline and \
                not fe.knows("hog"):
            time.sleep(0.01)
        try:
            _post(fe.url, {"prompt": [1, 2], "max_new_tokens": 2,
                           "ttft_target_ms": 0.01})
            assert False, "expected 503 shed"
        except urllib.error.HTTPError as exc:
            assert exc.code == 503
            body = json.loads(exc.read())
            assert body["shed"] is True
            assert "shed" in body["error"]
        t.join(120)
        assert result["r"]["num_tokens"] == 48
        assert engine.slo_sheds == 1
    finally:
        fe.shutdown()


def test_loadgen_round_robins_across_replicas(params):
    """A serving fleet: run_load spreads requests across replica
    URLs and reports the per-replica completion breakdown."""
    engines = [serving.ContinuousBatcher(CFG, params, num_slots=2,
                                         max_decode_len=64)
               for _ in range(2)]
    fronts = [ServingFrontEnd(e, port=0).start() for e in engines]
    try:
        report = loadgen.run_load(
            [f.url for f in fronts], num_requests=8, rate_hz=100.0,
            prompt_len=(2, 4), max_new_tokens=(2, 4), vocab_size=97,
            seed=5)
        assert report["completed"] == 8 and report["failed"] == 0
        assert report["replicas"] == 2
        per = report["completed_by_replica"]
        assert sorted(per.values()) == [4, 4], per
        assert set(per) == {f.url for f in fronts}
    finally:
        for f in fronts:
            f.shutdown()


# --------------------- the engine from inside (ISSUE 24) ----------------

def test_stats_and_metrics_carry_the_engine_block(params):
    engine = serving.ContinuousBatcher(
        CFG, params, num_slots=2, max_decode_len=64, kv_page_size=8,
        kv_num_pages=12)
    front = ServingFrontEnd(engine, port=0).start()
    try:
        for k in range(3):
            _post(front.url, {"prompt": [1 + k] * 9,
                              "max_new_tokens": 4})
        with urllib.request.urlopen(f"{front.url}/v1/stats",
                                    timeout=30) as resp:
            block = json.loads(resp.read())["engine"]
        with urllib.request.urlopen(f"{front.url}/metrics",
                                    timeout=30) as resp:
            metrics = resp.read().decode()
    finally:
        front.shutdown()
    assert block["slots_total"] == 2 and block["kv_pages_total"] == 12
    assert block["slots_active"] == 0 and block["queued"] == 0
    assert block["kv_pages_in_use"] == 0
    assert block["kv_first_chunks_prefetched"] == 0     # nobody seated
    # finished prompts' whole pages stay indexed, parked in the LRU
    assert block["kv_pages_lru"] == block["prefix_index_pages"] == 3
    assert block["kv_pages_free"] == 12 - 3
    assert block["steps"] >= 3 * 3 and block["step_ms_mean"] > 0
    assert set(block["phase_ms_mean"]) == set(serving.STEP_PHASES)
    assert sum(block["phase_ms_mean"].values()) <= block["step_ms_mean"]
    assert block["compiles"] >= 0 and block["compile_seconds"] >= 0
    values = {}
    for line in metrics.splitlines():
        name, _, value = line.rpartition(" ")
        values[name] = float(value)
    assert values["shipyard_serving_slots_active"] == 0
    assert values["shipyard_serving_queue_depth"] == 0
    assert values["shipyard_serving_kv_pages_in_use"] == 0
    assert values["shipyard_serving_kv_pages_total"] == 12
    assert values["shipyard_serving_kv_first_chunks_prefetched"] == 0
    assert values["shipyard_serving_steps_total"] == block["steps"]
    assert "shipyard_serving_compiles_total" in values
    for phase in serving.STEP_PHASES:
        assert values['shipyard_serving_step_phase_seconds_total'
                      f'{{phase="{phase}"}}'] == \
            pytest.approx(block["phase_seconds"][phase])
    # a dense engine has no pages to report: the lines are left out
    dense = ServingFrontEnd(serving.ContinuousBatcher(
        CFG, params, num_slots=2, max_decode_len=64), port=0)
    lines = "\n".join(dense.prometheus_metrics())
    dense._httpd.server_close()
    assert "kv_pages" not in lines and "slots_active 0" in lines


def test_a_request_is_in_one_step_row_and_in_its_prefill_span(
        params, tmp_path, monkeypatch):
    """The step that stalled for a request and the request's own
    serve_prefill span share the request id; and what the recorder
    buffered is in the file once shutdown() has returned."""
    from batch_shipyard_tpu.trace import spans as trace_spans
    path = tmp_path / "spans.jsonl"
    trace_spans.flush()
    monkeypatch.setenv("SHIPYARD_TRACE_FILE", str(path))
    monkeypatch.setenv("SHIPYARD_TRACE_ID", "trace-1")
    monkeypatch.setenv("SHIPYARD_TRACE_SPAN_ID", "run-1")
    engine = serving.ContinuousBatcher(CFG, params, num_slots=2,
                                       max_decode_len=64)
    engine.traced_steps = 99999         # a front end starts the head
    front = ServingFrontEnd(engine, port=0).start()
    assert engine.traced_steps == 0
    try:
        ids = [_post(front.url, {"prompt": [2 + k] * 5,
                                 "max_new_tokens": 3,
                                 "request_id": f"q{k}"})["request_id"]
               for k in range(3)]
    finally:
        front.shutdown()
    with open(path, encoding="utf-8") as fh:    # no flush() here
        rows = [json.loads(line) for line in fh]
    steps = [r for r in rows if r["kind"] == "serve_step"]
    prefills = {r["attrs"]["request_id"]: r for r in rows
                if r["kind"] == "serve_prefill"}
    assert sorted(prefills) == sorted(ids) == ["q0", "q1", "q2"]
    for request_id in ids:
        holders = [s for s in steps if request_id in
                   {a["request_id"] for a in s["attrs"]["admitted"]}]
        assert len(holders) == 1
        step, span = holders[0], prefills[request_id]
        assert step["attrs"]["prefill_ms"] > 0
        # the step began before the request's prefill did and ended
        # after its first token (both on time.time())
        assert step["start"] <= span["start"] + 0.05
        assert span["end"] <= step["end"] + 0.05
    assert len(steps) == engine.steps_total == engine.traced_steps


# ------- one writer for all token streams (ISSUE 32): the wire, the ------
# ------- order, a client that stops reading, the counters ---------------

import socket      # noqa: E402
import sys         # noqa: E402
import threading   # noqa: E402

from batch_shipyard_tpu.models import server as server_mod  # noqa: E402


def _open_stream(address, payload, rcvbuf=None):
    """POST a streaming generate on a raw socket; the reply is left
    unread."""
    sock = socket.socket()
    if rcvbuf is not None:      # before connect: it sets the window
        sock.setsockopt(socket.SOL_SOCKET, socket.SO_RCVBUF, rcvbuf)
    sock.settimeout(120)
    sock.connect(tuple(address))
    body = json.dumps(dict(payload, stream=True)).encode()
    sock.sendall(b"POST /v1/generate HTTP/1.1\r\nHost: test\r\n"
                 b"Content-Type: application/json\r\n"
                 b"Content-Length: %d\r\n\r\n" % len(body) + body)
    return sock


def _read_reply(sock):
    """-> (head, raw chunked body) of one streamed reply."""
    data = b""
    while b"\r\n\r\n" not in data:
        data += sock.recv(65536)
    head, _, body = data.partition(b"\r\n\r\n")
    while not body.endswith(b"0\r\n\r\n"):
        more = sock.recv(65536)
        if not more:
            break
        body += more
    return head, body


def _dechunk(body):
    """The chunks of a chunked body, each as its raw bytes."""
    lines, at = [], 0
    while True:
        eol = body.index(b"\r\n", at)
        size = int(body[at:eol], 16)
        at = eol + 2
        if size == 0:
            assert body[at:] == b"\r\n"
            return lines
        lines.append(body[at:at + size])
        assert body[at + size:at + size + 2] == b"\r\n"
        at += size + 2


def _golden(objs):
    """The body the per-stream loop wrote before there was a writer:
    json.dumps of each line's object in _chunk's framing, then the
    terminating chunk."""
    out = b""
    for obj in objs:
        line = json.dumps(obj).encode() + b"\n"
        out += f"{len(line):x}\r\n".encode() + line + b"\r\n"
    return out + b"0\r\n\r\n"


def _stream(address, payload):
    """One streamed reply -> (head, raw body, its lines' objects)."""
    sock = _open_stream(address, payload)
    try:
        head, body = _read_reply(sock)
    finally:
        sock.close()
    return head, body, [json.loads(line) for line in _dechunk(body)]


def _throttle(engine, seconds):
    step = engine.step

    def slow_step():
        time.sleep(seconds)
        return step()

    engine.step = slow_step


def _until(condition, what, seconds=60.0):
    deadline = time.monotonic() + seconds
    while not condition():
        assert time.monotonic() < deadline, f"timed out: {what}"
        time.sleep(0.01)


FINAL_KEYS = ["request_id", "tokens", "num_tokens", "ttft_ms", "tpot_ms",
              "latency_ms", "slo_class"]


@pytest.mark.parametrize("kind", ["token", "final", "error", "draining",
                                  "shed"])
def test_a_streams_bytes_are_json_dumps_in_chunk_framing(kind, params):
    """(a) Byte for byte the wire of the per-stream loop: every line
    is json.dumps of its object + newline in chunked framing, then
    0 CRLF CRLF; per kind of line, the run that brings it."""
    engine = serving.ContinuousBatcher(
        CFG, params, num_slots=1 if kind == "shed" else 2,
        max_decode_len=64,
        slo_shed_grace_ms=0.0 if kind == "shed" else None)
    fe = ServingFrontEnd(engine, port=0).start()
    hog = None
    try:
        payload = {"prompt": [5, 17, 31, 2], "max_new_tokens": 6,
                   "request_id": "wire"}
        if kind == "error":
            payload["max_new_tokens"] = 100000
        elif kind == "draining":
            _post(fe.url, {"prompt": [1], "max_new_tokens": 2})
            _throttle(engine, 0.02)
            payload["max_new_tokens"] = 50
            threading.Thread(target=lambda: (
                _until(lambda: (fe.request_status("wire") or {}).get(
                    "emitted_tokens", 0) >= 3, "three tokens"),
                fe.drain(grace_s=0.0, reason="test")),
                daemon=True).start()
        elif kind == "shed":
            hog = threading.Thread(target=_post, args=(fe.url, {
                "request_id": "hog", "prompt": [7, 7],
                "max_new_tokens": 48}), daemon=True)
            hog.start()
            _until(lambda: fe.knows("hog"), "the hog is seated")
            payload["ttft_target_ms"] = 0.01
        head, body, objs = _stream(fe.address, payload)
        assert head.startswith(b"HTTP/1.1 200")
        assert b"Transfer-Encoding: chunked" in head
        assert b"Content-Type: application/x-ndjson" in head
        assert body == _golden(objs)
        last = objs[-1]
        if kind in ("token", "final"):
            assert list(last) == FINAL_KEYS and last["num_tokens"] == 6
            assert objs[:-1] == [{"token": token, "index": i} for i, token
                                 in enumerate(last["tokens"])]
            assert all(list(obj) == ["token", "index"]
                       for obj in objs[:-1])
        elif kind == "error":
            assert list(last) == ["error"] and len(objs) == 1
            assert "max_decode_len" in last["error"]
        elif kind == "draining":
            assert list(last) == ["error", "draining"]
            assert last["draining"] is True and "draining" in last["error"]
            # the tokens served before the notice, in order, first
            assert 3 <= len(objs) - 1 < 50
            assert [obj["index"] for obj in objs[:-1]] == list(
                range(len(objs) - 1))
        else:
            assert list(last) == ["error", "shed"] and len(objs) == 1
            assert last["shed"] is True and "shed" in last["error"]
        if hog is not None:
            hog.join(120)
    finally:
        fe.shutdown()


@pytest.mark.parametrize("n", [1, 8, 32])
def test_concurrent_streams_get_every_line_once_in_order(n, params):
    """(b) N streams at once: each gets every index once, in order,
    the prefill's token first and the final line last; the writer took
    at most one hand-over a landed step and one an admission, and
    wrote as many token lines as tokens were served."""
    engine = serving.ContinuousBatcher(CFG, params, num_slots=4,
                                       max_decode_len=64)
    fe = ServingFrontEnd(engine, port=0).start()
    got: dict = {}

    def client(k):
        got[k] = _stream(fe.address, {
            "request_id": f"s{k}", "prompt": [1 + k % 90] * (2 + k % 5),
            "max_new_tokens": 3 + k % 9})[2]

    interval = sys.getswitchinterval()
    try:
        # engine, writer, handlers and clients trade the GIL two
        # hundred times as often: a lost hand-over or a line out of
        # order would show
        sys.setswitchinterval(interval / 200)
        threads = [threading.Thread(target=client, args=(k,),
                                    daemon=True) for k in range(n)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(120)
        assert not any(t.is_alive() for t in threads)
        stats = fe.stats()
    finally:
        sys.setswitchinterval(interval)
        fe.shutdown()
    served = 0
    for k in range(n):
        *tokens, final = got[k]
        assert [obj["index"] for obj in tokens] == list(
            range(3 + k % 9)), k
        assert final["request_id"] == f"s{k}"
        assert final["tokens"] == [obj["token"] for obj in tokens]
        served += len(tokens)
    assert stats["stream_tokens_written"] == served
    assert stats["generated_tokens"] == served
    assert 0 < stats["stream_handovers"] <= (
        stats["engine"]["decode_steps"] + n)
    assert stats["streams_dropped_backlog"] == 0
    if n > 4:   # four slots, all streaming: a step feeds several
        assert stats["stream_handovers"] < served


@pytest.mark.parametrize("how", ["bound", "io_timeout"])
def test_a_client_that_never_reads_is_dropped_and_stalls_nobody(
        how, params, monkeypatch):
    """(c) A client with a tiny receive buffer that never reads: its
    sends are deferred, the other streams and the engine go on as
    usual, and once it owes more than the bound (or its socket has
    taken nothing for io_timeout_s) the stream is dropped and its
    registration retired, as a vanished client's is; the engine
    finishes the run."""
    if how == "bound":
        monkeypatch.setattr(server_mod._StreamWriter, "BACKLOG_LIMIT",
                            2048)
    engine = serving.ContinuousBatcher(CFG, params, num_slots=3,
                                       max_decode_len=1024)
    fe = ServingFrontEnd(engine, port=0,
                         io_timeout_s=0.5 if how == "io_timeout"
                         else None)
    # accepted connections inherit it: what the kernel holds for a
    # client that does not read stays small
    fe._httpd.socket.setsockopt(socket.SOL_SOCKET, socket.SO_SNDBUF,
                                4096)
    fe.start()

    def two_streams():
        t0 = time.monotonic()
        out: dict = {}
        threads = [threading.Thread(
            target=lambda k=k: out.update({k: _stream(fe.address, {
                "prompt": [3 + k, 9], "max_new_tokens": 24})[2]}),
            daemon=True) for k in range(2)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(120)
        assert sorted(out) == [0, 1]
        for objs in out.values():
            assert [obj["index"] for obj in objs[:-1]] == list(range(24))
            assert objs[-1]["num_tokens"] == 24
        return time.monotonic() - t0

    stuck = None
    try:
        two_streams()               # compiles
        usual = two_streams()
        stuck = _open_stream(fe.address, {
            "request_id": "stuck", "prompt": [2, 4, 6],
            "max_new_tokens": 900}, rcvbuf=1)
        _until(lambda: (fe.request_status("stuck") or {}).get(
            "emitted_tokens", 0) >= 1, "the stuck run decodes")
        beside = two_streams()
        assert beside < 10 * usual + 5
        _until(lambda: fe.stats()["streams_dropped_backlog"] == 1,
               "the stream is dropped")
        stats = fe.stats()
        assert stats["stream_sends_deferred"] > 0
        # the registration is retired at once; the engine finishes
        _until(lambda: "stuck" not in fe._inflight, "retired")
        _until(lambda: not fe.knows("stuck"), "the run ends", 120)
        assert stats["stream_tokens_written"] < 2 * 2 * 24 + 900 + 24
        assert not fe._active_runs and not fe._inflight
        assert two_streams() < 10 * usual + 5
    finally:
        if stuck is not None:
            stuck.close()
        fe.shutdown()


def test_a_client_that_closes_mid_stream_leaks_nothing(params):
    """(d) The client goes away between two token lines: the writer
    drops the stream on its next send, the handler retires the
    registration, the engine finishes the run on its own."""
    engine = serving.ContinuousBatcher(CFG, params, num_slots=2,
                                       max_decode_len=64)
    fe = ServingFrontEnd(engine, port=0).start()
    try:
        _post(fe.url, {"prompt": [1], "max_new_tokens": 2})
        _throttle(engine, 0.01)
        sock = _open_stream(fe.address, {
            "request_id": "gone", "prompt": [4, 4],
            "max_new_tokens": 50})
        data = b""
        while b'"index": 1}' not in data:
            data += sock.recv(65536)
        sock.close()
        _until(lambda: "gone" not in fe._inflight, "retired")
        _until(lambda: not fe.knows("gone"), "the run ends")
        assert not fe._active_runs and not fe._engine_active
        assert not fe._inflight
        stats = fe.stats()
        assert stats["streams_dropped_backlog"] == 0
        assert 2 <= stats["stream_tokens_written"] < 2 + 50
        # the id is free again and the server serves
        out = _stream(fe.address, {"request_id": "gone",
                                   "prompt": [4, 4],
                                   "max_new_tokens": 5})[2]
        assert out[-1]["num_tokens"] == 5
    finally:
        fe.shutdown()


class OnTokenOnlyEngine:
    """An engine that only knows on_token, called a token (as the
    stand-in under tests/benchmark/drivers/ does): request k's i-th
    token is 10 * k + i, one a step."""

    draining = False

    def __init__(self):
        self.on_token = self.on_admit = self.on_shed = None
        self.traced_steps = 0
        self._runs: dict = {}

    def submit(self, request, resumed=None):
        self._runs[request.request_id] = (request, [])

    def pending(self):
        return len(self._runs)

    def cancel(self, request_id):
        return self._runs.pop(request_id, None) is not None

    def step(self):
        finished = []
        for request_id, (request, out) in list(self._runs.items()):
            out.append(10 * request.prompt[0] + len(out))
            self.on_token(request_id, out[-1], len(out) - 1)
            if len(out) == request.max_new_tokens:
                del self._runs[request_id]
                finished.append((request_id, out))
        return finished


def test_an_engine_that_only_calls_on_token_streams_through_the_writer():
    """(e) on_token a token, never on_tokens: the same writer, a
    batch of one a call."""
    fe = ServingFrontEnd(OnTokenOnlyEngine(), port=0).start()
    got: dict = {}
    try:
        threads = [threading.Thread(
            target=lambda k=k: got.update({k: _stream(fe.address, {
                "prompt": [k], "max_new_tokens": 4 + k})}),
            daemon=True) for k in range(3)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(60)
        writer = fe._streams
        assert writer.handovers == writer.tokens_written == 4 + 5 + 6
        assert writer.sends_deferred == writer.dropped_backlog == 0
    finally:
        fe.shutdown()
    for k in range(3):
        _head, body, objs = got[k]
        assert body == _golden(objs)
        assert objs[:-1] == [{"token": 10 * k + i, "index": i}
                             for i in range(4 + k)]
        assert objs[-1]["tokens"] == [10 * k + i for i in range(4 + k)]


def test_tokens_landed_before_the_handler_registered_are_flushed(
        front):
    """(f) The run is over before the connection is handed to the
    writer: every line waited for it, and leaves in order at
    registration."""
    pending = front.submit_stream({
        "prompt": [5, 17, 31, 2], "max_new_tokens": 7,
        "request_id": "early"})
    assert pending.event.wait(60)
    _until(lambda: front._streams.tokens_written == 7, "lines kept")
    assert pending.stream.sock is None and pending.stream.unsent
    ours, theirs = socket.socketpair()
    try:
        assert front.serve_stream(pending, ours) is True
        assert ours.gettimeout() is None        # blocking again
        ours.close()
        body = b""
        while chunk := theirs.recv(65536):
            body += chunk
    finally:
        theirs.close()
    objs = [json.loads(line) for line in _dechunk(body)]
    assert body == _golden(objs)
    assert [obj["index"] for obj in objs[:-1]] == list(range(7))
    assert objs[-1]["tokens"] == [obj["token"] for obj in objs[:-1]]
    assert "early" not in front._inflight
    # a non-streaming request never touches the writer
    before = front._streams.handovers
    _post(front.url, {"prompt": [5, 17], "max_new_tokens": 4})
    assert front._streams.handovers == before == 1 + 6
