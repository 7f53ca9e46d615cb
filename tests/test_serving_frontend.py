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
