"""Serving fleet router (VERDICT r4 next #6): queue-depth-aware
dispatch across replica front ends, health-check rotation, failover,
sticky cancel, streaming passthrough, and loadgen-through-router."""

import json
import threading
import time
import urllib.error
import urllib.request

import jax
import jax.numpy as jnp
import pytest

from batch_shipyard_tpu.models import loadgen, serving
from batch_shipyard_tpu.models import transformer as tfm
from batch_shipyard_tpu.models.router import ServingRouter
from batch_shipyard_tpu.models.server import ServingFrontEnd

CFG = tfm.TransformerConfig(
    vocab_size=97, d_model=32, n_layers=2, n_heads=2, d_head=16,
    d_ff=64, max_seq_len=64, dtype=jnp.float32,
    param_dtype=jnp.float32)


@pytest.fixture(scope="module")
def params():
    model = tfm.TransformerLM(CFG)
    return model.init(jax.random.PRNGKey(7),
                      jnp.zeros((1, 8), jnp.int32))["params"]


def _front(params):
    engine = serving.ContinuousBatcher(CFG, params, num_slots=2,
                                       max_decode_len=64)
    return ServingFrontEnd(engine, port=0).start()


@pytest.fixture()
def fleet(params):
    fronts = [_front(params), _front(params)]
    router = ServingRouter([f.url for f in fronts],
                           health_interval=0.2).start()
    yield router, fronts
    router.shutdown()
    for f in fronts:
        try:
            f.shutdown()
        except Exception:
            pass


def _post(url, payload, timeout=120):
    req = urllib.request.Request(
        f"{url}/v1/generate", data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json"}, method="POST")
    with urllib.request.urlopen(req, timeout=timeout) as resp:
        return json.loads(resp.read())


def _get(url, path):
    with urllib.request.urlopen(f"{url}{path}", timeout=30) as resp:
        return resp.status, json.loads(resp.read())


def test_router_dispatches_and_balances(fleet):
    router, fronts = fleet
    seen = set()
    for k in range(4):
        out = _post(router.url, {"prompt": [1 + k, 2, 3],
                                 "max_new_tokens": 3})
        assert out["num_tokens"] == 3
        seen.add(out["_replica"])
    # Sequential idle-fleet requests alternate via the dispatched
    # tie-break: both replicas must have served.
    assert seen == {f.url for f in fronts}
    status, stats = _get(router.url, "/v1/stats")
    assert status == 200
    assert stats["completed"] == 4
    assert stats["healthy_replicas"] == 2
    assert all(s["completed"] >= 1 for s in stats["per_replica"])


def test_router_prefers_less_loaded_replica(fleet):
    router, _fronts = fleet
    # Occupy one replica with a long generation; concurrent short
    # requests must land on the other.
    long_done = {}

    def _long():
        long_done["r"] = _post(router.url, {
            "request_id": "long-run", "prompt": [9, 9, 9],
            "max_new_tokens": 40})

    t = threading.Thread(target=_long, daemon=True)
    t.start()
    # Wait until the router has the long run in flight.
    deadline = time.monotonic() + 20
    busy_url = None
    while time.monotonic() < deadline and busy_url is None:
        for snap in router.replicas():
            if snap["inflight"] > 0:
                busy_url = snap["url"]
        time.sleep(0.01)
    assert busy_url is not None
    short = _post(router.url, {"prompt": [4, 5], "max_new_tokens": 2})
    assert short["_replica"] != busy_url
    t.join(120)
    assert long_done["r"]["num_tokens"] == 40


def test_router_health_failover_and_503(fleet):
    router, fronts = fleet
    fronts[1].shutdown()
    # Next probe cycle marks it unhealthy.
    deadline = time.monotonic() + 10
    while time.monotonic() < deadline and router.healthy_count() != 1:
        time.sleep(0.05)
    assert router.healthy_count() == 1
    status, health = _get(router.url, "/healthz")
    assert status == 200 and health["healthy_replicas"] == 1
    # All traffic now goes to the survivor.
    for _ in range(3):
        out = _post(router.url, {"prompt": [1, 2],
                                 "max_new_tokens": 2})
        assert out["_replica"] == fronts[0].url
    fronts[0].shutdown()
    deadline = time.monotonic() + 10
    while time.monotonic() < deadline and router.healthy_count():
        time.sleep(0.05)
    with pytest.raises(urllib.error.HTTPError) as exc:
        _post(router.url, {"prompt": [1], "max_new_tokens": 1})
    assert exc.value.code == 503


def test_router_dispatch_failover_marks_unhealthy(fleet, params):
    """A replica that dies between probes: the dispatch itself fails
    over and flags it."""
    router, fronts = fleet
    victim = fronts[1]
    victim.shutdown()  # dies silently; probe hasn't run yet
    with router._lock:
        for r in router._replicas:
            r.healthy = True  # simulate stale healthy state
    for _ in range(4):
        out = _post(router.url, {"prompt": [3, 1],
                                 "max_new_tokens": 2})
        assert out["_replica"] == fronts[0].url
    snaps = {s["url"]: s for s in router.replicas()}
    assert snaps[victim.url]["healthy"] is False


def _poll(predicate, deadline_s: float = 60.0, interval: float = 0.02):
    """Poll-with-deadline (VERDICT r5 #7): on a saturated box any
    single fixed timeout flakes; the loop retries until the condition
    holds or the generous deadline expires."""
    deadline = time.monotonic() + deadline_s
    while time.monotonic() < deadline:
        if predicate():
            return True
        time.sleep(interval)
    return predicate()


def test_router_sticky_cancel(fleet):
    router, _fronts = fleet
    result = {}

    def _long():
        try:
            result["r"] = _post(router.url, {
                "request_id": "cancel-me", "prompt": [7, 7],
                "max_new_tokens": 60}, timeout=240)
        except urllib.error.HTTPError as exc:
            result["code"] = exc.code
            result["body"] = json.loads(exc.read())

    t = threading.Thread(target=_long, daemon=True)
    t.start()
    assert _poll(lambda: "cancel-me" in router._owner)
    # The owner mapping can exist before the replica has the run
    # registered (the POST is still in flight to it): poll the DELETE
    # until the owner answers 202 rather than asserting the first
    # attempt.
    cancel_result = {}

    def _cancelled():
        code, payload = router.cancel("cancel-me")
        cancel_result["code"] = code
        return code == 202

    assert _poll(_cancelled, deadline_s=60.0), cancel_result
    assert _poll(lambda: "code" in result or "r" in result,
                 deadline_s=120.0)
    t.join(10)
    # The replica completes the waiter with 409 cancelled.
    assert result.get("code") == 409, result
    assert "cancelled" in result["body"]["error"]


def test_router_broadcast_cancel_finds_unknown_owner(fleet):
    """A request the router never dispatched (server-assigned or
    submitted directly to a replica): broadcast probes replicas —
    non-owners 404, the owner 202s."""
    router, fronts = fleet
    result = {}

    def _long():
        try:
            result["r"] = _post(fronts[1].url, {
                "request_id": "direct-long", "prompt": [8, 8],
                "max_new_tokens": 60})
        except urllib.error.HTTPError as exc:
            result["code"] = exc.code

    t = threading.Thread(target=_long, daemon=True)
    t.start()
    deadline = time.monotonic() + 20
    while time.monotonic() < deadline and \
            not fronts[1].knows("direct-long"):
        time.sleep(0.01)
    assert "direct-long" not in router._owner
    code, payload = router.cancel("direct-long")
    assert code == 202, payload
    t.join(60)
    assert result.get("code") == 409
    # A fully unknown id 404s everywhere.
    code, payload = router.cancel("never-existed")
    assert code == 404


def test_router_rejects_duplicate_inflight_request_id(fleet):
    """A retry of a live id must not land on the OTHER replica and
    decode twice — the router gates ids fleet-wide (the per-replica
    front end can only see its own)."""
    router, _fronts = fleet
    result = {}

    def _long():
        result["r"] = _post(router.url, {
            "request_id": "dup-id", "prompt": [6, 6],
            "max_new_tokens": 50})

    t = threading.Thread(target=_long, daemon=True)
    t.start()
    deadline = time.monotonic() + 20
    while time.monotonic() < deadline and \
            "dup-id" not in router._owner:
        time.sleep(0.01)
    with pytest.raises(urllib.error.HTTPError) as exc:
        _post(router.url, {"request_id": "dup-id", "prompt": [1],
                           "max_new_tokens": 1})
    assert exc.value.code == 400
    assert "in flight" in json.loads(exc.value.read())["error"]
    t.join(120)
    assert result["r"]["num_tokens"] == 50
    # After completion the id is reusable.
    out = _post(router.url, {"request_id": "dup-id", "prompt": [2],
                             "max_new_tokens": 1})
    assert out["num_tokens"] == 1


def test_router_timeout_orphans_and_reconciles(params):
    """A dispatch that outlives request_timeout: 504 to the caller,
    NO re-dispatch (the run may still be live), the id stays gated
    until the health loop sees the replica forget it."""
    engine = serving.ContinuousBatcher(CFG, params, num_slots=2,
                                       max_decode_len=64)
    # Deterministic slowness: every engine step pays a fixed delay,
    # so a 50-token decode is guaranteed to outlive the 2s timeout.
    orig_step = engine.step
    engine.step = lambda: (time.sleep(0.1), orig_step())[1]
    fronts = [ServingFrontEnd(engine, port=0).start()]
    router = ServingRouter([fronts[0].url], health_interval=0.2,
                           request_timeout=2.0).start()
    try:
        with pytest.raises(urllib.error.HTTPError) as exc:
            _post(router.url, {"request_id": "slow", "prompt": [3, 3],
                               "max_new_tokens": 50})
        assert exc.value.code == 504
        # Still owned: a retry is refused while the run may be live.
        assert "slow" in router._owner
        with pytest.raises(urllib.error.HTTPError) as exc2:
            _post(router.url, {"request_id": "slow", "prompt": [1],
                               "max_new_tokens": 1})
        assert exc2.value.code == 400
        # Once the replica finishes (or we cancel) and forgets the
        # id, reconciliation releases it.
        fronts[0].cancel("slow")
        deadline = time.monotonic() + 30
        while time.monotonic() < deadline and \
                "slow" in router._owner:
            time.sleep(0.05)
        assert "slow" not in router._owner
        out = _post(router.url, {"request_id": "slow", "prompt": [2],
                                 "max_new_tokens": 1})
        assert out["num_tokens"] == 1
    finally:
        router.shutdown()
        fronts[0].shutdown()


def test_router_streaming_passthrough(fleet):
    router, _fronts = fleet
    req = urllib.request.Request(
        f"{router.url}/v1/generate",
        data=json.dumps({"prompt": [5, 6, 7], "max_new_tokens": 4,
                         "stream": True}).encode(),
        headers={"Content-Type": "application/json"}, method="POST")
    with urllib.request.urlopen(req, timeout=120) as resp:
        assert resp.headers["Content-Type"] == "application/x-ndjson"
        lines = [json.loads(line) for line in resp if line.strip()]
    tokens = [ln for ln in lines if "token" in ln]
    finals = [ln for ln in lines if "tokens" in ln]
    assert len(tokens) == 4
    assert len(finals) == 1 and finals[0]["num_tokens"] == 4


def test_loadgen_through_router(fleet):
    router, _fronts = fleet
    report = loadgen.run_load(router.url, num_requests=8,
                              rate_hz=50.0, prompt_len=(2, 6),
                              max_new_tokens=(2, 5), vocab_size=97,
                              seed=3)
    assert report["completed"] == 8
    assert report["failed"] == 0
    assert report["generated_tokens"] > 0
    status, stats = _get(router.url, "/v1/stats")
    assert stats["completed"] >= 8

def test_prometheus_metrics_endpoints(fleet):
    """Front end and router expose Prometheus text metrics the
    monitoring stack can scrape (docs/09-monitoring.md)."""
    router, fronts = fleet
    _post(router.url, {"prompt": [4, 2], "max_new_tokens": 3})

    def scrape(url):
        with urllib.request.urlopen(f"{url}/metrics",
                                    timeout=30) as resp:
            assert resp.headers["Content-Type"].startswith(
                "text/plain")
            return resp.read().decode()

    front_text = scrape(fronts[0].url)
    assert "shipyard_serving_completed_requests_total" in front_text
    assert 'shipyard_serving_ttft_ms{quantile="0.50"}' in front_text
    router_text = scrape(router.url)
    assert "shipyard_router_healthy_replicas 2" in router_text
    assert "shipyard_router_dispatched_total 1" in router_text
    assert ('shipyard_router_replica_healthy{replica="'
            + fronts[0].url + '"} 1') in router_text
    # Every line is NAME{labels} VALUE or NAME VALUE (parseable).
    for line in router_text.strip().splitlines():
        name, value = line.rsplit(" ", 1)
        float(value)


def test_failover_window_rejects_duplicate_request_id(params):
    """ADVICE r5 (medium): between a connection-error dispatch and the
    retry's re-registration, the duplicate-id gate must STILL hold —
    the claim is demoted to the reserved sentinel, never popped. A
    concurrent same-id POST inside that exact window is rejected."""
    import socket

    from batch_shipyard_tpu.models.router import DuplicateRequestError

    front = _front(params)
    # A port that refuses connections (bound then closed).
    probe = socket.socket()
    probe.bind(("127.0.0.1", 0))
    dead_url = f"http://127.0.0.1:{probe.getsockname()[1]}"
    probe.close()
    # Never start(): no probers run, replicas stay optimistic-healthy,
    # and dispatch() is exercised directly (it needs no HTTP thread).
    router = ServingRouter([dead_url, front.url],
                           health_interval=30.0)
    with router._lock:
        for r in router._replicas:
            if r.url == front.url:
                r.dispatched = 5  # tie-break: dead replica picked 1st
    observed = {}
    orig_mark = router._mark_unhealthy

    def duplicate_inside_window(replica, exc):
        # Runs after finish(retrying=True) and BEFORE the retry
        # iteration re-registers the owner — the historical window.
        try:
            router._claim("fo-dup")
            observed["window_open"] = True
        except DuplicateRequestError:
            observed["window_open"] = False
        orig_mark(replica, exc)

    router._mark_unhealthy = duplicate_inside_window
    try:
        code, payload = router.dispatch(
            {"request_id": "fo-dup", "prompt": [1, 2],
             "max_new_tokens": 2})
        assert code == 200
        assert payload["_replica"] == front.url
        # The dead replica WAS tried first (the window ran).
        assert observed.get("window_open") is False, observed
        # After completion the id is released for reuse.
        code, _payload = router.dispatch(
            {"request_id": "fo-dup", "prompt": [2],
             "max_new_tokens": 1})
        assert code == 200
    finally:
        front.shutdown()


def test_two_racing_posts_across_forced_failover(params):
    """ADVICE r5 closure proof, adversarial form: TWO genuinely
    concurrent dispatches of the SAME request_id race while the
    router is mid-failover (dead replica tried first). Exactly one
    may decode; the other must be rejected by the duplicate gate —
    and the single surviving replica must have served exactly one
    request with that id. A real second thread (not just a probe
    inside the window) pins the whole claim/reserve/failover
    interleaving."""
    import socket

    from batch_shipyard_tpu.models.router import DuplicateRequestError

    front = _front(params)
    probe = socket.socket()
    probe.bind(("127.0.0.1", 0))
    dead_url = f"http://127.0.0.1:{probe.getsockname()[1]}"
    probe.close()
    router = ServingRouter([dead_url, front.url],
                           health_interval=30.0)
    with router._lock:
        for r in router._replicas:
            if r.url == front.url:
                r.dispatched = 5  # tie-break: dead replica first
    results: dict = {"ok": 0, "dup": 0, "other": []}
    results_lock = threading.Lock()
    # Deterministic interleaving: racer B fires the moment racer A
    # enters the failover window (after finish(retrying=True), before
    # the retry re-registers) — the historical double-decode window.
    window_entered = threading.Event()
    second_done = threading.Event()
    orig_mark = router._mark_unhealthy

    def mark_and_hold(replica, exc):
        orig_mark(replica, exc)
        window_entered.set()
        second_done.wait(timeout=30)  # keep A inside the window

    router._mark_unhealthy = mark_and_hold

    def racer(wait_for_window):
        if wait_for_window:
            window_entered.wait(timeout=30)
        try:
            code, payload = router.dispatch(
                {"request_id": "race-1", "prompt": [1, 2],
                 "max_new_tokens": 2})
            with results_lock:
                if code == 200:
                    results["ok"] += 1
                else:
                    results["other"].append((code, payload))
        except DuplicateRequestError:
            with results_lock:
                results["dup"] += 1
        except Exception as exc:  # noqa: BLE001 - recorded, asserted
            with results_lock:
                results["other"].append(repr(exc))
        finally:
            if wait_for_window:
                second_done.set()

    threads = [threading.Thread(target=racer, args=(False,)),
               threading.Thread(target=racer, args=(True,))]
    try:
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
        assert results["ok"] == 1, results
        assert results["dup"] == 1, results
        assert not results["other"], results
        # The fleet decoded the id exactly once.
        with urllib.request.urlopen(f"{front.url}/v1/stats",
                                    timeout=10) as resp:
            stats = json.loads(resp.read())
        assert stats.get("completed_requests") == 1, stats
        # The id is released after completion: a THIRD post reuses it.
        code, _ = router.dispatch(
            {"request_id": "race-1", "prompt": [3],
             "max_new_tokens": 1})
        assert code == 200
    finally:
        front.shutdown()


def test_router_midstream_timeout_orphans_ownership(params):
    """ADVICE r5 (medium): a mid-stream read timeout means the run may
    still be live on the (slow) replica — ownership must survive into
    orphan reconciliation, keeping the duplicate gate shut, instead of
    being popped by finish(ok=False)."""
    engine = serving.ContinuousBatcher(CFG, params, num_slots=2,
                                       max_decode_len=64)
    orig_step = engine.step
    engine.step = lambda: (time.sleep(1.0), orig_step())[1]
    front = ServingFrontEnd(engine, port=0).start()
    router = ServingRouter([front.url], health_interval=0.2,
                           request_timeout=0.5).start()
    try:
        req = urllib.request.Request(
            f"{router.url}/v1/generate",
            data=json.dumps({"request_id": "slow-stream",
                             "prompt": [3, 3], "max_new_tokens": 8,
                             "stream": True}).encode(),
            headers={"Content-Type": "application/json"},
            method="POST")
        with urllib.request.urlopen(req, timeout=60) as resp:
            lines = [json.loads(line) for line in resp
                     if line.strip()]
        # The router terminated the client stream with an error line.
        assert any("error" in ln for ln in lines), lines
        # Ownership survived the timeout: the id is orphaned, not
        # released, and a retry is refused while the run may be live.
        assert "slow-stream" in router._owner
        assert "slow-stream" in router._orphaned
        with pytest.raises(urllib.error.HTTPError) as exc:
            _post(router.url, {"request_id": "slow-stream",
                               "prompt": [1], "max_new_tokens": 1})
        assert exc.value.code == 400
        # Once the replica forgets the run, reconciliation releases.
        front.cancel("slow-stream")
        assert _poll(lambda: "slow-stream" not in router._owner,
                     deadline_s=60.0)
        assert "slow-stream" not in router._orphaned
    finally:
        router.shutdown()
        front.shutdown()


def test_owner_ttl_retires_stale_entries_resubmit_safe(fleet):
    """TTL retirement of finished/leaked ownership entries: a stale
    RESERVED claim retires unconditionally, a stale LIVE entry retires
    once the owning replica provably forgot the id (404 probe) — and a
    retired id is immediately safe to resubmit (the regression the
    sweep must not introduce: dropping an id reopens the duplicate
    gate cleanly, without double-decode)."""
    router, fronts = fleet
    past = time.time() - 10_000
    with router._lock:
        replica = next(r for r in router._replicas
                       if r.url == fronts[0].url)
        router._owner["stale-reserved"] = None
        router._owner_stamp["stale-reserved"] = past
        router._owner["stale-live"] = replica
        router._owner_stamp["stale-live"] = past
    router._retire_stale()
    assert "stale-reserved" not in router._owner
    # The replica never knew "stale-live": the probe 404s, so the
    # leaked mapping is dropped too.
    assert "stale-live" not in router._owner
    assert not router._owner_stamp
    for rid in ("stale-reserved", "stale-live"):
        out = _post(router.url, {"request_id": rid, "prompt": [1, 2],
                                 "max_new_tokens": 2})
        assert out["num_tokens"] == 2


def test_owner_ttl_spares_live_decode(fleet):
    """The PR 10 failover-race guarantee survives any TTL: an id the
    owning replica still knows (a genuinely long decode) is NOT
    retired — its stamp refreshes instead, so the duplicate gate and
    sticky cancel keep working."""
    router, fronts = fleet
    result = {}

    def _long():
        result["r"] = _post(router.url, {
            "request_id": "ttl-live", "prompt": [2, 2],
            "max_new_tokens": 60}, timeout=240)

    t = threading.Thread(target=_long, daemon=True)
    t.start()
    assert _poll(lambda: any(f.knows("ttl-live") for f in fronts))
    with router._lock:
        router._owner_stamp["ttl-live"] = time.time() - 10_000
    router._retire_stale()
    assert "ttl-live" in router._owner
    assert time.time() - router._owner_stamp["ttl-live"] < 100, \
        "stamp not refreshed after a live probe"
    with pytest.raises(urllib.error.HTTPError) as exc:
        _post(router.url, {"request_id": "ttl-live", "prompt": [1],
                           "max_new_tokens": 1})
    assert exc.value.code == 400  # gate still shut
    t.join(120)
    assert result["r"]["num_tokens"] == 60


def test_prefix_affinity_routes_to_same_replica(fleet):
    """Requests sharing a client prefix key land on the replica whose
    KV pool holds the prefix pages; derived keys hash the first-N
    prompt tokens; affinity entries are pure hints retired by TTL."""
    router, _fronts = fleet
    urls = set()
    for k in range(4):
        out = _post(router.url, {"prompt": [k, 1, 2],
                                 "prefix_key": "tmpl-A",
                                 "max_new_tokens": 2})
        urls.add(out["_replica"])
    assert len(urls) == 1, "affinity failed to stick"
    assert router.affinity_routed >= 3
    _status, stats = _get(router.url, "/v1/stats")
    assert stats["affinity_routed"] >= 3
    # Derived keys: identical heads agree, short prompts get none.
    head = list(range(32))
    k1 = router._affinity_key({"prompt": head + [99]})
    k2 = router._affinity_key({"prompt": head + [7, 8]})
    assert k1 is not None and k1 == k2
    assert router._affinity_key({"prompt": [5] * 31}) is None
    assert router._affinity_key(
        {"prefix_key": "x", "prompt": head}) == "client:x"
    # TTL drops affinity hints (no probe needed — they are not
    # correctness state).
    with router._lock:
        for key in list(router._affinity):
            router._affinity[key] = (router._affinity[key][0],
                                     time.time() - 10_000)
    router._retire_stale()
    assert not router._affinity


def test_prefix_affinity_yields_under_load_imbalance(fleet):
    """Stickiness must not create hot spots: when the sticky replica
    is more than affinity_load_slack ahead of the least-loaded one,
    the request routes away (and re-homes the prefix there)."""
    router, _fronts = fleet
    out = _post(router.url, {"prompt": [1, 2], "prefix_key": "hot",
                             "max_new_tokens": 1})
    sticky_url = out["_replica"]
    with router._lock:
        for r in router._replicas:
            if r.url == sticky_url:
                r.inflight += 10  # simulated hot spot
    try:
        out2 = _post(router.url, {"prompt": [3, 4],
                                  "prefix_key": "hot",
                                  "max_new_tokens": 1})
        assert out2["_replica"] != sticky_url
    finally:
        with router._lock:
            for r in router._replicas:
                if r.url == sticky_url:
                    r.inflight -= 10


def test_stalled_probe_does_not_delay_other_replica_detection(params):
    """ADVICE r5 (low): with long-lived per-replica probers, a hung
    probe on replica A must not stretch fault detection for replica B
    — the old per-interval thread sweep joined on the slowest probe
    (probe_timeout*2+1) before re-probing anyone."""
    from http.server import ThreadingHTTPServer

    from batch_shipyard_tpu.models.server import JsonRequestHandler

    stall = threading.Event()

    class StallableHandler(JsonRequestHandler):
        def do_GET(self):  # noqa: N802
            if stall.is_set():
                time.sleep(15)  # hang past the detection deadline
            if self.path == "/healthz":
                self._reply(200, {"ok": True})
            else:
                self._reply(200, {"engine_backlog": 0})

    stall_srv = ThreadingHTTPServer(("127.0.0.1", 0),
                                    StallableHandler)
    threading.Thread(target=stall_srv.serve_forever,
                     daemon=True).start()
    host, port = stall_srv.server_address[:2]
    front_b = _front(params)
    router = ServingRouter([f"http://{host}:{port}", front_b.url],
                           health_interval=0.2).start()
    try:
        assert _poll(lambda: router.healthy_count() == 2,
                     deadline_s=10.0)
        stall.set()
        time.sleep(0.5)  # let A's prober enter the hang
        front_b.shutdown()
        detected_at = time.monotonic()
        assert _poll(
            lambda: {s["url"]: s["healthy"]
                     for s in router.replicas()}[front_b.url] is False,
            deadline_s=3.0), \
            "replica B's failure not detected while A's probe hung"
        assert time.monotonic() - detected_at < 3.5
    finally:
        stall_srv.shutdown()
        stall_srv.server_close()
        router.shutdown()
