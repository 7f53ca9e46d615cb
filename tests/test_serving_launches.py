"""The device's timeline from the engine's own landings
(ContinuousBatcher._landed): one Launch record a landed decode step
or prefill, and what is made from it: the cumulative counters, the
admission estimates, a serve_step row's ``landed`` list and
``no_work_seconds``, and the stall record. CPU, float32, tiny models:
what is counted, that the periods tile the wall, when a landing finds
its result ready, what a stall record holds, and that the estimates
are the ones the two EWMA functions before it gave."""

import json
import logging
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from batch_shipyard_tpu.models import serving
from batch_shipyard_tpu.models import transformer as tfm
from batch_shipyard_tpu.models.server import ServingFrontEnd
from batch_shipyard_tpu.trace import export as trace_export

CFG = tfm.TransformerConfig(
    vocab_size=97, d_model=32, n_layers=2, n_heads=2, d_head=16,
    d_ff=64, max_seq_len=64, dtype=jnp.float32,
    param_dtype=jnp.float32)
KINDS = {
    "paged": lambda params: {"kv_page_size": 8},
    "dense": lambda params: {},
    "speculative": lambda params: {
        "speculative": serving.SpeculativeConfig(CFG, params, gamma=2)},
}


@pytest.fixture(scope="module")
def params():
    return tfm.TransformerLM(CFG).init(
        jax.random.PRNGKey(7), jnp.zeros((1, 8), jnp.int32))["params"]


def _engine(kind, params, **kwargs):
    return serving.ContinuousBatcher(
        CFG, params, num_slots=3, max_decode_len=64,
        **KINDS[kind](params), **kwargs)


def _requests(count, name="r", new=5):
    rng = np.random.RandomState(3)
    return [serving.Request(
        f"{name}{i}", [int(t) for t in rng.randint(1, 97, (4 + 5 * i,))],
        max_new_tokens=new + i) for i in range(count)]


def _drain(engine):
    done = {}
    for _ in range(500):
        if not engine.pending():
            return done
        done.update(engine.step())
    raise AssertionError("engine failed to drain")


def _landed(rows):
    return [launch for row in rows if row["kind"] == "serve_step"
            for launch in row["attrs"]["landed"]]


# ---------------- (a) every launch once, and the wall tiled ---------------

@pytest.mark.parametrize("kind", list(KINDS))
def test_every_landed_launch_is_in_one_row_with_its_admitted_twin(
        kind, params, recorder):
    engine = _engine(kind, params)
    built = engine._landed_at
    requests = _requests(5)
    for request in requests:
        engine.submit(request)
    _drain(engine)
    time.sleep(0.03)                    # a dry spell between two bursts
    late = _requests(2, name="late")
    for request in late:
        engine.submit(request)
    _drain(engine)
    rows = recorder()
    assert {row["kind"] for row in rows} == {"serve_step"}  # no stall
    stats = engine.step_stats()
    landed = _landed(rows)
    # every launch once: the counters saw what the rows hold
    by_kind = {k: [x for x in landed if x["kind"] == k]
               for k in serving.LAUNCH_KINDS}
    assert {k: len(v) for k, v in by_kind.items()} == stats["launches"]
    assert stats["launches"]["prefill"] == stats["prefills"] == 7
    assert stats["launches"]["decode"] == (
        engine.spec_rounds if kind == "speculative"
        else stats["decode_steps"])
    for k, entries in by_kind.items():
        assert sum(x["period_ms"] for x in entries) / 1e3 == \
            pytest.approx(stats["launch_seconds"][k])
        assert sum(x["ready"] for x in entries) == \
            stats["landings_ready"][k]
    when = [x["landed_at"] for x in landed]
    assert when == sorted(when) and len(set(when)) == len(when)
    assert all(x["period_ms"] > 0 and x["behind_ms"] >= 0
               for x in landed)
    assert all(1 <= x["rows"] <= 3 for x in by_kind["decode"])
    # a prefill's entry is its admitted twin's
    admitted = {a["request_id"]: a for row in rows
                for a in row["attrs"]["admitted"]}
    assert sorted(admitted) == sorted(
        r.request_id for r in requests + late)
    for entry in by_kind["prefill"]:
        twin = admitted[entry["request_id"]]
        assert {k: entry[k] for k in ("path", "bucket", "tokens")} == \
            {k: twin[k] for k in ("path", "bucket", "tokens")}
    assert stats["prefill_tokens"] == sum(
        a["tokens"] for a in admitted.values())
    assert stats["prefill_bucket_tokens"] == sum(
        a["bucket"] for a in admitted.values())
    # the dry spells: the engine's construction to the first dispatch,
    # and the nap, each on the row whose first dispatch ended it
    dry = [row["attrs"]["no_work_seconds"] for row in rows]
    assert sum(dry) == pytest.approx(stats["no_work_seconds"])
    # (the serial engine also runs dry where a round ends every
    # seated request with others still queued)
    assert dry[0] > 0 and 0.03 <= max(dry[1:]) < 0.03 + 0.5
    assert sum(1 for d in dry if d > 0) == 2 or kind == "speculative"
    assert stats["stalls"] == 0
    if kind == "speculative":
        return      # serial: between two launches the host works
    # the launches' periods and the dry spells tile the engine's life
    # up to its last landing
    wall = engine._landed_at - built
    covered = sum(x["period_ms"] for x in landed) / 1e3 + sum(dry)
    assert covered == pytest.approx(wall, rel=0.01)


def test_what_a_cancel_lands_is_on_the_next_row(params, recorder):
    engine = _engine("paged", params)
    stays, goes = _requests(2, new=12)
    engine.submit(stays)
    engine.submit(goes)
    for _ in range(3):
        engine.step()
    before = len(_landed(recorder()))
    assert engine._unread
    assert engine.cancel(goes.request_id)       # settles outside step()
    assert not engine._unread
    assert len(_landed(recorder())) == before
    engine.step()       # dispatches a step, has none of its own to land
    assert len(_landed(recorder())) == before + 1   # the settled one
    _drain(engine)
    assert {k: sum(x["kind"] == k for x in _landed(recorder()))
            for k in serving.LAUNCH_KINDS} == \
        engine.step_stats()["launches"]


def test_the_counters_run_with_the_recorder_off(params):
    engine = _engine("paged", params)
    for request in _requests(3):
        engine.submit(request)
    _drain(engine)
    stats = engine.step_stats()
    assert stats["launches"] == {"decode": stats["decode_steps"],
                                 "prefill": 3}
    assert all(stats["launch_seconds"][k] > 0
               for k in serving.LAUNCH_KINDS)
    assert set(stats["landings_ready"]) == set(serving.LAUNCH_KINDS)
    assert stats["prefill_bucket_tokens"] == 16 + 16 + 16
    assert stats["prefill_tokens"] == 4 + 9 + 14
    assert stats["no_work_seconds"] > 0 and stats["stalls"] == 0
    assert len(engine._ring) == min(
        serving.LAUNCH_RING, sum(stats["launches"].values()))
    assert engine.traced_steps == 0


def test_the_ring_keeps_the_last_launches_only(params):
    engine = _engine("dense", params)
    engine.submit(serving.Request("long", [1, 2, 3],
                                  max_new_tokens=60))
    engine.submit(serving.Request("more", [4, 5, 6],
                                  max_new_tokens=60))
    _drain(engine)
    engine.submit(serving.Request("again", [7, 8], max_new_tokens=9))
    _drain(engine)
    assert sum(engine.step_stats()["launches"].values()) > \
        serving.LAUNCH_RING
    assert len(engine._ring) == serving.LAUNCH_RING == 64
    assert engine._ring[-1].landed_at == engine._landed_at


# ---------------- (b) who set the pace of a landing -----------------------

def test_a_slow_host_finds_its_results_ready_and_a_fast_one_does_not():
    """A model whose CPU step takes milliseconds: the engine thread
    dispatches step k and waits for step k-1, so a landing finds its
    result NOT ready; an on_tokens hook that sleeps for several steps
    lets the device finish first, and the landings after it do."""
    config = tfm.TransformerConfig(
        vocab_size=2048, d_model=256, n_layers=4, n_heads=4, d_head=64,
        d_ff=1024, max_seq_len=64, dtype=jnp.float32,
        param_dtype=jnp.float32)
    weights = tfm.TransformerLM(config).init(
        jax.random.PRNGKey(1), jnp.zeros((1, 8), jnp.int32))["params"]

    def run(nap_s):
        engine = serving.ContinuousBatcher(
            config, weights, num_slots=4, max_decode_len=64,
            kv_page_size=8)
        if nap_s:
            engine.on_tokens = lambda batch: time.sleep(nap_s)
        for i in range(4):
            engine.submit(serving.Request(
                f"r{i}", list(range(1, 9)), max_new_tokens=24))
        _drain(engine)
        stats = engine.step_stats()
        return (stats["landings_ready"]["decode"]
                / stats["launches"]["decode"],
                engine.slo_stats()["step_ms"])

    share, step_ms = run(0)
    assert share < 0.5
    share, _ = run(max(0.02, 4 * step_ms / 1e3))
    assert share > 0.5


# ---------------- (c) the stall record ------------------------------------

class _SlowRead:
    """numpy, but ``asarray`` sleeps once when it is handed the array
    ``armed`` picks: a sleep inside the engine's wait."""

    def __init__(self, armed, nap_s):
        self.armed, self.nap_s, self.naps = armed, nap_s, 0

    def __getattr__(self, name):
        return getattr(np, name)

    def asarray(self, array, *args, **kwargs):
        if not self.naps and self.armed(array):
            self.naps += 1
            time.sleep(self.nap_s)
        return np.asarray(array, *args, **kwargs)


def _stalls(rows):
    return [row for row in rows if row["kind"] == "serve_stall"]


@pytest.mark.parametrize("wait,kind,armed", [
    ("readback", "decode", lambda a: getattr(a, "shape", None) == (3,)),
    ("prefill", "prefill", lambda a: getattr(a, "shape", None) == (1,)),
])
def test_a_landing_seconds_late_writes_one_stall_record(
        wait, kind, armed, params, recorder, monkeypatch, caplog):
    engine = _engine("dense", params)
    for request in _requests(3):
        engine.submit(request)
    _drain(engine)                      # every program is compiled
    assert _stalls(recorder()) == [] and engine.stalls == 0
    monkeypatch.setattr(serving, "STALL_MS", 200.0)
    slow = _SlowRead(armed, 0.5)
    monkeypatch.setattr(serving, "np", slow)
    for request in _requests(3, name="again"):
        engine.submit(request)
    with caplog.at_level(logging.WARNING,
                         logger=serving.logger.name):
        _drain(engine)
    assert slow.naps == 1
    (stall,) = _stalls(recorder())
    attrs = stall["attrs"]
    assert engine.step_stats()["stalls"] == 1
    assert (attrs["kind"], attrs["wait"]) == (kind, wait)
    launch = attrs["launch"]
    assert launch["kind"] == kind and launch["period_ms"] >= 500
    assert attrs["ready"] == launch["ready"]
    # the ring ends with the launch, and has what landed before it
    assert attrs["ring"][-1] == launch
    assert 1 < len(attrs["ring"]) <= serving.LAUNCH_RING
    # what the host did meanwhile: it slept, so its thread used no CPU
    assert 500 <= attrs["interval_ms"] < 5000
    assert 0 <= attrs["thread_cpu_s"] < 0.25
    for name in ("ru_utime_s", "ru_stime_s", "ru_nivcsw", "ru_majflt"):
        assert attrs[name] >= 0
    assert len(attrs["gc_collections"]) == 3
    assert len(attrs["loadavg"]) == 3 and attrs["threads"] >= 1
    assert (stall["end"] - stall["start"]) * 1e3 == \
        pytest.approx(attrs["interval_ms"])
    # the same record as one JSON line at WARNING
    (line,) = [r.getMessage() for r in caplog.records
               if r.getMessage().startswith("serve_stall ")]
    assert json.loads(line[len("serve_stall "):]) == attrs
    # the launch is on its row too
    assert launch in _landed(recorder())


def test_a_call_the_host_was_late_in_writes_the_host_form(
        params, recorder, monkeypatch, caplog):
    engine = _engine("dense", params)
    for request in _requests(3):
        engine.submit(request)
    _drain(engine)
    monkeypatch.setattr(serving, "STALL_MS", 400.0)
    naps = []

    def slow_emit(batch):
        if not naps and len(batch) > 1:     # a decode step's tokens
            naps.append(batch)
            time.sleep(0.2)

    engine.on_tokens = slow_emit
    for request in _requests(3, name="again"):
        engine.submit(request)
    with caplog.at_level(logging.WARNING,
                         logger=serving.logger.name):
        _drain(engine)
    assert len(naps) == 1
    (stall,) = _stalls(recorder())
    attrs = stall["attrs"]
    assert attrs["kind"] == "host" and attrs["phase"] == "emit"
    assert attrs["phase_ms"]["emit"] >= 200
    assert attrs["call_ms"] >= 200
    assert attrs["call_ms"] - attrs["landed_ms"] > 100
    assert attrs["interval_ms"] >= attrs["call_ms"]
    assert attrs["thread_cpu_s"] < 0.1 and attrs["ring"]
    assert "launch" not in attrs and "wait" not in attrs
    assert engine.stalls == 1
    assert sum(r.getMessage().startswith("serve_stall ")
               for r in caplog.records) == 1
    # the landing after it found its result ready: the host was late
    landed = _landed(recorder())
    late = next(i for i, x in enumerate(landed)
                if x["landed_at"] > attrs["ring"][-1]["landed_at"])
    assert landed[late]["ready"] and landed[late]["period_ms"] >= 200


def test_a_landing_that_compiled_is_no_stall(recorder, monkeypatch):
    """A width no other test uses, so its first landings hold a
    compile (or a load from the persistent cache) and take far longer
    than the threshold: ``compiles`` has those, not ``stalls``."""
    monkeypatch.setattr(serving, "STALL_MS", 1.0)
    config = tfm.TransformerConfig(
        vocab_size=97, d_model=40, n_layers=1, n_heads=2, d_head=20,
        d_ff=48, max_seq_len=64, dtype=jnp.float32,
        param_dtype=jnp.float32)
    weights = tfm.TransformerLM(config).init(
        jax.random.PRNGKey(2), jnp.zeros((1, 8), jnp.int32))["params"]
    engine = serving.ContinuousBatcher(config, weights, num_slots=1,
                                       max_decode_len=64)
    engine.submit(serving.Request("a", [1, 2, 3], max_new_tokens=1))
    before = engine.step_stats()["compiles"]
    engine.step()
    stats = engine.step_stats()
    assert stats["compiles"] > before
    assert stats["launches"]["prefill"] == 1
    assert stats["launch_seconds"]["prefill"] * 1e3 > serving.STALL_MS
    assert stats["stalls"] == 0 and _stalls(recorder()) == []


# ---------------- (d) the admission estimates -----------------------------

class _Before:
    """The two mechanisms _landed replaced, as they stood:
    _record_step_time and _record_prefill_time."""

    def __init__(self):
        self.step_ms = self.prefill_ms_per_token = None
        self.step_samples = 0
        self.timed_buckets = set()

    def step(self, dt_ms):
        self.step_samples += 1
        if self.step_samples == 1:
            return
        self.step_ms = dt_ms if self.step_ms is None else \
            0.7 * self.step_ms + 0.3 * dt_ms

    def prefill(self, key, dt_ms, n_tokens):
        if key not in self.timed_buckets:
            self.timed_buckets.add(key)
            return
        per_token = dt_ms / max(1, n_tokens)
        self.prefill_ms_per_token = per_token \
            if self.prefill_ms_per_token is None else \
            0.7 * self.prefill_ms_per_token + 0.3 * per_token


# (kind, period_ms, path, bucket): a recorded sequence of landings
SEQUENCE = [
    ("prefill", 900.0, "cold", 16), ("decode", 700.0, "", 0),
    ("decode", 2.0, "", 0), ("prefill", 8.0, "cold", 16),
    ("decode", 2.4, "", 0), ("prefill", 400.0, "cold", 64),
    ("decode", 1.9, "", 0), ("prefill", 40.0, "cold", 64),
    ("prefill", 300.0, "shared", 16), ("decode", 2.2, "", 0),
    ("prefill", 6.0, "shared", 16), ("prefill", 9.5, "cold", 16),
    ("decode", 30.0, "", 0), ("prefill", 20.0, "cold", 32),
    ("decode", 2.1, "", 0),
]


def test_the_estimates_and_the_deferrals_are_what_they_were(params):
    engine = _engine("paged", params, tpot_stall_factor=2.0)
    # one request decoding with a per-token target, two in the queue
    engine._slots[0] = serving._Slot(request=serving.Request(
        "seated", [1], max_new_tokens=9, tpot_target_ms=4.0))
    short = serving._QueueEntry(serving.Request(
        "short", [1] * 9, max_new_tokens=2), submitted_at=0.0)
    long_ = serving._QueueEntry(serving.Request(
        "long", [1] * 40, max_new_tokens=2), submitted_at=0.0)
    urgent = serving._QueueEntry(serving.Request(
        "urgent", [1] * 40, max_new_tokens=2, ttft_target_ms=1.0),
        submitted_at=0.0)
    before = _Before()
    now = 100.0
    decisions = []
    for kind, period_ms, path, bucket in SEQUENCE:
        now += period_ms / 1e3
        what = {} if kind == "decode" else {
            "path": path, "bucket": bucket, "tokens": bucket - 3,
            "request_id": "x"}
        engine._landed(serving.Launch(
            kind, now - period_ms / 1e3, now, period_ms, 0.0, False,
            1, **what))
        if kind == "decode":
            before.step(period_ms)
        else:
            before.prefill((path, bucket), period_ms, bucket)
        slo = engine.slo_stats()
        assert slo["step_ms"] == before.step_ms
        assert slo["prefill_ms_per_token"] == \
            before.prefill_ms_per_token

        def was(entry, tokens, deadline=None):
            # _should_defer as it stood, on the estimate as it stood
            per_token = before.prefill_ms_per_token
            if per_token is None:
                return False
            stall = engine._bucket_length(tokens) * per_token
            if stall <= 4.0 * 2.0:
                return False
            return not (deadline is not None
                        and now + stall / 1e3 >= deadline)

        got = [engine._should_defer(entry, now)
               for entry in (short, long_, urgent)]
        assert got == [was(short, 9), was(long_, 40),
                       was(urgent, 40, deadline=0.001)]
        decisions.append(got)
    assert engine.slo_stats()["step_ms"] == pytest.approx(
        7.957922, abs=1e-6)
    assert engine.slo_stats()["prefill_ms_per_token"] == pytest.approx(
        0.52025, abs=1e-9)
    # the sequence decides both ways
    assert [True, True, False] in decisions
    assert [False, True, False] in decisions
    assert [False, False, False] in decisions


# ---------------- (f) the export track, /v1/stats and /metrics ------------

def test_the_launches_export_on_a_track_beside_the_steps(params,
                                                         recorder):
    engine = _engine("paged", params)
    for request in _requests(2):
        engine.submit(request)
    _drain(engine)
    rows = [dict(row, task_id="serve-0", node_id="n1")
            for row in recorder()]
    rows.append({"kind": "task_run", "trace_id": "trace-1",
                 "span_id": "run-1", "parent_span_id": None,
                 "start": 0.0, "end": 9e9, "task_id": "serve-0",
                 "node_id": "n1", "attrs": {}})
    chrome = trace_export.to_chrome_trace(
        {"spans": rows, "goodput": []}, "trace-1")
    assert trace_export.validate_parent_links(chrome) == []
    track = [e for e in chrome["traceEvents"]
             if e["tid"] == "serve-0 device (as the engine saw it)"]
    assert len(track) == len(_landed(rows))
    assert {e["name"] for e in track} == {"decode", "prefill cold 16"}
    # back to back: a launch begins where the one before it landed,
    # unless it was dispatched later than that
    track.sort(key=lambda e: e["ts"])
    for earlier, later in zip(track, track[1:]):
        assert later["ts"] >= earlier["ts"] + earlier["dur"] - 1.0
        if later["args"]["behind_ms"] > 0:
            assert later["ts"] == pytest.approx(
                earlier["ts"] + earlier["dur"], abs=1.0)
    # on the steps' clock: inside the run of rows
    steps = [e for e in chrome["traceEvents"]
             if e["name"] == "serve_step"]
    assert min(e["ts"] for e in steps) - 1e6 <= track[0]["ts"]
    assert track[-1]["ts"] + track[-1]["dur"] <= \
        max(e["ts"] + e["dur"] for e in steps) + 1.0


def test_the_front_end_reports_the_counters_and_names_its_wait(
        params, caplog):
    engine = _engine("paged", params)
    for request in _requests(2):        # before the front end: not its
        engine.submit(request)
    _drain(engine)
    front = ServingFrontEnd(engine, port=0).start()
    try:
        time.sleep(0.05)        # parked: the wait is under its name
        assert front._waiting.prefix + "no_work" == "serve:no_work"
        result = front.generate({"prompt": [5, 6, 7],
                                 "max_new_tokens": 4})
        assert len(result["tokens"]) == 4
        block = front.stats()["engine"]
        lines = front.prometheus_metrics()
    finally:
        with caplog.at_level(logging.INFO):
            front.shutdown()
    assert front._waiting.total["no_work"] > 0
    assert block["launches"] == {"decode": block["decode_steps"],
                                 "prefill": 3}
    assert block["prefill_bucket_tokens"] == 48
    assert block["prefill_tokens"] == 4 + 9 + 3
    # shutdown() logs what landed since the front end took over
    (line,) = [r.getMessage() for r in caplog.records
               if r.getMessage().startswith("engine launches: ")]
    assert "prefill 1 (" in line and "decode 3 (" in line
    assert "prefill tokens 3 of 16 padded" in line
    # a model without routed layers has no grouped prefill
    assert "; prefills_grouped 0; " in line
    assert block["prefills_grouped"] == 0
    assert line.endswith("0 stalls")
    assert block["no_work_seconds"] > 0 and block["stalls"] == 0
    values = {line.rsplit(" ", 1)[0]: float(line.rsplit(" ", 1)[1])
              for line in lines if not line.startswith("#")}
    for kind in serving.LAUNCH_KINDS:
        label = '{kind="%s"}' % kind
        assert values[f"shipyard_serving_launches_total{label}"] == \
            block["launches"][kind]
        assert values[
            f"shipyard_serving_launch_seconds_total{label}"] > 0
        assert f"shipyard_serving_landings_ready_total{label}" in values
    assert values["shipyard_serving_prefill_bucket_tokens_total"] == 48
    assert values["shipyard_serving_prefill_tokens_total"] == 16
    assert values["shipyard_serving_prefills_total"] == \
        block["prefills"] == 3
    assert values["shipyard_serving_prefills_grouped_total"] == 0
    assert values["shipyard_serving_no_work_seconds_total"] > 0
    assert values["shipyard_serving_stalls_total"] == 0
