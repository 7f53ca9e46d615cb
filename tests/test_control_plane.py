"""Control-plane partition tolerance (ISSUE 13).

Three layers under test:

  * lease-based sweep leadership with monotonic fencing epochs
    (state/leases.py) — acquisition exclusivity, partition
    abdication on the local clock, epoch monotonicity, fencing;
  * store-outage ride-through (state/resilient.py) — critical-op
    retry, advisory WAL ordering/coalescing, replay idempotence,
    crash-restart backlog drain, the store_outage pricing event;
  * agent crash-restart adoption (slot ledger + watcher) — the
    exited-while-unowned classification path, plus the three seeded
    chaos drills that pin the whole stack end to end.
"""

import json
import os
import threading
import time

import pytest

from batch_shipyard_tpu.state import leases as state_leases
from batch_shipyard_tpu.state import names
from batch_shipyard_tpu.state.base import NotFoundError
from batch_shipyard_tpu.state.memory import MemoryStateStore
from batch_shipyard_tpu.state.resilient import ResilientStore

LEASE_KEY = "leader/testpool/role"
EPOCH_KEY = "leader/testpool/role.epoch"


def _lease(store, owner, duration=0.6, blocked=None):
    return state_leases.LeaderLease(
        store, LEASE_KEY, EPOCH_KEY, owner,
        duration_seconds=duration, blocked=blocked)


# ------------------------------- leases --------------------------------

def test_lease_exclusive_and_epoch_monotonic():
    store = MemoryStateStore()
    a, b = _lease(store, "A"), _lease(store, "B")
    e1 = a.epoch()
    assert e1 is not None
    # Held: the second owner cannot acquire, and re-entry by the
    # holder stays in the SAME term (no epoch churn).
    assert b.epoch() is None
    assert a.epoch() == e1
    assert a.fenced(e1)
    info = state_leases.read_leader(store, EPOCH_KEY)
    assert info["owner"] == "A" and info["epoch"] == e1
    # Graceful release: the successor acquires immediately, in a NEW
    # strictly-later term.
    a.release()
    e2 = b.epoch()
    assert e2 is not None and e2 > e1
    assert not a.fenced(e1)


def test_lease_partition_abdicates_before_successor():
    """THE double-leader window test: a holder partitioned from the
    store loses local authority (fenced() false, epoch() None)
    strictly before the successor can acquire — at no instant do two
    owners both believe they lead."""
    store = MemoryStateStore()
    blocked = [False]
    a = _lease(store, "A", duration=0.5,
               blocked=lambda: blocked[0])
    b = _lease(store, "B", duration=0.5)
    e1 = a.epoch()
    assert e1 is not None
    blocked[0] = True
    # Poll both sides through the handover: record any instant where
    # both claim authority.
    overlap = False
    b_epoch = None
    deadline = time.monotonic() + 2.0
    while time.monotonic() < deadline:
        a_has = a.fenced(e1)
        b_epoch = b.epoch()
        if a_has and b_epoch is not None:
            overlap = True
        if b_epoch is not None:
            break
        time.sleep(0.02)
    assert b_epoch is not None, "successor never acquired"
    assert not overlap, "double leader: both held authority at once"
    assert b_epoch > e1
    # The deposed holder knows it on its own clock, store unreachable.
    assert a.epoch() is None


def test_lease_epoch_bump_failure_abdicates():
    """A leader that cannot record its fencing epoch must not act:
    the acquisition is rolled back (lease released) so a functional
    peer can lead instead."""
    store = MemoryStateStore()

    class NoEpochStore:
        def __getattr__(self, name):
            attr = getattr(store, name)
            if name == "put_object":
                def broken(*a, **k):
                    raise RuntimeError("epoch object unwritable")
                return broken
            return attr

    a = state_leases.LeaderLease(NoEpochStore(), LEASE_KEY,
                                 EPOCH_KEY, "A",
                                 duration_seconds=0.5)
    assert a.epoch() is None
    b = _lease(store, "B", duration=0.5)
    assert b.epoch() is not None


# --------------------------- resilient store ---------------------------

class FlakyStore:
    """Transport-failure wrapper: every op raises while .down."""

    def __init__(self, inner):
        self.inner = inner
        self.down = False
        self.calls = []

    def __getattr__(self, name):
        attr = getattr(self.inner, name)
        if not callable(attr):
            return attr

        def call(*args, **kwargs):
            self.calls.append(name)
            if self.down:
                raise RuntimeError("store down")
            return attr(*args, **kwargs)
        return call


def _resilient(flaky, tmp_path, **kw):
    kw.setdefault("retry_base", 0.02)
    kw.setdefault("retry_cap", 0.1)
    kw.setdefault("probe_interval", 0.05)
    return ResilientStore(flaky, str(tmp_path / "wal.jsonl"),
                          pool_id="testpool", node_id="n0", **kw)


def test_resilient_critical_retries_and_prices_outage(tmp_path):
    raw = MemoryStateStore()
    flaky = FlakyStore(raw)
    rs = _resilient(flaky, tmp_path)
    flaky.down = True
    threading.Timer(0.25, lambda: setattr(flaky, "down",
                                          False)).start()
    t0 = time.monotonic()
    rs.insert_entity(names.TABLE_TASKS, "p$j", "t0",
                     {"state": "pending"})
    assert time.monotonic() - t0 >= 0.2
    # The op landed exactly once and the outage window was priced
    # with the exact [first-failure, first-success] interval.
    assert raw.get_entity(names.TABLE_TASKS, "p$j",
                          "t0")["state"] == "pending"
    outages = [r for r in raw.query_entities(names.TABLE_GOODPUT)
               if r["kind"] == "store_outage"]
    assert len(outages) == 1
    assert outages[0]["end"] - outages[0]["start"] >= 0.2
    assert outages[0]["node_id"] == "n0"


def test_resilient_put_stream_rides_outage_untorn(tmp_path):
    """put_object_stream is critical (output uploads are what the
    completion path's classification hangs on) AND retry-safe: the
    single-shot chunk iterator is spooled locally once, so a retry
    after a failed attempt re-streams the WHOLE payload — never a
    torn object from a half-consumed iterator."""
    raw = MemoryStateStore()
    flaky = FlakyStore(raw)
    rs = _resilient(flaky, tmp_path)
    payload = [b"aa", b"bb", b"cc"]
    consumed = []

    def chunks():
        for block in payload:
            consumed.append(block)
            yield block

    flaky.down = True
    threading.Timer(0.25, lambda: setattr(flaky, "down",
                                          False)).start()
    rs.put_object_stream("outputs/k", chunks())
    assert raw.get_object("outputs/k") == b"aabbcc"
    # The caller's iterator was consumed exactly once, up front.
    assert consumed == payload
    # And the ride-through was priced like any critical op's.
    outages = [r for r in raw.query_entities(names.TABLE_GOODPUT)
               if r["kind"] == "store_outage"]
    assert len(outages) == 1


def test_resilient_get_stream_retries_open(tmp_path):
    """get_object_stream retries open + first chunk through an
    outage (backends implement it as a generator, so the bare call
    never fails); a missing key still surfaces as NotFoundError at
    the call."""
    raw = MemoryStateStore()
    raw.put_object("k", b"x" * 100)
    flaky = FlakyStore(raw)
    rs = _resilient(flaky, tmp_path)
    flaky.down = True
    threading.Timer(0.2, lambda: setattr(flaky, "down",
                                         False)).start()
    assert b"".join(rs.get_object_stream("k")) == b"x" * 100
    with pytest.raises(NotFoundError):
        list(rs.get_object_stream("missing"))


def test_resilient_critical_ceiling_survives_latch_flap(tmp_path):
    """The retry ceiling is per-CALL, not per-latch: a deterministic
    caller error failing against a healthy store keeps re-latching
    an 'outage' that concurrent advisory probes immediately clear —
    a latch-based clock would restart from ~0 every attempt and
    retry forever. The call must hit StoreOutageError at the
    ceiling regardless of the flapping."""
    from batch_shipyard_tpu.state.resilient import StoreOutageError

    raw = MemoryStateStore()

    class OneOpBroken:
        def __init__(self, inner):
            self.inner = inner

        def __getattr__(self, name):
            attr = getattr(self.inner, name)
            if name == "merge_entity":
                def broken(*a, **k):
                    raise RuntimeError("caller bug")
                return broken
            return attr

    rs = ResilientStore(OneOpBroken(raw),
                        str(tmp_path / "wal.jsonl"),
                        pool_id="testpool", node_id="n0",
                        retry_base=0.02, retry_cap=0.05,
                        probe_interval=0.01,
                        max_outage_seconds=0.4)
    stop = threading.Event()

    def flapper():
        while not stop.is_set():
            # Healthy advisory traffic: journals under the latch,
            # probes, recovers — flapping the latch open.
            rs.insert_entity(names.TABLE_GOODPUT, "testpool",
                             f"f{time.monotonic()}", {"kind": "idle",
                                                      "start": 0,
                                                      "end": 1})
            time.sleep(0.02)

    thread = threading.Thread(target=flapper, daemon=True)
    thread.start()
    try:
        t0 = time.monotonic()
        with pytest.raises(StoreOutageError):
            rs.merge_entity(names.TABLE_TASKS, "p$j", "t",
                            {"state": "x"})
        assert time.monotonic() - t0 < 5.0
    finally:
        stop.set()
        thread.join(timeout=5.0)


def test_resilient_semantic_errors_propagate(tmp_path):
    rs = _resilient(FlakyStore(MemoryStateStore()), tmp_path)
    with pytest.raises(NotFoundError):
        rs.get_entity(names.TABLE_TASKS, "p$j", "missing")
    # No outage was latched by a successful round trip.
    assert rs.journal_backlog() == 0


def test_resilient_advisory_wal_order_and_replay(tmp_path):
    raw = MemoryStateStore()
    flaky = FlakyStore(raw)
    rs = _resilient(flaky, tmp_path, probe_interval=3600.0)
    flaky.down = True
    for i in range(4):
        rs.insert_entity(names.TABLE_GOODPUT, "testpool",
                         f"{i:03d}$r", {"kind": "idle", "seq": i,
                                        "start": i, "end": i + 1})
    assert rs.journal_backlog() == 4
    assert os.path.exists(tmp_path / "wal.jsonl")
    # Recovery through a critical op replays IN ORDER.
    flaky.down = False
    rs.queue_length("q")
    assert rs.journal_backlog() == 0
    rows = sorted(raw.query_entities(names.TABLE_GOODPUT),
                  key=lambda r: r["_rk"])
    seqs = [r["seq"] for r in rows if r["kind"] == "idle"]
    assert seqs == [0, 1, 2, 3]
    assert not os.path.exists(tmp_path / "wal.jsonl")


def test_resilient_heartbeat_coalescing(tmp_path):
    raw = MemoryStateStore()
    flaky = FlakyStore(raw)
    rs = _resilient(flaky, tmp_path, probe_interval=3600.0)
    raw.upsert_entity(names.TABLE_NODES, "testpool", "n0",
                      {"state": "idle"})
    flaky.down = True
    for beat in range(10):
        rs.merge_entity(names.TABLE_NODES, "testpool", "n0",
                        {"heartbeat_at": float(beat),
                         "running_tasks": beat % 3})
    # O(entities), not O(outage duration) — and the merged payload
    # is the NEWEST.
    assert rs.journal_backlog() == 1
    flaky.down = False
    rs.queue_length("q")
    node = raw.get_entity(names.TABLE_NODES, "testpool", "n0")
    assert node["heartbeat_at"] == 9.0
    assert node["state"] == "idle"


def test_resilient_coalescing_respects_op_boundaries(tmp_path):
    """Coalescing folds repeats into the NEWEST same-op entry only
    (review fix): an upsert journaled between two merges is a full-
    row replace — folding the later merge backwards across it (or
    replaying the upsert with merge semantics) would resurrect
    columns the upsert dropped."""
    raw = MemoryStateStore()
    flaky = FlakyStore(raw)
    rs = _resilient(flaky, tmp_path, probe_interval=3600.0)
    raw.upsert_entity(names.TABLE_NODES, "testpool", "n0",
                      {"state": "idle", "extra": "stale"})
    flaky.down = True
    rs.merge_entity(names.TABLE_NODES, "testpool", "n0",
                    {"heartbeat_at": 1.0})
    rs.upsert_entity(names.TABLE_NODES, "testpool", "n0",
                     {"state": "running"})
    rs.merge_entity(names.TABLE_NODES, "testpool", "n0",
                    {"heartbeat_at": 2.0})
    # Three entries: the trailing merge must not cross the upsert.
    assert rs.journal_backlog() == 3
    flaky.down = False
    rs.queue_length("q")
    assert rs.journal_backlog() == 0
    node = raw.get_entity(names.TABLE_NODES, "testpool", "n0")
    assert node["state"] == "running"
    assert node["heartbeat_at"] == 2.0
    # The upsert's replace semantics survived the journal.
    assert "extra" not in node


def test_resilient_replay_idempotent_after_crash(tmp_path):
    """Crash-mid-replay: entries already applied re-insert into
    EntityExistsError, which replay treats as success — no
    double-counted intervals."""
    raw = MemoryStateStore()
    flaky = FlakyStore(raw)
    rs = _resilient(flaky, tmp_path, probe_interval=3600.0)
    flaky.down = True
    rs.insert_entity(names.TABLE_GOODPUT, "testpool", "000$r",
                     {"kind": "idle", "start": 0, "end": 1})
    rs.insert_entity(names.TABLE_GOODPUT, "testpool", "001$r",
                     {"kind": "idle", "start": 1, "end": 2})
    # Simulate the crash: the first entry was ALREADY applied before
    # the journal could be trimmed.
    raw.insert_entity(names.TABLE_GOODPUT, "testpool", "000$r",
                      {"kind": "idle", "start": 0, "end": 1})
    flaky.down = False
    # A fresh wrapper over the same journal (the restarted agent).
    rs2 = _resilient(flaky, tmp_path)
    assert rs2.journal_backlog() == 2
    rs2.queue_length("q")
    assert rs2.journal_backlog() == 0
    rows = [r for r in raw.query_entities(names.TABLE_GOODPUT)
            if r["kind"] == "idle"]
    assert len(rows) == 2


def test_resilient_wal_survives_restart(tmp_path):
    flaky = FlakyStore(MemoryStateStore())
    rs = _resilient(flaky, tmp_path, probe_interval=3600.0)
    flaky.down = True
    rs.insert_entity(names.TABLE_GOODPUT, "testpool", "000$r",
                     {"kind": "idle", "start": 0, "end": 1})
    del rs  # the agent process dies with a backlog
    flaky.down = False
    rs2 = _resilient(flaky, tmp_path)
    assert rs2.journal_backlog() == 1
    rs2.queue_length("q")
    assert rs2.journal_backlog() == 0
    assert len(list(flaky.inner.query_entities(
        names.TABLE_GOODPUT))) == 1


def test_resilient_fresh_advisory_queues_behind_undrained_backlog(
        tmp_path):
    """Latch-close vs replay-drain race (review fix): until the
    backlog is fully drained, a fresh advisory write must NOT bypass
    the journal — the replay of its own entity's stale journaled
    value would overwrite it, moving heartbeat_at backwards and
    letting sibling nodes orphan-reclaim a live node's tasks."""
    raw = MemoryStateStore()
    flaky = FlakyStore(raw)
    raw.upsert_entity(names.TABLE_NODES, "testpool", "n0",
                      {"state": "idle"})
    rs = _resilient(flaky, tmp_path, probe_interval=3600.0)
    flaky.down = True
    rs.merge_entity(names.TABLE_NODES, "testpool", "n0",
                    {"heartbeat_at": 1.0})
    assert rs.journal_backlog() == 1
    del rs  # agent dies with the stale beat journaled
    flaky.down = False
    # Restarted wrapper: backlog loaded, store healthy, NO latch.
    rs2 = _resilient(flaky, tmp_path, probe_interval=3600.0)
    assert rs2.journal_backlog() == 1
    assert not rs2.outage_active()
    # Pin the drain mid-flight: a concurrent replay owns the lock.
    assert rs2._replay_lock.acquire(blocking=False)
    try:
        rs2.merge_entity(names.TABLE_NODES, "testpool", "n0",
                         {"heartbeat_at": 2.0})
        # The fresh beat queued BEHIND the stale backlog instead of
        # writing through it.
        assert raw.get_entity(
            names.TABLE_NODES, "testpool",
            "n0").get("heartbeat_at") is None
    finally:
        rs2._replay_lock.release()
    rs2.queue_length("q")
    assert rs2.journal_backlog() == 0
    # Newest value wins: the drain applied the coalesced/ordered
    # journal, never a stale-over-fresh overwrite.
    assert raw.get_entity(names.TABLE_NODES, "testpool",
                          "n0")["heartbeat_at"] == 2.0


def test_resilient_bounded_caps_critical_retry(tmp_path):
    """A bounded() caller (the agent heartbeat thread) gets
    StoreOutageError within its window instead of sleeping toward
    max_outage_seconds — one dark store must not park the thread
    that drives heartbeats, lease renewal and eviction kills (review
    fix). Outside the block the full ride-through still applies."""
    from batch_shipyard_tpu.state.resilient import StoreOutageError
    flaky = FlakyStore(MemoryStateStore())
    rs = _resilient(flaky, tmp_path, max_outage_seconds=900.0)
    flaky.down = True
    t0 = time.monotonic()
    with pytest.raises(StoreOutageError):
        with rs.bounded(0.3):
            rs.get_entity(names.TABLE_TASKS, "p$j", "t0")
    assert time.monotonic() - t0 < 2.0
    assert rs.outage_active()
    # Scoped: the same op outside the block rides the outage out.
    threading.Timer(0.2, lambda: setattr(flaky, "down",
                                         False)).start()
    assert rs.queue_length("q") == 0
    assert not rs.outage_active()


def test_resilient_replay_never_resurrects_deleted_node(tmp_path):
    """A journaled nodes-table upsert whose target the substrate
    deleted during the outage is dropped on replay, not re-created
    (review fix): upsert_entity re-creates unconditionally, and a
    resurrected row would be ghost capacity to federation _pool_facts
    and heimdall until something else garbage-collected it."""
    raw = MemoryStateStore()
    flaky = FlakyStore(raw)
    rs = _resilient(flaky, tmp_path, probe_interval=3600.0)
    raw.upsert_entity(names.TABLE_NODES, "testpool", "n0",
                      {"state": "idle"})
    flaky.down = True
    rs.upsert_entity(names.TABLE_NODES, "testpool", "n0",
                     {"state": "idle", "heartbeat_at": 1.0})
    assert rs.journal_backlog() == 1
    # The pool is resized away mid-outage (writes through RAW: the
    # substrate's own store handle is not this wrapper).
    raw.delete_entity(names.TABLE_NODES, "testpool", "n0")
    flaky.down = False
    rs.queue_length("q")
    assert rs.journal_backlog() == 0
    with pytest.raises(NotFoundError):
        raw.get_entity(names.TABLE_NODES, "testpool", "n0")


def test_preempt_notice_deferred_until_stamp_stands():
    """defer_notice=True returns the notice-emitting closure instead
    of publishing eagerly (review fix): the sweep's post-write fence
    check can RETRACT a late-landing stamp, and an eagerly-emitted
    TASK_PREEMPT_NOTICE would survive the retraction as a phantom
    preemption in every consumer (drill invariant, heimdall,
    accounting)."""
    from batch_shipyard_tpu.goodput import events as goodput_events
    from batch_shipyard_tpu.jobs import manager as jobs_mgr
    store = MemoryStateStore()
    store.insert_entity(names.TABLE_TASKS,
                        names.task_pk("p", "j"), "t0",
                        {"state": "running", "spec": {}})

    def notices():
        return [r for r in store.query_entities(names.TABLE_GOODPUT)
                if r["kind"] == goodput_events.TASK_PREEMPT_NOTICE]

    emit = jobs_mgr.request_preemption(store, "p", "j", "t0",
                                       leader_epoch=7,
                                       defer_notice=True)
    assert callable(emit)  # stamp landed, notice withheld
    assert store.get_entity(
        names.TABLE_TASKS, names.task_pk("p", "j"),
        "t0")[names.TASK_COL_PREEMPT_REQUEST]["leader_epoch"] == 7
    assert notices() == []
    emit()
    assert len(notices()) == 1
    assert notices()[0]["attrs"]["leader_epoch"] == 7
    # The undeferred path (manual CLI preemptions) still emits
    # inline; re-stamping stays an idempotent no-op either way.
    assert jobs_mgr.request_preemption(store, "p", "j", "t0") is True
    assert len(notices()) == 1


def test_heimdall_exports_fed_elastic_lease_epoch():
    """The fed-elastic lease epoch rides shipyard_leader_epoch per
    federation (review fix): docs/30's lease table promises all
    THREE leases are observable, and the federation evaluator's
    double-fire (a double-fanned gang migration) is the least
    idempotent of them."""
    from batch_shipyard_tpu.monitor import heimdall
    store = MemoryStateStore()
    store.upsert_entity(names.TABLE_FEDERATIONS, "fed", "fedA",
                        {"pools": []})
    scope = "fed-fedA"
    lease = state_leases.LeaderLease(
        store,
        key=names.leader_lease_key(scope,
                                   state_leases.ROLE_FED_ELASTIC),
        epoch_key=names.leader_epoch_key(
            scope, state_leases.ROLE_FED_ELASTIC),
        owner="proc0", duration_seconds=5.0)
    epoch = lease.epoch()
    assert epoch is not None
    lines = heimdall._federation_lease_metrics(store)
    assert lines == [
        f'shipyard_leader_epoch{{lease="fed-elastic",'
        f'federation="fedA"}} {epoch}']


# --------------------------- adoption (unit) ---------------------------

def _assert_slot_ledger_retired(work_dir, within=5.0):
    """The agent retires the slot ledger LAST (a crash before that
    leaves the task adoptable), so a moment after the entity's state
    changed: wait that moment, then hold it to the removal."""
    ledger = os.path.join(work_dir, "slots", "slot0.json")
    deadline = time.monotonic() + within
    while os.path.exists(ledger) and time.monotonic() < deadline:
        time.sleep(0.05)
    assert not os.path.exists(ledger)


def test_adoption_classifies_exited_task_without_rerun(tmp_path):
    """The 'still-valid claim, process already exited' adoption leg:
    a restarted agent finds a slot ledger whose pid is dead but
    whose exit-code sentinel says 0 — the task is classified
    completed through the normal path, retries untouched, instead of
    the reclaim-rerun."""
    from batch_shipyard_tpu.agent import task_runner
    from batch_shipyard_tpu.agent.node_agent import (
        NodeAgent, NodeIdentity)
    from batch_shipyard_tpu.config import settings as settings_mod

    store = MemoryStateStore()
    conf = {"pool_specification": {
        "id": "adoptpool", "substrate": "fake",
        "vm_configuration": {"vm_count": {"dedicated": 1}},
        "task_slots_per_node": 1,
        "max_wait_time_seconds": 60}}
    pool = settings_mod.pool_settings(conf)
    identity = NodeIdentity(
        pool_id="adoptpool", node_id="n0", node_index=0,
        hostname="n0", internal_ip="10.0.0.1")
    work_dir = str(tmp_path / "node")
    task_dir = os.path.join(work_dir, "tasks", "j1", "t1")
    os.makedirs(task_dir)
    os.makedirs(os.path.join(work_dir, "slots"))
    with open(os.path.join(task_dir, "stdout.txt"), "w",
              encoding="utf-8") as fh:
        fh.write("done\n")
    with open(os.path.join(task_dir, "stderr.txt"), "w",
              encoding="utf-8") as fh:
        fh.write("")
    with open(os.path.join(task_dir,
                           task_runner.EXIT_CODE_FILENAME), "w",
              encoding="utf-8") as fh:
        fh.write("0")
    # The predecessor's claim: running, owned by this node, with a
    # ledger naming a long-dead pid.
    spec = {"command": "echo done", "max_task_retries": 2}
    store.upsert_entity(names.TABLE_JOBS, "adoptpool", "j1",
                        {"state": "active"})
    store.upsert_entity(names.TABLE_TASKS, "adoptpool$j1", "t1",
                        {"state": "running", "node_id": "n0",
                         "retries": 0, "spec": spec})
    store.upsert_entity(names.TABLE_NODES, "adoptpool", "n0",
                        {"state": "running", "node_index": 0,
                         "heartbeat_at": time.time() - 1.5})
    with open(os.path.join(work_dir, "slots", "slot0.json"), "w",
              encoding="utf-8") as fh:
        json.dump({"slot": 0, "job_id": "j1", "task_id": "t1",
                   "pid": 2 ** 22 + 12345, "runtime": "none",
                   "container": None, "task_dir": task_dir,
                   "command": "echo done", "env": {},
                   "started_at": "2026-01-01T00:00:00.000000Z"},
                  fh)
    # Nodeprep marker so start() takes the reboot-resume fast path.
    with open(os.path.join(work_dir, ".nodeprep_finished"), "w",
              encoding="utf-8") as fh:
        fh.write("x")
    agent = NodeAgent(store, identity, pool, work_dir=work_dir,
                      heartbeat_interval=0.2, poll_interval=0.05)
    agent.start()
    try:
        deadline = time.monotonic() + 10.0
        state = None
        while time.monotonic() < deadline:
            state = store.get_entity(names.TABLE_TASKS,
                                     "adoptpool$j1",
                                     "t1").get("state")
            if state == "completed":
                break
            time.sleep(0.05)
        assert state == "completed", state
        task = store.get_entity(names.TABLE_TASKS, "adoptpool$j1",
                                "t1")
        assert int(task.get("retries", 0) or 0) == 0
        # The adoption leg + restart span were recorded.
        kinds = [r["kind"] for r in store.query_entities(
            names.TABLE_GOODPUT, partition_key="adoptpool")]
        assert "adoption" in kinds, kinds
        _assert_slot_ledger_retired(work_dir)
    finally:
        agent.stop()
        agent.join(timeout=5.0)


def test_adoption_unknowable_container_exit_hands_back_to_reclaim(
        tmp_path):
    """Containerized adoption with an unlearnable outcome (no exit
    sentinel — only the runtime-'none' shell trailer writes one from
    inside the task's session — and no container left to ask): the
    task must NOT be classified as failed. It hands back through the
    orphan-reclaim semantics — pending, no retry consumed, neutral
    health (review fix: previously hard-coded exit -9)."""
    import subprocess as sp

    from batch_shipyard_tpu.agent.node_agent import (
        NodeAgent, NodeIdentity)
    from batch_shipyard_tpu.config import settings as settings_mod

    store = MemoryStateStore()
    conf = {"pool_specification": {
        "id": "adoptpool", "substrate": "fake",
        "vm_configuration": {"vm_count": {"dedicated": 1}},
        "task_slots_per_node": 1,
        "max_wait_time_seconds": 60}}
    pool = settings_mod.pool_settings(conf)
    identity = NodeIdentity(
        pool_id="adoptpool", node_id="n0", node_index=0,
        hostname="n0", internal_ip="10.0.0.1")
    work_dir = str(tmp_path / "node")
    task_dir = os.path.join(work_dir, "tasks", "j1", "t1")
    os.makedirs(task_dir)
    os.makedirs(os.path.join(work_dir, "slots"))
    spec = {"command": "echo run", "max_task_retries": 2}
    store.upsert_entity(names.TABLE_JOBS, "adoptpool", "j1",
                        {"state": "active"})
    store.upsert_entity(names.TABLE_TASKS, "adoptpool$j1", "t1",
                        {"state": "running", "node_id": "n0",
                         "retries": 0, "spec": spec})
    store.upsert_entity(names.TABLE_NODES, "adoptpool", "n0",
                        {"state": "running", "node_index": 0,
                         "heartbeat_at": time.time() - 1.5})
    # A live stand-in for the adopted docker-client pid; launched
    # start_new_session like every real task (the adoption pid-
    # identity guard requires a session leader), reaped on exit so
    # the watcher sees a genuinely-dead process, not a zombie.
    proc = sp.Popen(["sleep", "0.4"], start_new_session=True)
    threading.Thread(target=proc.wait, daemon=True).start()
    with open(os.path.join(work_dir, "slots", "slot0.json"), "w",
              encoding="utf-8") as fh:
        json.dump({"slot": 0, "job_id": "j1", "task_id": "t1",
                   "pid": proc.pid, "runtime": "docker",
                   "container": "shipyard-adopt-gone",
                   "task_dir": task_dir, "command": "echo run",
                   "env": {},
                   "started_at": "2026-01-01T00:00:00.000000Z"},
                  fh)
    with open(os.path.join(work_dir, ".nodeprep_finished"), "w",
              encoding="utf-8") as fh:
        fh.write("x")
    agent = NodeAgent(store, identity, pool, work_dir=work_dir,
                      heartbeat_interval=0.2, poll_interval=0.05)
    agent.start()
    try:
        deadline = time.monotonic() + 15.0
        state = None
        while time.monotonic() < deadline:
            state = store.get_entity(names.TABLE_TASKS,
                                     "adoptpool$j1",
                                     "t1").get("state")
            if state == "pending":
                break
            time.sleep(0.05)
        task = store.get_entity(names.TABLE_TASKS, "adoptpool$j1",
                                "t1")
        assert task.get("state") == "pending", task.get("state")
        assert task.get("node_id") is None
        # Reclaim semantics: repeat work, never budget or health.
        assert int(task.get("retries", 0) or 0) == 0
        node = store.get_entity(names.TABLE_NODES, "adoptpool",
                                "n0")
        assert float(node.get("health", 1.0) or 1.0) >= 1.0
        _assert_slot_ledger_retired(work_dir)
    finally:
        agent.stop()
        agent.join(timeout=5.0)


def test_adopted_task_wedge_watchdog_enforced(tmp_path):
    """Adoption re-arms the task's runtime limits (review fix): the
    original run_task watchdog died with the old agent, so a wedged
    adopted task must still be killed and classified — not hold its
    slot (and the node's capacity) forever."""
    import subprocess as sp

    from batch_shipyard_tpu.agent.node_agent import (
        NodeAgent, NodeIdentity)
    from batch_shipyard_tpu.config import settings as settings_mod

    store = MemoryStateStore()
    conf = {"pool_specification": {
        "id": "adoptpool", "substrate": "fake",
        "vm_configuration": {"vm_count": {"dedicated": 1}},
        "task_slots_per_node": 1,
        "max_wait_time_seconds": 60}}
    pool = settings_mod.pool_settings(conf)
    identity = NodeIdentity(
        pool_id="adoptpool", node_id="n0", node_index=0,
        hostname="n0", internal_ip="10.0.0.1")
    work_dir = str(tmp_path / "node")
    task_dir = os.path.join(work_dir, "tasks", "j1", "t1")
    os.makedirs(task_dir)
    os.makedirs(os.path.join(work_dir, "slots"))
    # A beat file whose last beat predates the deadline by far: the
    # adopted task is wedged from the watcher's first look.
    beat_file = str(tmp_path / "progress_beat")
    with open(beat_file, "w", encoding="utf-8") as fh:
        fh.write("")
    os.utime(beat_file, (time.time() - 100, time.time() - 100))
    spec = {"command": "sleep 30", "max_task_retries": 0,
            "progress_deadline_seconds": 0.5}
    store.upsert_entity(names.TABLE_JOBS, "adoptpool", "j1",
                        {"state": "active"})
    store.upsert_entity(names.TABLE_TASKS, "adoptpool$j1", "t1",
                        {"state": "running", "node_id": "n0",
                         "retries": 0, "spec": spec})
    store.upsert_entity(names.TABLE_NODES, "adoptpool", "n0",
                        {"state": "running", "node_index": 0,
                         "heartbeat_at": time.time() - 1.5})
    # Own session group: _hard_kill_task_group SIGKILLs the pgid.
    proc = sp.Popen(["sleep", "30"], start_new_session=True)
    try:
        with open(os.path.join(work_dir, "slots", "slot0.json"),
                  "w", encoding="utf-8") as fh:
            json.dump({"slot": 0, "job_id": "j1", "task_id": "t1",
                       "pid": proc.pid, "runtime": "none",
                       "container": None, "task_dir": task_dir,
                       "command": "sleep 30",
                       "env": {"SHIPYARD_PROGRESS_FILE": beat_file},
                       "started_at": "2026-01-01T00:00:00.000000Z"},
                      fh)
        with open(os.path.join(work_dir, ".nodeprep_finished"), "w",
                  encoding="utf-8") as fh:
            fh.write("x")
        agent = NodeAgent(store, identity, pool, work_dir=work_dir,
                          heartbeat_interval=0.2,
                          poll_interval=0.05)
        agent.start()
        try:
            deadline = time.monotonic() + 10.0
            state = None
            while time.monotonic() < deadline:
                state = store.get_entity(names.TABLE_TASKS,
                                         "adoptpool$j1",
                                         "t1").get("state")
                if state == "failed":
                    break
                time.sleep(0.05)
            assert state == "failed", state
            # The wedged process really died (poll() reaps it).
            kill_deadline = time.monotonic() + 5.0
            while proc.poll() is None and \
                    time.monotonic() < kill_deadline:
                time.sleep(0.05)
            assert proc.poll() is not None
            _assert_slot_ledger_retired(work_dir)
        finally:
            agent.stop()
            agent.join(timeout=5.0)
    finally:
        if proc.poll() is None:
            proc.kill()


def _bare_agent(tmp_path, store, pool_id="adoptpool",
                job_state_ttl=5.0):
    """A constructed-but-not-started NodeAgent over a fake pool —
    for driving adoption/forwarding methods directly, without the
    heartbeat/worker threads."""
    from batch_shipyard_tpu.agent.node_agent import (
        NodeAgent, NodeIdentity)
    from batch_shipyard_tpu.config import settings as settings_mod

    conf = {"pool_specification": {
        "id": pool_id, "substrate": "fake",
        "vm_configuration": {"vm_count": {"dedicated": 1}},
        "task_slots_per_node": 1,
        "max_wait_time_seconds": 60}}
    pool = settings_mod.pool_settings(conf)
    identity = NodeIdentity(
        pool_id=pool_id, node_id="n0", node_index=0,
        hostname="n0", internal_ip="10.0.0.1")
    work_dir = str(tmp_path / "node")
    os.makedirs(os.path.join(work_dir, "slots"), exist_ok=True)
    return NodeAgent(store, identity, pool, work_dir=work_dir,
                     heartbeat_interval=0.2, poll_interval=0.05,
                     job_state_ttl=job_state_ttl)


def test_gang_member_ledger_fenced_on_restart(tmp_path):
    """A gang member's slot ledger is written at launch and a
    restarted agent FENCES (kills) the leftover live process instead
    of adopting it: the rendezvous context died with the old agent,
    so the gang requeue owns the rerun — and must never share the
    task dir with a live predecessor (the double-execution class)."""
    import subprocess as sp

    from batch_shipyard_tpu.agent.node_agent import NodeAgent

    store = MemoryStateStore()
    agent = _bare_agent(tmp_path, store)
    proc = sp.Popen(["sleep", "30"], start_new_session=True)
    try:
        ledger = {"slot": 0, "job_id": "j1", "task_id": "t1",
                  "pid": proc.pid, "gang": True,
                  "pid_start_ticks":
                      NodeAgent._proc_start_ticks(proc.pid),
                  "runtime": "none", "container": None,
                  "task_dir": str(tmp_path / "node" / "tasks"
                                  / "j1" / "t1"),
                  "command": "sleep 30", "env": {},
                  "started_at": "2026-01-01T00:00:00.000000Z"}
        path = os.path.join(agent.work_dir, "slots", "slot0.json")
        with open(path, "w", encoding="utf-8") as fh:
            json.dump(ledger, fh)
        adopted = agent._adopt_restart_state()
        assert adopted == 0
        # Fenced: the member process is dead, the ledger retired —
        # purely locally, no store rows were needed or touched.
        deadline = time.monotonic() + 5.0
        while proc.poll() is None and time.monotonic() < deadline:
            time.sleep(0.05)
        assert proc.poll() is not None
        assert not os.path.exists(path)
        assert not agent._adopted_slots
    finally:
        if proc.poll() is None:
            proc.kill()


def test_adoption_never_touches_a_recycled_pid(tmp_path):
    """Pid-identity guard: a ledgered pid that now belongs to a
    STRANGER (not a session leader — the shape of an OS-recycled
    number, since every task launches start_new_session) is treated
    as dead: no adoption, no kill, ledger retired so the ordinary
    reclaim-rerun owns the task."""
    import subprocess as sp

    store = MemoryStateStore()
    agent = _bare_agent(tmp_path, store)
    store.upsert_entity(names.TABLE_JOBS, "adoptpool", "j1",
                        {"state": "active"})
    store.upsert_entity(names.TABLE_TASKS, "adoptpool$j1", "t1",
                        {"state": "running", "node_id": "n0",
                         "retries": 0,
                         "spec": {"command": "sleep 30"}})
    # NOT start_new_session: pgid != pid, like a recycled number.
    proc = sp.Popen(["sleep", "30"])
    try:
        path = os.path.join(agent.work_dir, "slots", "slot0.json")
        with open(path, "w", encoding="utf-8") as fh:
            json.dump({"slot": 0, "job_id": "j1", "task_id": "t1",
                       "pid": proc.pid, "runtime": "none",
                       "container": None,
                       "task_dir": str(tmp_path / "t"),
                       "command": "sleep 30", "env": {},
                       "started_at": "2026-01-01T00:00:00.000000Z"},
                      fh)
        adopted = agent._adopt_restart_state()
        assert adopted == 0
        # The stranger was NOT killed and nothing waits on it.
        assert proc.poll() is None
        assert not os.path.exists(path)
        assert not agent._adopted_slots
    finally:
        proc.kill()
        proc.wait(timeout=5.0)


def test_stale_epoch_preempt_stamp_held_until_confirmed(tmp_path):
    """Consumer-side fence for the author-retraction race: a stamp
    whose leader_epoch predates the sweep lease's current term is
    held for one confirmation cycle before delivery. A stamp the
    author retracts during the hold is never delivered (no spurious
    drain); one that survives confirmation IS delivered (a
    legitimate pre-turnover stamp must still drain its victim)."""
    from batch_shipyard_tpu.agent.node_agent import _AdoptedProc
    from batch_shipyard_tpu.utils import util

    store = MemoryStateStore()
    agent = _bare_agent(tmp_path, store, job_state_ttl=0.0)
    epoch_key = names.leader_epoch_key(
        "adoptpool", state_leases.ROLE_PREEMPT_SWEEP)
    # Two terms recorded: current epoch is 2; stamps carrying 1 are
    # stale.
    body = json.dumps({"owner": "n9", "lease": "x"}).encode("utf-8")
    store.put_object(epoch_key, body)
    assert store.put_object(epoch_key, body) == 2
    task_dir = os.path.join(agent.work_dir, "tasks", "j1", "t1")
    os.makedirs(task_dir)
    store.upsert_entity(names.TABLE_JOBS, "adoptpool", "j1",
                        {"state": "active"})

    def _stamp(requested_at, epoch):
        request = {"reason": "r", "requested_at": requested_at}
        if epoch is not None:
            request["leader_epoch"] = epoch
        store.upsert_entity(
            names.TABLE_TASKS, "adoptpool$j1", "t1",
            {"state": "running", "node_id": "n0", "retries": 0,
             "spec": {"command": "sleep 30"},
             names.TASK_COL_PREEMPT_REQUEST: request})

    request_file = os.path.join(task_dir, "preempt_request.json")
    agent._live_procs[("j1", "t1")] = _AdoptedProc(None)
    # Round 1: stale stamp, retracted during the hold -> never
    # delivered.
    _stamp(util.datetime_utcnow_iso(), epoch=1)
    agent._forward_preempt_requests()
    assert not os.path.exists(request_file)  # held, not delivered
    store.merge_entity(names.TABLE_TASKS, "adoptpool$j1", "t1",
                       {names.TASK_COL_PREEMPT_REQUEST: None})
    time.sleep(0.6)
    agent._forward_preempt_requests()
    assert not os.path.exists(request_file)
    # Round 2: stale stamp that SURVIVES confirmation is delivered.
    _stamp(util.datetime_utcnow_iso(), epoch=1)
    agent._forward_preempt_requests()
    assert not os.path.exists(request_file)
    time.sleep(0.6)
    agent._forward_preempt_requests()
    assert os.path.exists(request_file)
    os.remove(request_file)
    os.remove(request_file + ".delivered")
    # Epoch-less (manual jobs preempt) stamps deliver immediately.
    _stamp(util.datetime_utcnow_iso(), epoch=None)
    agent._forward_preempt_requests()
    assert os.path.exists(request_file)


# ------------------------------- drills --------------------------------

def test_store_outage_drill():
    from batch_shipyard_tpu.chaos import drill
    report = drill.run_store_outage_drill(seed=0)
    assert report["invariants"]["ok"] is True
    assert report["invariants"]["retries"] == 0
    assert report["invariants"]["store_outage_seconds"] > 0


def test_leader_partition_drill():
    from batch_shipyard_tpu.chaos import drill
    report = drill.run_leader_partition_drill(seed=0)
    inv = report["invariants"]
    assert inv["ok"] is True
    assert inv["preempt_notices"] == 1
    assert inv["stamp_epoch"] == inv["epoch_after"]
    assert inv["epoch_after"] > inv["epoch_before"]
    assert len(inv["lease_holders"]) == 1


def test_agent_restart_drill():
    from batch_shipyard_tpu.chaos import drill
    report = drill.run_agent_restart_drill(seed=0)
    inv = report["invariants"]
    assert inv["ok"] is True
    assert inv["task_starts"] == 1
    assert inv["retries"] == 0
    assert inv["adoption_seconds"] > 0
