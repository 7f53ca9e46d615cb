"""Self-healing execution layer tests (PR 5): deterministic chaos
plans, the wedge watchdog, the backoff/quarantine retry supervisor,
node health scoring, and checkpoint-aware gang requeue.

All CPU-only fakepod pools; every wait is poll-with-deadline (no
fixed sleeps beyond sub-second task payloads) so the suite stays
cheap under container load."""

import json
import os
import signal
import time

import pytest

from batch_shipyard_tpu.chaos.plan import ChaosPlan
from batch_shipyard_tpu.config import settings as settings_mod
from batch_shipyard_tpu.jobs import manager as jobs_mgr
from batch_shipyard_tpu.pool import manager as pool_mgr
from batch_shipyard_tpu.state import names
from batch_shipyard_tpu.state.memory import MemoryStateStore
from batch_shipyard_tpu.substrate.fakepod import FakePodSubstrate

GLOBAL = settings_mod.global_settings({})

# Fast supervisor settings for every pool in this file: sub-second
# backoff so retried tasks re-run promptly.
FAST_RETRY = {"retry_backoff_base": 0.2, "retry_backoff_cap": 1.0}


def _make_pool(pool_id: str, accelerator: str = "v5litepod-8",
               slots: int = 2, stale: float = 3.0,
               agent_kwargs: dict = FAST_RETRY):
    conf = {"pool_specification": {
        "id": pool_id, "substrate": "fake",
        "tpu": {"accelerator_type": accelerator},
        "task_slots_per_node": slots,
        "max_wait_time_seconds": 30}}
    store = MemoryStateStore()
    substrate = FakePodSubstrate(store, node_stale_seconds=stale)
    substrate.agent_kwargs = dict(agent_kwargs)
    pool = settings_mod.pool_settings(conf)
    pool_mgr.create_pool(store, substrate, pool, GLOBAL, conf)
    return store, substrate, pool


def _poll(predicate, timeout: float, interval: float = 0.1,
          message: str = "condition"):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        value = predicate()
        if value:
            return value
        time.sleep(interval)
    raise AssertionError(f"timed out waiting for {message}")


# ------------------------------ plans ----------------------------------

def test_chaos_plan_same_seed_same_schedule():
    """Determinism acceptance: two plans from one seed inject
    identically (fingerprint equality), different seeds differ, and
    a plan round-trips through its dict serialization."""
    a = ChaosPlan.generate(7, duration=10.0, num_nodes=4)
    b = ChaosPlan.generate(7, duration=10.0, num_nodes=4)
    assert a.fingerprint() == b.fingerprint()
    assert a.injections == b.injections
    assert a.fingerprint() != ChaosPlan.generate(8).fingerprint()
    rt = ChaosPlan.from_dict(json.loads(json.dumps(a.to_dict())))
    assert rt.fingerprint() == a.fingerprint()
    # Schedule sanity: every injection lands inside the drill window
    # with runway on both sides, sorted by time.
    ats = [i.at for i in a.injections]
    assert ats == sorted(ats)
    assert all(0 < at < 10.0 for at in ats)


def test_chaos_plan_rejects_unknown_kind():
    with pytest.raises(ValueError):
        ChaosPlan.generate(0, kinds=("task_wedge", "bogus"))


# -------------------------- wedge watchdog -----------------------------

def test_wedge_watchdog_kills_and_retry_completes(tmp_path):
    """The wedge shape: a task that stays
    alive but emits no progress beats is killed by the watchdog at
    its progress deadline, requeued with backoff, and completes on
    the retry — an unbounded hang became one bounded retry."""
    store, substrate, pool = _make_pool("wedgepool")
    marker = tmp_path / "attempted"
    try:
        jobs = settings_mod.job_settings_list({"job_specifications": [{
            "id": "wedge",
            "tasks": [{"id": "t0",
                       # Attempt 1 wedges (no beats, long sleep);
                       # attempt 2 sees the marker and succeeds.
                       "command": (f"if [ -f {marker} ]; then "
                                   f"echo healed; else "
                                   f"touch {marker} && sleep 60; fi"),
                       "progress_deadline_seconds": 1,
                       "max_task_retries": 2}],
        }]})
        start = time.monotonic()
        jobs_mgr.add_jobs(store, pool, jobs)
        tasks = jobs_mgr.wait_for_tasks(store, "wedgepool", "wedge",
                                        timeout=30, poll_interval=0.2)
        elapsed = time.monotonic() - start
        assert tasks[0]["state"] == "completed"
        assert tasks[0]["retries"] == 1
        out = jobs_mgr.get_task_output(store, "wedgepool", "wedge",
                                       "t0")
        assert out.strip() == b"healed"
        # The wedge attempt is in the diagnostics history with its
        # watchdog reason, and the whole recovery beat the 60s hang
        # by an order of magnitude.
        history = tasks[0].get("attempt_history") or []
        assert any("wedged" in (a.get("reason") or "")
                   for a in history), history
        assert elapsed < 25, elapsed
    finally:
        substrate.stop_all()


def test_progress_beats_defeat_the_watchdog(tmp_path):
    """A task that keeps beating its progress file is NOT killed even
    though it runs far past the deadline — the watchdog measures
    progress staleness, not wall time."""
    store, substrate, pool = _make_pool("beatpool")
    try:
        # Beat every 0.5s for 3s against a 1s deadline.
        cmd = ("for i in 1 2 3 4 5 6; do "
               "touch $SHIPYARD_PROGRESS_FILE; sleep 0.5; done; "
               "echo steady")
        jobs = settings_mod.job_settings_list({"job_specifications": [{
            "id": "beats",
            "tasks": [{"id": "t0", "command": cmd,
                       "progress_deadline_seconds": 1,
                       "max_task_retries": 1}],
        }]})
        jobs_mgr.add_jobs(store, pool, jobs)
        tasks = jobs_mgr.wait_for_tasks(store, "beatpool", "beats",
                                        timeout=30, poll_interval=0.2)
        assert tasks[0]["state"] == "completed"
        assert not tasks[0].get("retries")
        assert not tasks[0].get("wedged")
    finally:
        substrate.stop_all()


# ------------------------- retry supervisor ----------------------------

def test_retry_backoff_stamps_not_before(tmp_path):
    """A failed task requeues with an exponential-backoff not_before
    honored by the claim path: the retry never starts before it."""
    store, substrate, pool = _make_pool(
        "backoffpool",
        agent_kwargs={"retry_backoff_base": 0.8,
                      "retry_backoff_cap": 2.0})
    marker = tmp_path / "failed-once"
    try:
        jobs = settings_mod.job_settings_list({"job_specifications": [{
            "id": "boff",
            "tasks": [{"id": "t0",
                       "command": (f"if [ -f {marker} ]; then "
                                   f"echo ok; else "
                                   f"touch {marker} && exit 1; fi"),
                       "max_task_retries": 3}],
        }]})
        jobs_mgr.add_jobs(store, pool, jobs)
        # Catch the backoff window: retries bumped, not_before ahead.
        entity = _poll(
            lambda: (e := jobs_mgr.get_task(
                store, "backoffpool", "boff", "t0")).get("retries")
            and e, timeout=15, interval=0.05,
            message="first requeue")
        not_before = float(entity["not_before"])
        requeue_observed = time.time()
        assert entity["retries"] == 1
        assert entity["last_exit_code"] == 1
        # base 0.8 * 2^0 with +-25% jitter => [0.6, 1.0]s
        assert 0.0 < not_before - requeue_observed <= 1.1
        tasks = jobs_mgr.wait_for_tasks(store, "backoffpool", "boff",
                                        timeout=30, poll_interval=0.2)
        assert tasks[0]["state"] == "completed"
        started_retry = tasks[0].get("started_at")
        assert started_retry is not None
        # The retry's start honored the backoff stamp.
        import datetime
        started_ts = datetime.datetime.strptime(
            started_retry, "%Y-%m-%dT%H:%M:%S.%fZ").replace(
            tzinfo=datetime.timezone.utc).timestamp()
        assert started_ts >= not_before - 0.25
    finally:
        substrate.stop_all()


def test_poison_quarantine_with_diagnostics():
    """Exhausting the retry budget parks the task in the quarantined
    terminal state with a post-mortem bundle: stderr tail, node id
    history, exit codes — surfaced by `shipyard jobs tasks list`."""
    store, substrate, pool = _make_pool("qpool")
    try:
        jobs = settings_mod.job_settings_list({"job_specifications": [{
            "id": "poison",
            "tasks": [{"id": "bad",
                       "command": ("echo boom-stderr >&2; exit 3"),
                       "max_task_retries": 1},
                      {"id": "good", "command": "echo fine"}],
        }]})
        jobs_mgr.add_jobs(store, pool, jobs)
        tasks = {t["_rk"]: t for t in jobs_mgr.wait_for_tasks(
            store, "qpool", "poison", timeout=30,
            poll_interval=0.2)}
        assert tasks["good"]["state"] == "completed"
        bad = tasks["bad"]
        assert bad["state"] == names.TASK_STATE_QUARANTINED
        assert bad["exit_code"] == 3
        assert "retry budget exhausted" in bad["error"]
        diag = bad["diagnostics"]
        assert "boom-stderr" in diag["stderr_tail"]
        history = diag["attempt_history"]
        assert [a["exit_code"] for a in history] == [3, 3]  # + 1 retry
        assert len(history) == 2
        assert all(a.get("node_id") for a in history)
        # The operator surface (jobs tasks list) projects the node /
        # exit-code histories from the stored attempt_history.
        from batch_shipyard_tpu import fleet as fleet_mod
        emitted = {}
        ctx = type("Ctx", (), {"store": store, "pool": pool})()
        orig = fleet_mod._emit
        fleet_mod._emit = lambda data, raw=False: emitted.update(data)
        try:
            fleet_mod.action_jobs_tasks_list(ctx, "poison", raw=True)
        finally:
            fleet_mod._emit = orig
        shown = {t["id"]: t for t in emitted["tasks"]}
        assert shown["bad"]["diagnostics"]["exit_codes"] == [3, 3]
        assert len(shown["bad"]["diagnostics"]["node_history"]) == 2
        # Quarantined is terminal for job rollups: stats count it and
        # the job autocompletes despite the poison task.
        stats = pool_mgr.pool_stats(store, "qpool")
        assert stats["tasks"][names.TASK_STATE_QUARANTINED] == 1
    finally:
        substrate.stop_all()


def test_zero_budget_task_fails_plain():
    """max_task_retries=0 (the default) keeps the legacy contract:
    a failing task lands in 'failed', not 'quarantined'."""
    store, substrate, pool = _make_pool("legacypool")
    try:
        jobs = settings_mod.job_settings_list({"job_specifications": [{
            "id": "legacy",
            "tasks": [{"id": "t0", "command": "exit 7"}],
        }]})
        jobs_mgr.add_jobs(store, pool, jobs)
        tasks = jobs_mgr.wait_for_tasks(store, "legacypool", "legacy",
                                        timeout=30, poll_interval=0.2)
        assert tasks[0]["state"] == "failed"
        assert tasks[0]["exit_code"] == 7
    finally:
        substrate.stop_all()


# ------------------------- node health score ---------------------------

def test_node_health_quarantine_and_recovery():
    """Repeated wedges decay a node's health score below the
    threshold: the node quarantines itself (claims refused, gang
    joins refused, columns published for observers), then recovers
    above the threshold after successes and claims again."""
    store, substrate, pool = _make_pool("healthpool")
    try:
        agents = _poll(
            lambda: list(substrate._agents.get("healthpool",
                                               {}).values()),
            timeout=15, message="agents booted")
        agent = agents[0]
        assert not agent.node_quarantined()
        # Three wedges: 1.0 -> 0.5 -> 0.25 -> 0.125 < 0.25 threshold.
        for _ in range(3):
            agent._note_task_outcome(False, wedged=True)
        assert agent.node_quarantined()
        # Published on the node entity for claim-exclusion observers
        # (gang recovery target choice, heimdall gauges).
        node = _poll(
            lambda: (n := store.get_entity(
                names.TABLE_NODES, "healthpool",
                agent.identity.node_id)).get(
                names.NODE_COL_QUARANTINED) and n,
            timeout=10, message="quarantine column")
        assert node[names.NODE_COL_HEALTH] < 0.25
        # A quarantined node refuses new work on both claim paths.
        pk = names.task_pk("healthpool", "jx")
        store.insert_entity(names.TABLE_TASKS, pk, "tx",
                            {"state": "pending", "spec": {}})
        entity = store.get_entity(names.TABLE_TASKS, pk, "tx")
        assert agent._claim_regular("jx", "tx", entity) is None
        assert agent._gang_claim(
            names.gang_pk("healthpool", "jx", "tx"), 0) is False
        # Successes recover it past the threshold; claims resume.
        for _ in range(3):
            agent._note_task_outcome(True)
        assert not agent.node_quarantined()
        entity = store.get_entity(names.TABLE_TASKS, pk, "tx")
        assert agent._claim_regular("jx", "tx", entity) is not None
    finally:
        substrate.stop_all()


def test_node_quarantine_probation_release():
    """Quarantine is probational, never permanent: a quarantined node
    claims nothing, so it can never earn back its score through task
    successes — without the probation timer a poison job of ordinary
    failing tasks would auto-drain every node in the pool forever.
    After the window the node resumes claims at exactly the threshold
    score, where a single further failure re-quarantines it."""
    store, substrate, pool = _make_pool(
        "probation",
        agent_kwargs={**FAST_RETRY, "health_probation_seconds": 0.3})
    try:
        agents = _poll(
            lambda: list(substrate._agents.get("probation",
                                               {}).values()),
            timeout=15, message="agents booted")
        agent = agents[0]
        for _ in range(3):
            agent._note_task_outcome(False, wedged=True)
        assert agent.node_quarantined()
        _poll(lambda: not agent.node_quarantined(),
              timeout=10, message="probation release")
        # Claims resume after release.
        pk = names.task_pk("probation", "jp")
        store.insert_entity(names.TABLE_TASKS, pk, "tp",
                            {"state": "pending", "spec": {}})
        entity = store.get_entity(names.TABLE_TASKS, pk, "tp")
        assert agent._claim_regular("jp", "tp", entity) is not None
        # Probation means probation: one more failure at the
        # threshold score re-quarantines immediately.
        agent._note_task_outcome(False)
        assert agent.node_quarantined()
    finally:
        substrate.stop_all()


def test_beat_throttle_scales_to_deadline(tmp_path, monkeypatch):
    """A tight watchdog deadline must not be starved by the beat
    throttle itself: with $SHIPYARD_PROGRESS_DEADLINE exported the
    throttle shrinks to deadline/4, so a task that progresses every
    step always lands beats well inside its deadline."""
    from batch_shipyard_tpu.agent import progress as progress_mod
    path = tmp_path / "beat"
    monkeypatch.setenv(progress_mod.PROGRESS_FILE_ENV, str(path))
    monkeypatch.setenv(progress_mod.PROGRESS_DEADLINE_ENV, "1")
    assert progress_mod._throttle_seconds() == pytest.approx(0.25)
    progress_mod._last_beat_at = 0.0
    progress_mod.beat()
    first = path.stat().st_mtime
    # Inside BEAT_INTERVAL (would be dropped by the fixed throttle)
    # but past deadline/4: the beat must land (mtime advances — the
    # only signal the watchdog reads).
    time.sleep(0.3)
    progress_mod.beat()
    assert path.stat().st_mtime > first
    # Without the exported deadline the ceiling applies unchanged.
    monkeypatch.delenv(progress_mod.PROGRESS_DEADLINE_ENV)
    assert progress_mod._throttle_seconds() == progress_mod.BEAT_INTERVAL


def test_wedging_node_health_drops_e2e(tmp_path):
    """Acceptance: an injected wedge drops the wedging node's health
    score on its entity (the heimdall gauge source) while the task
    still completes through retry."""
    store, substrate, pool = _make_pool("wdgscore")
    marker = tmp_path / "once"
    try:
        jobs = settings_mod.job_settings_list({"job_specifications": [{
            "id": "w",
            "tasks": [{"id": "t0",
                       "command": (f"if [ -f {marker} ]; then "
                                   f"echo done; else "
                                   f"touch {marker} && sleep 60; fi"),
                       "progress_deadline_seconds": 1,
                       "max_task_retries": 2}],
        }]})
        jobs_mgr.add_jobs(store, pool, jobs)
        tasks = jobs_mgr.wait_for_tasks(store, "wdgscore", "w",
                                        timeout=30, poll_interval=0.2)
        assert tasks[0]["state"] == "completed"
        wedge_nodes = [a.get("node_id") for a in
                       tasks[0]["attempt_history"]
                       if "wedged" in (a.get("reason") or "")]
        assert wedge_nodes
        node = store.get_entity(names.TABLE_NODES, "wdgscore",
                                wedge_nodes[0])
        assert node[names.NODE_COL_HEALTH] < 1.0
    finally:
        substrate.stop_all()


# -------------------- checkpoint-aware gang requeue --------------------

def test_gang_member_killed_midrun_resumes_from_checkpoint(tmp_path):
    """Acceptance e2e: a gang losing a member mid-run (its process
    killed, the preemption shape) requeues within the retry budget
    and the rerun RESUMES from the committed checkpoint — the step
    counter strictly advances past the restored step instead of
    restarting from zero."""
    store, substrate, pool = _make_pool("gangpool",
                                        accelerator="v5litepod-16")
    ckpt = tmp_path / "ckpt"
    try:
        # Attempt 1: instance 0 commits step 3, then the gang
        # "trains" (sleeps) — one instance gets SIGKILLed mid-sleep.
        # Attempt 2: restore the committed step and advance strictly
        # past it. Only instance 0 touches the checkpoint (the
        # single-writer convention real save pipelines follow), so
        # there is no cross-instance write race.
        cmd = (f"step=$(cat {ckpt} 2>/dev/null || echo 0); "
               f"if [ \"$SHIPYARD_TASK_INSTANCE\" != \"0\" ]; then "
               f"sleep 3; "
               f"elif [ \"$step\" = \"0\" ]; then echo 3 > {ckpt}; "
               f"sleep 3; else echo $((step+2)) > {ckpt}; fi")
        jobs = settings_mod.job_settings_list({"job_specifications": [{
            "id": "gj",
            "tasks": [{"id": "g0", "command": cmd,
                       "max_task_retries": 2,
                       "multi_instance": {"num_instances": 2}}],
        }]})
        jobs_mgr.add_jobs(store, pool, jobs)

        def committed_and_running():
            procs = []
            for agent in substrate._agents.get("gangpool",
                                               {}).values():
                procs.extend(agent._live_procs.values())
            # Kill only after the checkpoint committed: the rerun
            # must have a restore point (real preemptions can land
            # earlier; then recovery replays from step 0 — fine, but
            # not the resume path this test pins down).
            return procs if len(procs) >= 2 and ckpt.exists() \
                else None

        procs = _poll(committed_and_running, timeout=20,
                      message="gang instances running past commit")
        os.killpg(os.getpgid(procs[0].pid), signal.SIGKILL)
        tasks = jobs_mgr.wait_for_tasks(store, "gangpool", "gj",
                                        timeout=40, poll_interval=0.2)
        assert tasks[0]["state"] == "completed"
        assert tasks[0]["retries"] == 1
        # Strictly past the restored step: 3 (committed) -> 5.
        assert int(ckpt.read_text().strip()) == 5
        # The rerun's rendezvous used a fresh attempt-namespaced gang
        # partition and everything was cleaned up.
        assert not list(store.query_entities(names.TABLE_GANGS))
    finally:
        substrate.stop_all()


def test_broken_gang_requeues_within_budget():
    """A gang with a dead member (stale heartbeat, the preempted-node
    shape) and retry budget left is REQUEUED by the surviving
    observers — not failed terminally — and the rerun completes on
    healthy nodes."""
    store, substrate, pool = _make_pool("grec")
    pk = names.task_pk("grec", "jg")
    store.insert_entity(names.TABLE_JOBS, "grec", "jg",
                        {"state": "active", "spec": {}})
    spec = {"command": "echo recovered", "runtime": "none",
            "max_task_retries": 1,
            "multi_instance": {"num_instances": 2,
                               "jax_distributed": {"enabled": False}}}
    try:
        store.insert_entity(names.TABLE_TASKS, pk, "g0",
                            {"state": "running", "spec": spec,
                             "retries": 0})
        # Ghost member holds instance 0 of attempt 0 on a dead node
        # (no heartbeat, no registration grace).
        gang_pk = names.gang_pk("grec", "jg", "g0")
        store.insert_entity(names.TABLE_GANGS, gang_pk, "i0", {
            "node_id": "ghost", "hostname": "ghost",
            "internal_ip": "10.9.9.9", "slice_index": 0,
            "worker_index": 0, "state": "joined"})
        store.insert_entity(names.TABLE_GANGS, gang_pk,
                            "node$ghost", {"instance": 0})
        store.upsert_entity(names.TABLE_NODES, "grec", "ghost", {
            "state": "running", "heartbeat_at": 0.0})
        for k in range(2):
            store.put_message(
                names.task_queue("grec"),
                json.dumps({"job_id": "jg", "task_id": "g0",
                            "instance": k}).encode())
        tasks = jobs_mgr.wait_for_tasks(store, "grec", "jg",
                                        timeout=40, poll_interval=0.2)
        assert tasks[0]["state"] == "completed"
        assert tasks[0]["retries"] == 1
        assert not list(store.query_entities(names.TABLE_GANGS))
    finally:
        substrate.stop_all()


def test_broken_gang_budget_exhausted_quarantines():
    """A broken gang past its retry budget lands in quarantine with
    the diagnostics bundle naming the lost nodes."""
    store, substrate, pool = _make_pool("gquar")
    pk = names.task_pk("gquar", "jq")
    store.insert_entity(names.TABLE_JOBS, "gquar", "jq",
                        {"state": "active", "spec": {}})
    spec = {"command": "echo never", "runtime": "none",
            "max_task_retries": 1,
            "multi_instance": {"num_instances": 8,
                               "jax_distributed": {"enabled": False}}}
    try:
        # retries == max_task_retries: the budget is already burned.
        store.insert_entity(names.TABLE_TASKS, pk, "g0",
                            {"state": "running", "spec": spec,
                             "retries": 1})
        gang_pk = names.gang_pk("gquar", "jq", "g0", attempt=1)
        store.insert_entity(names.TABLE_GANGS, gang_pk, "i0", {
            "node_id": "ghost", "hostname": "ghost",
            "internal_ip": "10.9.9.9", "slice_index": 0,
            "worker_index": 0, "state": "joined"})
        store.insert_entity(names.TABLE_GANGS, gang_pk,
                            "node$ghost", {"instance": 0})
        store.upsert_entity(names.TABLE_NODES, "gquar", "ghost", {
            "state": "running", "heartbeat_at": 0.0})
        store.put_message(
            names.task_queue("gquar"),
            json.dumps({"job_id": "jq", "task_id": "g0",
                        "instance": 1}).encode())
        tasks = jobs_mgr.wait_for_tasks(store, "gquar", "jq",
                                        timeout=40, poll_interval=0.2)
        assert tasks[0]["state"] == names.TASK_STATE_QUARANTINED
        assert "gang member(s) lost" in tasks[0]["error"]
        assert "ghost" in str(
            tasks[0]["diagnostics"]["attempt_history"])
        assert not list(store.query_entities(names.TABLE_GANGS))
    finally:
        substrate.stop_all()


def test_abandoned_gang_claim_resumed_by_owner():
    """Regression: a worker slot that crashes AFTER _gang_claim (a
    store fault in the rendezvous loop — the chaos store_error shape)
    strands an i<k> row owned by a LIVE node. No observer ever judges
    it stale and no other node can insert over it, so before the
    resume path the gang wedged forever (drill timeout with the gang
    task stuck pending). The redelivered message must let the owning
    node resume its own abandoned claim and complete the gang."""
    store, substrate, pool = _make_pool("gresume")
    store.insert_entity(names.TABLE_JOBS, "gresume", "jr",
                        {"state": "active", "spec": {}})
    pk = names.task_pk("gresume", "jr")
    spec = {"command": "echo resumed", "runtime": "none",
            "max_task_retries": 1,
            "multi_instance": {"num_instances": 2,
                               "jax_distributed": {"enabled": False}}}
    try:
        store.insert_entity(names.TABLE_TASKS, pk, "g0",
                            {"state": "pending", "spec": spec,
                             "retries": 0})
        # Strand a live agent node's claim of instance 0 — the exact
        # rows a post-claim crash leaves behind.
        agent = next(iter(substrate._agents["gresume"].values()))
        gang_pk = names.gang_pk("gresume", "jr", "g0")
        store.insert_entity(names.TABLE_GANGS, gang_pk,
                            f"node${agent.identity.node_id}",
                            {"instance": 0})
        store.insert_entity(names.TABLE_GANGS, gang_pk, "i0", {
            "node_id": agent.identity.node_id,
            "hostname": agent.identity.hostname,
            "internal_ip": agent.identity.internal_ip,
            "slice_index": 0, "worker_index": 0,
            "state": "joined"})
        for k in range(2):
            store.put_message(
                names.task_queue("gresume"),
                json.dumps({"job_id": "jr", "task_id": "g0",
                            "instance": k}).encode())
        tasks = jobs_mgr.wait_for_tasks(store, "gresume", "jr",
                                        timeout=40, poll_interval=0.2)
        assert tasks[0]["state"] == "completed"
        assert not list(store.query_entities(names.TABLE_GANGS))
    finally:
        substrate.stop_all()


def test_gang_claim_resume_is_guarded():
    """_gang_claim resumes ONLY a claim that is ours, still 'joined',
    and not live in any worker slot of this process — a duplicate
    message copy or a finished member must keep bouncing."""
    store, substrate, pool = _make_pool("gguard")
    try:
        agent = next(iter(substrate._agents["gguard"].values()))
        me = agent.identity.node_id
        gang_pk = names.gang_pk("gguard", "jx", "g0")
        store.insert_entity(names.TABLE_GANGS, gang_pk,
                            f"node${me}", {"instance": 0})
        store.insert_entity(names.TABLE_GANGS, gang_pk, "i0",
                            {"node_id": me, "state": "joined"})
        # Abandoned (no slot holds it): resumed.
        assert agent._gang_claim(gang_pk, 0) is True
        # Now registered as live: a duplicate copy bounces.
        assert agent._gang_claim(gang_pk, 0) is False
        with agent._running_lock:
            agent._active_gang_claims.discard((gang_pk, 0))
        # A 'done' member is never resumed (the all-done probe path
        # finalizes on its behalf instead of re-running it).
        store.merge_entity(names.TABLE_GANGS, gang_pk, "i0",
                           {"state": "done"})
        assert agent._gang_claim(gang_pk, 0) is False
        # Another node's row is never resumable here.
        store.merge_entity(names.TABLE_GANGS, gang_pk, "i0",
                           {"node_id": "other", "state": "joined"})
        assert agent._gang_claim(gang_pk, 0) is False
    finally:
        substrate.stop_all()


# ----------------------- _node_alive grace window ----------------------

def test_node_alive_registration_grace():
    """Regression (satellite): a node entity registered but not yet
    heartbeating (heartbeat_at absent/0) is ALIVE within the
    staleness window of its registration — a gang observer must not
    fail a healthy just-booted member. Without registered_at (legacy
    rows) or past the window it is dead, as before."""
    store, substrate, pool = _make_pool("gracepool",
                                        accelerator="v5litepod-4")
    try:
        agents = _poll(
            lambda: list(substrate._agents.get("gracepool",
                                               {}).values()),
            timeout=15, message="agent booted")
        agent = agents[0]
        # Fresh registration, first heartbeat not yet landed: alive.
        store.upsert_entity(names.TABLE_NODES, "gracepool", "booting",
                            {"state": "creating",
                             "registered_at": time.time()})
        assert agent._node_alive("booting")
        # Registration older than the staleness window: dead.
        store.upsert_entity(names.TABLE_NODES, "gracepool", "stale",
                            {"state": "creating",
                             "registered_at": time.time() - 60.0})
        assert not agent._node_alive("stale")
        # Legacy row with neither heartbeat nor registration: dead
        # (the pre-grace behavior, unchanged).
        store.upsert_entity(names.TABLE_NODES, "gracepool", "legacy",
                            {"state": "running"})
        assert not agent._node_alive("legacy")
        # A fresh heartbeat always wins.
        store.upsert_entity(names.TABLE_NODES, "gracepool", "alive",
                            {"state": "running",
                             "heartbeat_at": time.time()})
        assert agent._node_alive("alive")
    finally:
        substrate.stop_all()


def test_orphaned_gang_janitor_sweeps_leaked_rows():
    """A gang cleanup cut short mid-flight (store fault between a
    state transition and its row clear, or a claim whose second
    insert failed) leaves rendezvous rows nothing would ever retire.
    The heartbeat janitor sweeps any partition whose task is
    terminal, gone, or past that attempt — and keeps the live
    attempt's rows."""
    store, substrate, pool = _make_pool("janitor")
    tpk = names.task_pk("janitor", "jj")
    store.insert_entity(names.TABLE_JOBS, "janitor", "jj",
                        {"state": "active", "spec": {}})
    # Terminal task with a leaked attempt-0 claim marker.
    store.insert_entity(names.TABLE_TASKS, tpk, "gdone",
                        {"state": "completed", "retries": 0,
                         "spec": {}})
    done_pk = names.gang_pk("janitor", "jj", "gdone")
    store.insert_entity(names.TABLE_GANGS, done_pk, "node$n0",
                        {"instance": 0})
    # Task row gone entirely (job deleted mid-fault).
    ghost_pk = names.gang_pk("janitor", "jj", "ghost")
    store.insert_entity(names.TABLE_GANGS, ghost_pk, "i0",
                        {"state": "joined"})
    # Live task on attempt 2: its stale attempt-0 partition is
    # garbage, its current attempt-2 partition is not.
    store.insert_entity(names.TABLE_TASKS, tpk, "glive",
                        {"state": "running", "retries": 2,
                         "spec": {}})
    stale_pk = names.gang_pk("janitor", "jj", "glive", attempt=0)
    live_pk = names.gang_pk("janitor", "jj", "glive", attempt=2)
    store.insert_entity(names.TABLE_GANGS, stale_pk, "node$n1",
                        {"instance": 0})
    store.insert_entity(names.TABLE_GANGS, live_pk, "i0",
                        {"state": "joined"})
    try:
        # The sweep is leader-gated (lowest-indexed live node).
        agent = next(a for a in
                     substrate._agents["janitor"].values()
                     if a.identity.node_index == 0)
        agent._last_gang_sweep -= agent.gang_sweep_interval + 1
        agent._sweep_orphaned_gangs()
        for pk in (done_pk, ghost_pk, stale_pk):
            assert not list(store.query_entities(
                names.TABLE_GANGS, partition_key=pk)), pk
        assert list(store.query_entities(
            names.TABLE_GANGS, partition_key=live_pk))
    finally:
        substrate.stop_all()


# ----------------------------- full drill ------------------------------

def test_chaos_drill_acceptance_kinds():
    """The acceptance drill: a seeded schedule injecting {wedge,
    mid-run kill, node preemption, heartbeat blackout} over a fakepod
    pool — every injection actually lands, every task ends completed
    exactly once, no orphaned coordination state, and the goodput
    partition stays exact."""
    from batch_shipyard_tpu.chaos.drill import run_drill
    kinds = ("task_wedge", "task_kill", "node_preempt",
             "heartbeat_blackout")
    report = run_drill(seed=5, kinds=kinds, wait_timeout=90.0)
    assert report["invariants"]["ok"]
    # 16 regular tasks + the always-included gang task (which makes
    # the orphaned-gang-rows check below non-vacuous).
    assert report["invariants"]["tasks"] == {"completed": 17}
    assert report["invariants"]["orphaned_gang_rows"] == 0
    assert report["invariants"]["queue_depth"] == 0
    # Every fault kind landed (a drill whose kills miss their victims
    # proves nothing about the kill paths)...
    applied = {a["kind"] for a in report["applied"]
               if a.get("applied")}
    assert applied == set(kinds), report["applied"]
    # ...and healing actually happened: the wedge + kill forced
    # retries, and the supervisor's backoff wait is priced.
    assert report["invariants"]["retries"] >= 1
    assert report["invariants"]["backoff_seconds"] > 0.0
    # The same seed plans the same schedule (CLI `chaos plan`).
    assert (ChaosPlan.generate(5, num_nodes=4, kinds=kinds)
            .fingerprint() == report["fingerprint"])


def test_chaos_drill_store_faults_survived():
    """Store-fault drill: injected latency + an error burst on state
    store ops are absorbed by the agent loops (requeue, retry next
    tick) — no task is lost and the partition stays exact."""
    from batch_shipyard_tpu.chaos.drill import run_drill
    # Seed 11 fires both error bursts (8 ops) at 0.49 s: tasks that
    # sleep 1.0 s finish half a second after, so the bursts are spent
    # on heartbeats, claims and gang rows and none is left for an
    # output upload, whose blob the agent gives up by design
    # (_finish_regular_result) and the drill's check then misses.
    report = run_drill(
        seed=11, tasks=8, duration=3.0, task_sleep=1.0,
        kinds=("store_delay", "store_error"),
        injections_per_kind=2, wait_timeout=60.0)
    assert report["invariants"]["ok"]
    assert report["invariants"]["tasks"] == {"completed": 9}
    applied = {a["kind"] for a in report["applied"]
               if a.get("applied")}
    assert applied == {"store_delay", "store_error"}
