"""Cooperative preemption + elastic gang resize (ROADMAP item 3).

Covers the full control plane: numeric priority victim election by
the leader sweep, heartbeat-path request delivery, the drain ->
forced-COMMITTED-checkpoint -> EXIT_PREEMPTED contract
(workloads/preempt_probe.py speaks it without importing jax), the
full-budget/neutral-health requeue, the preemption_recovery goodput
leg, and elastic gangs re-forming at surviving size. All CPU fakepod.
"""

import os
import pathlib
import signal
import sys
import time

import pytest

from batch_shipyard_tpu.agent import preemption
from batch_shipyard_tpu.config import settings as settings_mod
from batch_shipyard_tpu.goodput import accounting
from batch_shipyard_tpu.goodput import events as goodput_events
from batch_shipyard_tpu.jobs import manager as jobs_mgr
from batch_shipyard_tpu.pool import manager as pool_mgr
from batch_shipyard_tpu.state import names

REPO_ROOT = str(pathlib.Path(__file__).resolve().parent.parent)

PROBE = (f"{sys.executable} -m "
         f"batch_shipyard_tpu.workloads.preempt_probe")


def _make_pool(pool_id, accelerator=None, nodes=2, slots=1,
               **agent_kwargs):
    from batch_shipyard_tpu.state.memory import MemoryStateStore
    from batch_shipyard_tpu.substrate.fakepod import FakePodSubstrate
    store = MemoryStateStore()
    substrate = FakePodSubstrate(store, heartbeat_interval=0.2,
                                 node_stale_seconds=2.0)
    substrate.agent_kwargs = {
        "claim_visibility_seconds": 3.0, "gang_sweep_interval": 1.0,
        "retry_backoff_base": 0.2, "retry_backoff_cap": 1.0,
        **agent_kwargs}
    spec = {"id": pool_id, "substrate": "fake",
            "task_slots_per_node": slots,
            "max_wait_time_seconds": 30}
    if accelerator:
        spec["tpu"] = {"accelerator_type": accelerator}
    else:
        spec["vm_configuration"] = {"vm_count": {"dedicated": nodes}}
    conf = {"pool_specification": spec}
    pool = settings_mod.pool_settings(conf)
    pool_mgr.create_pool(store, substrate, pool,
                         settings_mod.global_settings({}), conf)
    return store, substrate, pool


def _wait_running(store, pool_id, job_id, task_id, timeout=25):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        task = jobs_mgr.get_task(store, pool_id, job_id, task_id)
        if task.get("state") == "running":
            return task
        time.sleep(0.1)
    raise AssertionError(f"{task_id} never reached running: {task}")


def test_preempt_watcher_contract(tmp_path):
    """write_request is atomic, read round-trips, poll latches once
    (a loop polling mid-drain must not trigger a second drain), and
    with no env/path the watcher is a disarmed no-op."""
    path = str(tmp_path / "req.json")
    assert preemption.PreemptWatcher(path).poll() is None
    preemption.write_request(path, reason="test", extra_key=1)
    request = preemption.read_request(path)
    assert request["reason"] == "test"
    assert request["extra_key"] == 1
    assert request["requested_at"]
    watcher = preemption.PreemptWatcher(path)
    assert watcher.armed
    first = watcher.poll()
    assert first and first["reason"] == "test"
    assert watcher.poll() is None  # latched
    assert not watcher.armed
    # No sink configured: disarmed (the out-of-pool no-op rule).
    assert os.environ.get(preemption.PREEMPT_REQUEST_FILE_ENV) is None
    disarmed = preemption.PreemptWatcher()
    assert not disarmed.armed
    assert disarmed.poll() is None


def test_request_preemption_requires_running(mem_statestore):
    """Only assigned/running tasks are preemptible; stamping is
    idempotent (one pending request -> one drain)."""
    store = mem_statestore
    pk = names.task_pk("p", "j")
    store.insert_entity(names.TABLE_TASKS, pk, "t",
                        {"state": "pending", "spec": {}})
    assert not jobs_mgr.request_preemption(store, "p", "j", "t")
    store.merge_entity(names.TABLE_TASKS, pk, "t",
                       {"state": "running"})
    assert jobs_mgr.request_preemption(store, "p", "j", "t",
                                       reason="r1")
    stamped = store.get_entity(names.TABLE_TASKS, pk, "t")
    request = stamped[names.TASK_COL_PREEMPT_REQUEST]
    assert request["reason"] == "r1"
    # Idempotent: the pending request is not overwritten (its
    # requested_at is the delivery dedup key).
    assert jobs_mgr.request_preemption(store, "p", "j", "t",
                                       reason="r2")
    again = store.get_entity(names.TABLE_TASKS, pk, "t")
    assert again[names.TASK_COL_PREEMPT_REQUEST] == request
    # The notice marker landed in the goodput log.
    kinds = [e["kind"] for e in goodput_events.query(store, "p")]
    assert kinds.count(goodput_events.TASK_PREEMPT_NOTICE) == 1


def test_regular_task_preempted_resumes_at_full_budget(tmp_path):
    """Acceptance e2e (regular task): preempt request -> heartbeat
    delivery -> drain -> forced COMMITTED checkpoint -> distinct
    preempted exit -> requeue with retries UNTOUCHED and node health
    UNDEBITED -> resume from the barrier with zero lost steps ->
    preemption_recovery priced, partition exact."""
    store, substrate, pool = _make_pool("pp", nodes=1)
    ckpt = str(tmp_path / "state.json")
    try:
        jobs = settings_mod.job_settings_list({"job_specifications": [{
            "id": "j1",
            "tasks": [{"id": "t0",
                       "command": (f"{PROBE} --steps 40 "
                                   f"--step-seconds 0.05 "
                                   f"--ckpt {ckpt}"),
                       "environment_variables": {
                           "PYTHONPATH": REPO_ROOT},
                       "max_task_retries": 2}],
        }]})
        jobs_mgr.add_jobs(store, pool, jobs)
        _wait_running(store, "pp", "j1", "t0")
        time.sleep(0.4)
        assert jobs_mgr.request_preemption(store, "pp", "j1", "t0",
                                           reason="test")
        rows = jobs_mgr.wait_for_tasks(store, "pp", "j1", timeout=60,
                                       poll_interval=0.2)
        task = rows[0]
        assert task["state"] == "completed"
        assert task.get("retries", 0) == 0
        assert task.get(names.TASK_COL_PREEMPT_COUNT) == 1
        # Ledger: barrier-contiguous, no replay, no gap.
        ledger = [line.split() for line in open(
            ckpt + ".steps.log", encoding="utf-8")]
        assert ledger[0][2] == "preempted"
        assert ledger[-1][2] == "completed"
        cursor = 0
        for _inst, span, _status in ledger:
            lo, hi = span.split("..")
            assert int(lo) == cursor, ledger
            cursor = int(hi)
        assert cursor == 40
        # Health untouched: a preempted exit is neutral.
        for node in store.query_entities(names.TABLE_NODES,
                                         partition_key="pp"):
            assert float(node.get(names.NODE_COL_HEALTH, 1.0)) >= 1.0
            assert not node.get(names.NODE_COL_QUARANTINED)
        report = accounting.pool_report(store, "pp",
                                        include_jobs=False)
        assert report["badput_seconds"]["preemption_recovery"] > 0
        total = (report["productive_seconds"]
                 + sum(report["badput_seconds"].values())
                 + sum(report["overlapped_seconds"].values()))
        assert abs(total - report["wall_seconds"]) <= max(
            1e-6 * max(1.0, report["wall_seconds"]), 1e-6)
    finally:
        substrate.stop_all()


def test_spurious_preempt_exit_is_budgeted():
    """EXIT_PREEMPTED without a pending preempt request is NOT a
    preemption: the retry supervisor prices it (otherwise a buggy
    always-75 task requeues at full budget forever)."""
    store, substrate, pool = _make_pool("sp", nodes=1)
    try:
        jobs = settings_mod.job_settings_list({"job_specifications": [{
            "id": "js",
            "tasks": [{"id": "t0", "runtime": "inproc",
                       "command": "preempt-exit",
                       "max_task_retries": 1}],
        }]})
        jobs_mgr.add_jobs(store, pool, jobs)
        rows = jobs_mgr.wait_for_tasks(store, "sp", "js", timeout=40,
                                       poll_interval=0.2)
        task = rows[0]
        # Budget (1) burned, then quarantined — never a full-budget
        # preempt loop.
        assert task["state"] == names.TASK_STATE_QUARANTINED
        assert task.get("retries") == 1
        assert not task.get(names.TASK_COL_PREEMPT_COUNT)
    finally:
        substrate.stop_all()


def test_preempt_sweep_elects_lower_priority_victim(tmp_path):
    """Numeric priority within a band: a pending priority-5 task that
    cannot place (single slot held by priority-0 work) is starved
    past the grace window; the leader sweep elects the running task
    as victim, it drains cooperatively, and the high-priority task
    runs in the freed slot. The victim then resumes and completes —
    at full retry budget."""
    store, substrate, pool = _make_pool(
        "sw", nodes=1, preempt_sweep_interval=0.5,
        preempt_grace_seconds=0.3)
    ckpt = str(tmp_path / "state.json")
    try:
        jobs = settings_mod.job_settings_list({"job_specifications": [{
            "id": "lo",
            "tasks": [{"id": "victim",
                       "command": (f"{PROBE} --steps 50 "
                                   f"--step-seconds 0.05 "
                                   f"--ckpt {ckpt}"),
                       "environment_variables": {
                           "PYTHONPATH": REPO_ROOT},
                       "max_task_retries": 2}],
        }]})
        jobs_mgr.add_jobs(store, pool, jobs)
        _wait_running(store, "sw", "lo", "victim")
        hi = settings_mod.job_settings_list({"job_specifications": [{
            "id": "hi",
            "tasks": [{"id": "urgent", "runtime": "inproc",
                       "command": "noop", "priority": 5}],
        }]})
        jobs_mgr.add_jobs(store, pool, hi)
        hi_rows = jobs_mgr.wait_for_tasks(store, "sw", "hi",
                                          timeout=40,
                                          poll_interval=0.2)
        assert hi_rows[0]["state"] == "completed"
        lo_rows = jobs_mgr.wait_for_tasks(store, "sw", "lo",
                                          timeout=60,
                                          poll_interval=0.2)
        victim = lo_rows[0]
        assert victim["state"] == "completed"
        assert victim.get("retries", 0) == 0
        assert victim.get(names.TASK_COL_PREEMPT_COUNT, 0) >= 1
        # The sweep's notice named the starved task.
        notices = [e for e in goodput_events.query(store, "sw")
                   if e["kind"] == goodput_events.TASK_PREEMPT_NOTICE]
        assert notices and \
            notices[0]["attrs"]["by_task_id"] == "urgent"
    finally:
        substrate.stop_all()


def test_gang_preempted_as_unit_resumes_from_barrier(tmp_path):
    """A preempt request on a gang task reaches EVERY instance (each
    node's heartbeat delivers into its own instance dir); the gang
    drains as a unit, finalizes with the preempted status, requeues
    ALL instances at full budget, and the rerun resumes from the
    forced commit."""
    store, substrate, pool = _make_pool("gp",
                                        accelerator="v5litepod-16")
    ckpt = os.path.join(substrate.work_root, "probe", "state.json")
    try:
        jobs = settings_mod.job_settings_list({"job_specifications": [{
            "id": "jg",
            "tasks": [{"id": "g0",
                       "command": (f"{PROBE} --steps 40 "
                                   f"--step-seconds 0.05 "
                                   f"--ckpt {ckpt}"),
                       "environment_variables": {
                           "PYTHONPATH": REPO_ROOT},
                       "max_task_retries": 2,
                       "multi_instance": {
                           "num_instances": 2,
                           "jax_distributed": {"enabled": False}}}],
        }]})
        jobs_mgr.add_jobs(store, pool, jobs)
        _wait_running(store, "gp", "jg", "g0")
        time.sleep(0.6)
        assert jobs_mgr.request_preemption(store, "gp", "jg", "g0",
                                           reason="gang test")
        rows = jobs_mgr.wait_for_tasks(store, "gp", "jg", timeout=60,
                                       poll_interval=0.2)
        task = rows[0]
        assert task["state"] == "completed"
        assert task.get("retries", 0) == 0
        assert task.get(names.TASK_COL_PREEMPT_COUNT) == 1
        ledger = [line.split() for line in open(
            ckpt + ".steps.log", encoding="utf-8")]
        assert ledger[0][2] == "preempted"
        assert ledger[-1][2] == "completed"
        assert ledger[1][1].split("..")[0] == \
            ledger[0][1].split("..")[1]
        assert not list(store.query_entities(names.TABLE_GANGS))
    finally:
        substrate.stop_all()


def test_elastic_gang_resizes_to_surviving_nodes():
    """Acceptance e2e: a 4-wide elastic gang (min_instances=2) loses
    2 of its 4 nodes mid-run; recovery re-forms it at size 2 (the
    rerun sees SHIPYARD_TASK_INSTANCES=2), a GANG_RESIZE event is
    emitted, and no gang rows leak."""
    store, substrate, pool = _make_pool("el",
                                        accelerator="v5litepod-16",
                                        gang_timeout=10.0)
    try:
        jobs = settings_mod.job_settings_list({"job_specifications": [{
            "id": "je",
            "tasks": [{"id": "g0",
                       "command": ("sleep 2.5 && echo elastic-"
                                   "$SHIPYARD_TASK_INSTANCES"),
                       "max_task_retries": 3,
                       "multi_instance": {
                           "num_instances": 4, "min_instances": 2,
                           "jax_distributed": {"enabled": False}}}],
        }]})
        jobs_mgr.add_jobs(store, pool, jobs)
        _wait_running(store, "el", "je", "g0")
        time.sleep(0.5)
        for node_id in ["el-s0-w2", "el-s0-w3"]:
            agent = substrate.agent("el", node_id)
            agent.stop_event.set()
            for proc in list(agent._live_procs.values()):
                try:
                    os.killpg(os.getpgid(proc.pid), signal.SIGKILL)
                except (ProcessLookupError, OSError):
                    pass
            substrate.crash_node("el", node_id)
        rows = jobs_mgr.wait_for_tasks(store, "el", "je", timeout=90,
                                       poll_interval=0.2)
        task = rows[0]
        assert task["state"] == "completed"
        assert task.get(names.TASK_COL_GANG_SIZE) == 2
        out = jobs_mgr.get_task_output(store, "el", "je", "g0",
                                       instance=0)
        assert out.strip() == b"elastic-2"
        resizes = [e for e in goodput_events.query(store, "el")
                   if e["kind"] == goodput_events.GANG_RESIZE]
        assert resizes and resizes[0]["attrs"]["new_size"] == 2
        assert resizes[0]["attrs"]["old_size"] == 4
        assert not list(store.query_entities(names.TABLE_GANGS))
    finally:
        substrate.stop_all()


def test_elastic_gang_resizes_when_formation_starved():
    """A gang that can NEVER form at its spec size (4 instances, 2
    nodes) re-forms at the elastic floor on rendezvous timeout
    instead of failing terminally — the formation-starved resize
    path."""
    store, substrate, pool = _make_pool("ef", nodes=2,
                                        gang_timeout=3.0)
    try:
        jobs = settings_mod.job_settings_list({"job_specifications": [{
            "id": "jf",
            "tasks": [{"id": "g0",
                       "command": ("echo formed-"
                                   "$SHIPYARD_TASK_INSTANCES"),
                       "max_task_retries": 2,
                       "multi_instance": {
                           "num_instances": 4, "min_instances": 2,
                           "jax_distributed": {"enabled": False}}}],
        }]})
        jobs_mgr.add_jobs(store, pool, jobs)
        rows = jobs_mgr.wait_for_tasks(store, "ef", "jf", timeout=60,
                                       poll_interval=0.2)
        task = rows[0]
        assert task["state"] == "completed"
        assert task.get(names.TASK_COL_GANG_SIZE) == 2
        out = jobs_mgr.get_task_output(store, "ef", "jf", "g0",
                                       instance=0)
        assert out.strip() == b"formed-2"
        assert not list(store.query_entities(names.TABLE_GANGS))
    finally:
        substrate.stop_all()


def test_rigid_gang_rendezvous_timeout_still_fails():
    """No min_instances floor = the historical contract: a gang that
    cannot form fails with the rendezvous timeout, never silently
    shrinks."""
    store, substrate, pool = _make_pool("rg", nodes=2,
                                        gang_timeout=2.0)
    try:
        jobs = settings_mod.job_settings_list({"job_specifications": [{
            "id": "jr",
            "tasks": [{"id": "g0", "command": "echo never",
                       "multi_instance": {
                           "num_instances": 4,
                           "jax_distributed": {"enabled": False}}}],
        }]})
        jobs_mgr.add_jobs(store, pool, jobs)
        rows = jobs_mgr.wait_for_tasks(store, "rg", "jr", timeout=40,
                                       poll_interval=0.2)
        assert rows[0]["state"] == "failed"
        assert "rendezvous timeout" in rows[0].get("error", "")
    finally:
        substrate.stop_all()


def test_inproc_runtime_end_to_end():
    """runtime: "inproc" — the 10^5-proof task mode: noop completes,
    fail retries through the supervisor, unknown commands exit 127;
    no task dir or output files are created (the whole point)."""
    store, substrate, pool = _make_pool("ip", nodes=1, slots=2)
    try:
        jobs = settings_mod.job_settings_list({"job_specifications": [{
            "id": "ji",
            "tasks": [
                {"id": "ok", "runtime": "inproc", "command": "noop"},
                {"id": "bad", "runtime": "inproc",
                 "command": "does-not-exist"},
            ],
        }]})
        jobs_mgr.add_jobs(store, pool, jobs)
        rows = {t["_rk"]: t for t in jobs_mgr.wait_for_tasks(
            store, "ip", "ji", timeout=30, poll_interval=0.1)}
        assert rows["ok"]["state"] == "completed"
        assert rows["bad"]["state"] == "failed"
        assert rows["bad"]["exit_code"] == 127
        # No files: the runner never touched the task dir.
        agent = substrate.agent("ip", "ip-s0-w0")
        task_dir = os.path.join(agent.work_dir, "tasks", "ji", "ok")
        assert not os.path.exists(
            os.path.join(task_dir, "stdout.txt"))
    finally:
        substrate.stop_all()


def test_scheduler_scale_smoke():
    """The scheduler scale drill end-to-end at a tier-1-sized
    count (10^4): every task completes through the real scheduling
    path — server-side expansion, streaming batched submission,
    batched claims, summary-based drain — throughput is reported, and
    the goodput partition is exact."""
    from batch_shipyard_tpu.chaos import drill
    result = drill.run_scheduler_scale_drill(
        num_tasks=10_000, nodes=2, slots=2, shards=2, timeout=240)
    assert result["completed"], result
    assert result["by_state"] == {"completed": 10_000}
    assert result["goodput"]["partition_exact"], result
    assert result["tasks_per_second"] > 0
    assert result["queue_depth_after"] == 0
    # The submit leg is materialized pool-side (one expansion row
    # from the client) and its breakdown is priced.
    assert result["server_side_expansion"] is True
    breakdown = result["submit_breakdown"]
    assert breakdown["messages"] == 10_000
    assert breakdown["expansion_wall_seconds"] > 0
    assert result["submit_seconds"] < result["run_seconds"]


@pytest.mark.slow
def test_scheduler_scale_million():
    """The same drill at its default 10^6 tasks (slow phase)."""
    from batch_shipyard_tpu.chaos import drill
    result = drill.run_scheduler_scale_drill()
    assert result["num_tasks"] == 1_000_000
    assert result["completed"], result
    assert result["goodput"]["partition_exact"], result
    assert result["submit_seconds"] < result["run_seconds"]


@pytest.mark.slow
def test_preemption_drill_acceptance():
    """The full seeded preemption drill (chaos drill --preempt): a
    node_preempt_notice schedule against a running gang — all
    invariants asserted inside run_preemption_drill."""
    from batch_shipyard_tpu.chaos import drill
    report = drill.run_preemption_drill(seed=1)
    assert report["invariants"]["ok"]
    assert report["invariants"]["retries"] == 0
    assert report["invariants"]["preempt_count"] >= 1


@pytest.mark.slow
def test_victim_selection_drill_acceptance():
    """ISSUE 17's live victim-cost proof (chaos drill --victim): two
    equal-priority eligible victims where the deterministic
    (priority, task_id) tie-break points at the EXPENSIVE one
    ("aa-costly" sorts before "zz-cheap") — the sweep must elect the
    cheap victim anyway, proving the goodput-cost term from synced
    sched hints decided the election, not scan order or id order.
    All invariants asserted inside run_victim_selection_drill."""
    from batch_shipyard_tpu.chaos import drill
    report = drill.run_victim_selection_drill(seed=0)
    assert report["invariants"]["ok"]
    assert report["invariants"]["retries"] == 0
    assert report["invariants"]["cheap_preempt_count"] >= 1
    assert report["invariants"]["costly_preempt_count"] == 0
    costs = report["invariants"]["victim_costs"]
    assert costs["aa-costly"] > costs["zz-cheap"]
