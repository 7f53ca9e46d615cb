"""`shipyard lint` analyzer tests: every rule family fires on its bad
shape and stays silent on the blessed shape, suppression and baseline
semantics hold, and — the tier-1 gate — the repo itself is lint-clean
against the checked-in baseline.

Fixtures are inline source snippets fed through
AnalysisContext.from_strings, so each test pins exactly one shape; no
JAX, no store, milliseconds each.
"""

from collections import Counter

import pytest

from batch_shipyard_tpu import analysis
from batch_shipyard_tpu.analysis import core, rules_registry


def _run(sources: dict, rule_id: str):
    ctx = analysis.AnalysisContext.from_strings(sources)
    active, suppressed = analysis.run_rules(ctx, [rule_id])
    return active, suppressed


def _rules_of(sources: dict, rule_id: str):
    active, _ = _run(sources, rule_id)
    return active


# ------------------------------ framework ------------------------------

def test_every_rule_has_family_and_provenance():
    assert len(analysis.RULES) >= 20
    families = {r.family for r in analysis.RULES.values()}
    # The five tentpole families plus wiring, shell, and sim.
    assert {"store", "loop", "env", "registry", "jax", "wiring",
            "shell", "sim"} <= families
    for r in analysis.RULES.values():
        assert r.doc.strip(), r.id
        assert "Provenance" in r.doc, (
            f"rule {r.id} docstring must name the real bug it "
            f"descends from")


def test_unknown_rule_id_raises():
    ctx = analysis.AnalysisContext.from_strings({})
    with pytest.raises(KeyError):
        analysis.run_rules(ctx, ["no-such-rule"])


_FIRING_STORE = {
    "batch_shipyard_tpu/mod.py": (
        "from batch_shipyard_tpu.state import names\n"
        "def bad(store):\n"
        "    store.upsert_entity(names.TABLE_TASKS, 'pk', 'rk',\n"
        "                        {'x': 1})\n"
    )}


def test_inline_suppression_on_offending_line():
    src = {"batch_shipyard_tpu/mod.py": (
        "from batch_shipyard_tpu.state import names\n"
        "def bad(store):\n"
        "    store.upsert_entity(names.TABLE_TASKS, 'pk', 'rk', "
        "{'x': 1})  # shipyard-lint: disable=store-blind-upsert\n")}
    active, suppressed = _run(src, "store-blind-upsert")
    assert not active and len(suppressed) == 1


def test_trailing_suppression_does_not_bleed_to_next_line():
    """A trailing directive covers ITS line only — an unrelated
    violation directly below a justified one must still fail."""
    src = {"batch_shipyard_tpu/mod.py": (
        "from batch_shipyard_tpu.state import names\n"
        "def bad(store):\n"
        "    store.upsert_entity(names.TABLE_TASKS, 'pk', 'rk', "
        "{'x': 1})  # shipyard-lint: disable=store-blind-upsert\n"
        "    store.upsert_entity(names.TABLE_GANGS, 'pk', 'rk', "
        "{'x': 1})\n")}
    active, suppressed = _run(src, "store-blind-upsert")
    assert len(active) == 1 and len(suppressed) == 1
    assert "gangs" in active[0].message


def test_suppression_on_line_above():
    src = {"batch_shipyard_tpu/mod.py": (
        "from batch_shipyard_tpu.state import names\n"
        "def bad(store):\n"
        "    # shipyard-lint: disable=store-blind-upsert\n"
        "    store.upsert_entity(names.TABLE_TASKS, 'pk', 'rk', "
        "{'x': 1})\n")}
    active, suppressed = _run(src, "store-blind-upsert")
    assert not active and len(suppressed) == 1


def test_file_level_suppression_in_prologue_only():
    fire = "X=`date`\n" * 20
    head = "#!/bin/sh\n# shipyard-lint: disable-file=" \
           "shell-backtick-subst\n"
    active, suppressed = _run({"tools/a.sh": head + fire},
                              "shell-backtick-subst")
    assert not active and len(suppressed) == 20
    # Past the 10-line prologue the directive is inert.
    late = "#!/bin/sh\n" + "true\n" * 12 + \
        "# shipyard-lint: disable-file=shell-backtick-subst\n" + \
        "X=`date`\n"
    active, _ = _run({"tools/b.sh": late}, "shell-backtick-subst")
    assert len(active) == 1


def test_baseline_split_and_stale_detection(tmp_path):
    ctx = analysis.AnalysisContext.from_strings(_FIRING_STORE)
    active, _ = analysis.run_rules(ctx, ["store-blind-upsert"])
    assert len(active) == 1
    # Baselined: the finding warns instead of failing.
    baseline = Counter({active[0].fingerprint(): 1})
    report = analysis.analyze(ctx=ctx,
                              rule_ids=["store-blind-upsert"],
                              baseline=baseline)
    assert not report.new and len(report.baselined) == 1
    assert not report.stale_baseline
    # Stale: a baseline entry whose finding was fixed is reported so
    # triage debt shrinks monotonically.
    fixed_ctx = analysis.AnalysisContext.from_strings(
        {"batch_shipyard_tpu/mod.py": "x = 1\n"})
    report = analysis.analyze(ctx=fixed_ctx,
                              rule_ids=["store-blind-upsert"],
                              baseline=baseline)
    assert not report.new and not report.baselined
    assert report.stale_baseline == [active[0].fingerprint()]


def test_partial_rule_run_scopes_baseline():
    """`--rules X` judges only rule X's slice of the baseline: other
    rules' triaged entries are out of scope, not stale — a scoped run
    on a healthy tree must stay clean."""
    ctx = analysis.AnalysisContext.from_strings(_FIRING_STORE)
    other = Counter({("shell-backtick-subst", "tools/x.sh",
                      "backtick command substitution; use $(...)"): 1})
    active, _ = analysis.run_rules(ctx, ["store-blind-upsert"])
    baseline = other + Counter({active[0].fingerprint(): 1})
    report = analysis.analyze(ctx=ctx,
                              rule_ids=["store-blind-upsert"],
                              baseline=baseline)
    assert not report.new and not report.stale_baseline
    assert len(report.baselined) == 1


def test_baseline_write_is_deterministic(tmp_path):
    # Two findings, so the write exercises real ordering (a
    # single-element list would hide sort bugs).
    src = {"batch_shipyard_tpu/mod.py": (
        "from batch_shipyard_tpu.state import names\n"
        "def bad(store):\n"
        "    store.upsert_entity(names.TABLE_TASKS, 'pk', 'rk', "
        "{'x': 1})\n"
        "    store.upsert_entity(names.TABLE_GANGS, 'pk', 'rk', "
        "{'x': 1})\n")}
    ctx = analysis.AnalysisContext.from_strings(src)
    active, _ = analysis.run_rules(ctx, ["store-blind-upsert"])
    assert len(active) == 2
    p1, p2 = tmp_path / "b1.json", tmp_path / "b2.json"
    analysis.write_baseline(p1, list(reversed(active)))
    analysis.write_baseline(p2, active)
    assert p1.read_bytes() == p2.read_bytes()
    loaded = analysis.load_baseline(p1)
    assert loaded == Counter(f.fingerprint() for f in active)


# ---------------------------- store family -----------------------------

def test_store_blind_upsert_fires_and_blessed_shapes_pass():
    assert len(_rules_of(_FIRING_STORE, "store-blind-upsert")) == 1
    # Local-constant indirection resolves too (the schedules.py
    # shape that motivated the rule).
    via_const = {"batch_shipyard_tpu/mod.py": (
        "_T = 'gangs'\n"
        "def bad(store):\n"
        "    store.upsert_entity(_T, 'pk', 'rk', {'x': 1})\n")}
    assert len(_rules_of(via_const, "store-blind-upsert")) == 1
    blessed = {"batch_shipyard_tpu/mod.py": (
        "from batch_shipyard_tpu.state import names\n"
        "def good(store, row):\n"
        "    store.upsert_entity(names.TABLE_MONITOR, 'pk', 'rk',\n"
        "                        {'x': 1})\n"
        "    store.merge_entity(names.TABLE_TASKS, 'pk', 'rk',\n"
        "                       {'x': 1}, if_match=row['_etag'])\n"
        "    store.insert_entity(names.TABLE_TASKS, 'pk', 'rk',\n"
        "                        {'x': 1})\n")}
    assert not _rules_of(blessed, "store-blind-upsert")


def test_store_rmw_no_etag_fires_on_derived_write():
    firing = {"batch_shipyard_tpu/mod.py": (
        "from batch_shipyard_tpu.state import names\n"
        "def bump(store):\n"
        "    row = store.get_entity(names.TABLE_TASKS, 'p', 'r')\n"
        "    count = int(row.get('n', 0))\n"
        "    store.merge_entity(names.TABLE_TASKS, 'p', 'r',\n"
        "                       {'n': count + 1})\n")}
    found = _rules_of(firing, "store-rmw-no-etag")
    assert len(found) == 1 and found[0].line == 5
    blessed = {"batch_shipyard_tpu/mod.py": (
        "from batch_shipyard_tpu.state import names\n"
        "def bump(store):\n"
        "    row = store.get_entity(names.TABLE_TASKS, 'p', 'r')\n"
        "    count = int(row.get('n', 0))\n"
        "    store.merge_entity(names.TABLE_TASKS, 'p', 'r',\n"
        "                       {'n': count + 1},\n"
        "                       if_match=row['_etag'])\n")}
    assert not _rules_of(blessed, "store-rmw-no-etag")
    # A fresh-column stamp derives nothing from the read: allowed.
    stamp = {"batch_shipyard_tpu/mod.py": (
        "from batch_shipyard_tpu.state import names\n"
        "def stamp(store):\n"
        "    row = store.get_entity(names.TABLE_TASKS, 'p', 'r')\n"
        "    if row.get('state') != 'running':\n"
        "        return\n"
        "    store.merge_entity(names.TABLE_TASKS, 'p', 'r',\n"
        "                       {'note': 'seen'})\n")}
    assert not _rules_of(stamp, "store-rmw-no-etag")


def test_store_etag_retry_requires_refetch():
    firing = {"batch_shipyard_tpu/mod.py": (
        "from batch_shipyard_tpu.state.base import "
        "EtagMismatchError\n"
        "from batch_shipyard_tpu.state import names\n"
        "def retry(store, etag):\n"
        "    try:\n"
        "        store.merge_entity(names.TABLE_TASKS, 'p', 'r',\n"
        "                           {'x': 1}, if_match=etag)\n"
        "    except EtagMismatchError:\n"
        "        store.merge_entity(names.TABLE_TASKS, 'p', 'r',\n"
        "                           {'x': 1})\n")}
    assert len(_rules_of(firing, "store-etag-retry-no-refetch")) == 1
    blessed = {"batch_shipyard_tpu/mod.py": (
        "from batch_shipyard_tpu.state.base import "
        "EtagMismatchError\n"
        "from batch_shipyard_tpu.state import names\n"
        "def retry(store, etag):\n"
        "    try:\n"
        "        store.merge_entity(names.TABLE_TASKS, 'p', 'r',\n"
        "                           {'x': 1}, if_match=etag)\n"
        "    except EtagMismatchError:\n"
        "        row = store.get_entity(names.TABLE_TASKS, 'p',\n"
        "                               'r')\n"
        "        store.merge_entity(names.TABLE_TASKS, 'p', 'r',\n"
        "                           {'x': 1},\n"
        "                           if_match=row['_etag'])\n")}
    assert not _rules_of(blessed, "store-etag-retry-no-refetch")


# ----------------------------- loop family -----------------------------

def test_loop_unpartitioned_scan_needs_leader_gate():
    firing = {"batch_shipyard_tpu/agent/mod.py": (
        "from batch_shipyard_tpu.state import names\n"
        "class A:\n"
        "    def _sweep_things(self):\n"
        "        for row in self.store.query_entities(\n"
        "                names.TABLE_TASKS):\n"
        "            pass\n")}
    assert len(_rules_of(firing, "loop-unpartitioned-scan")) == 1
    gated = {"batch_shipyard_tpu/agent/mod.py": (
        "from batch_shipyard_tpu.state import names\n"
        "class A:\n"
        "    def _sweep_things(self):\n"
        "        if not self._is_gang_sweep_leader():\n"
        "            return\n"
        "        for row in self.store.query_entities(\n"
        "                names.TABLE_TASKS):\n"
        "            pass\n")}
    assert not _rules_of(gated, "loop-unpartitioned-scan")
    partitioned = {"batch_shipyard_tpu/agent/mod.py": (
        "from batch_shipyard_tpu.state import names\n"
        "class A:\n"
        "    def _sweep_things(self):\n"
        "        for row in self.store.query_entities(\n"
        "                names.TABLE_TASKS,\n"
        "                partition_key=self.pool_id):\n"
        "            pass\n")}
    assert not _rules_of(partitioned, "loop-unpartitioned-scan")


def test_leader_sweep_no_lease_requires_epoch_idiom():
    # A heartbeat-freshness election gates the scan rule but is NOT a
    # lease: the new rule still fires.
    elected = {"batch_shipyard_tpu/agent/mod.py": (
        "from batch_shipyard_tpu.state import names\n"
        "class A:\n"
        "    def _sweep_things(self):\n"
        "        if not self._is_gang_sweep_leader():\n"
        "            return\n"
        "        for row in self.store.query_entities(\n"
        "                names.TABLE_TASKS):\n"
        "            pass\n")}
    assert len(_rules_of(elected, "leader-sweep-no-lease")) == 1
    # The lease idiom (a leader_epoch call) is blessed.
    leased = {"batch_shipyard_tpu/agent/mod.py": (
        "from batch_shipyard_tpu.state import names\n"
        "class A:\n"
        "    def _sweep_things(self):\n"
        "        epoch = self._sweep_leader_epoch('janitor')\n"
        "        if epoch is None:\n"
        "            return\n"
        "        for row in self.store.query_entities(\n"
        "                names.TABLE_TASKS):\n"
        "            pass\n")}
    assert not _rules_of(leased, "leader-sweep-no-lease")
    # A leased sweep whose stamp does NOT thread the epoch through
    # still fires — the fencing is the point.
    unfenced = {"batch_shipyard_tpu/agent/mod.py": (
        "class A:\n"
        "    def _sweep_preempt(self):\n"
        "        epoch = self._sweep_leader_epoch('preempt')\n"
        "        if epoch is None:\n"
        "            return\n"
        "        request_preemption(self.store, 'p', 'j', 't')\n")}
    assert len(_rules_of(unfenced, "leader-sweep-no-lease")) == 1
    fenced = {"batch_shipyard_tpu/agent/mod.py": (
        "class A:\n"
        "    def _sweep_preempt(self):\n"
        "        epoch = self._sweep_leader_epoch('preempt')\n"
        "        if epoch is None:\n"
        "            return\n"
        "        request_preemption(self.store, 'p', 'j', 't',\n"
        "                           leader_epoch=epoch)\n")}
    assert not _rules_of(fenced, "leader-sweep-no-lease")
    # Non-sweep functions are out of scope (manual CLI preempts
    # carry their own follow-through).
    manual = {"batch_shipyard_tpu/agent/mod.py": (
        "def action_jobs_preempt(store):\n"
        "    request_preemption(store, 'p', 'j', 't')\n")}
    assert not _rules_of(manual, "leader-sweep-no-lease")


def test_loop_sleep_in_sweep_fires_only_on_hot_functions():
    firing = {"batch_shipyard_tpu/agent/mod.py": (
        "import time\n"
        "class A:\n"
        "    def _sweep_things(self):\n"
        "        time.sleep(1.0)\n")}
    assert len(_rules_of(firing, "loop-sleep-in-sweep")) == 1
    # Poll loops legitimately pace on sleep between empty polls.
    poll = {"batch_shipyard_tpu/agent/mod.py": (
        "import time\n"
        "class A:\n"
        "    def _worker_loop(self):\n"
        "        time.sleep(0.5)\n")}
    assert not _rules_of(poll, "loop-sleep-in-sweep")


# ------------------------------ env family -----------------------------

def test_env_read_unexported_fires_and_knobs_pass():
    firing = {"batch_shipyard_tpu/mod.py": (
        "import os\n"
        "V = os.environ.get('SHIPYARD_NOT_EXPORTED')\n")}
    assert len(_rules_of(firing, "env-read-unexported")) == 1
    exported = {"batch_shipyard_tpu/mod.py": (
        "import os\n"
        "V = os.environ.get('SHIPYARD_OK')\n"),
        "batch_shipyard_tpu/agent/mod.py": (
        "def launch(env):\n"
        "    env['SHIPYARD_OK'] = '1'\n")}
    assert not _rules_of(exported, "env-read-unexported")
    knob = {"batch_shipyard_tpu/mod.py": (
        "import os\n"
        "V = os.environ.get('SHIPYARD_SECRETS_FILE')\n")}
    assert not _rules_of(knob, "env-read-unexported")


def test_env_export_unread_honors_documented_contract():
    firing = {"batch_shipyard_tpu/agent/mod.py": (
        "def launch(env):\n"
        "    env['SHIPYARD_ORPHAN'] = '1'\n")}
    assert len(_rules_of(firing, "env-export-unread")) == 1
    documented = {"batch_shipyard_tpu/agent/task_runner.py": (
        '"""Env contract:\n\n'
        '  SHIPYARD_DOCUMENTED  exposed to user task commands\n'
        '"""\n'
        "def launch(env):\n"
        "    env['SHIPYARD_DOCUMENTED'] = '1'\n")}
    assert not _rules_of(documented, "env-export-unread")


def test_env_docker_unmapped_fires_on_dropped_contract_var():
    firing = {"batch_shipyard_tpu/agent/task_runner.py": (
        "def build_task_env(execution):\n"
        "    env = {}\n"
        "    env.update({\n"
        "        'SHIPYARD_POOL_ID': execution.pool_id,\n"
        "        'SHIPYARD_LOST': 'x',\n"
        "    })\n"
        "    return env\n"
        "def synthesize_command(execution):\n"
        "    argv = ['docker', 'run']\n"
        "    for var in ('SHIPYARD_POOL_ID',):\n"
        "        argv += ['-e', var]\n"
        "    return argv\n")}
    found = _rules_of(firing, "env-docker-unmapped")
    assert len(found) == 1 and "SHIPYARD_LOST" in found[0].message
    fixed = dict(firing)
    fixed["batch_shipyard_tpu/agent/task_runner.py"] = fixed[
        "batch_shipyard_tpu/agent/task_runner.py"].replace(
        "('SHIPYARD_POOL_ID',)", "('SHIPYARD_POOL_ID', "
        "'SHIPYARD_LOST')")
    assert not _rules_of(fixed, "env-docker-unmapped")
    # A variable named only in a COMMENT is not forwarded — the rule
    # must keep firing (deleting the -e line while keeping its
    # comment must not go green).
    commented = dict(firing)
    commented["batch_shipyard_tpu/agent/task_runner.py"] = commented[
        "batch_shipyard_tpu/agent/task_runner.py"].replace(
        "    argv = ['docker', 'run']\n",
        "    argv = ['docker', 'run']\n"
        "    # SHIPYARD_LOST is remapped below\n")
    found = _rules_of(commented, "env-docker-unmapped")
    assert len(found) == 1 and "SHIPYARD_LOST" in found[0].message
    # Nor in the DOCSTRING — prose must not count as forwarding.
    documented = dict(firing)
    documented["batch_shipyard_tpu/agent/task_runner.py"] = \
        documented["batch_shipyard_tpu/agent/task_runner.py"].replace(
        "def synthesize_command(execution):\n",
        "def synthesize_command(execution):\n"
        '    """SHIPYARD_LOST is forwarded below."""\n')
    found = _rules_of(documented, "env-docker-unmapped")
    assert len(found) == 1 and "SHIPYARD_LOST" in found[0].message


def test_env_docker_contract_holds_in_real_runner():
    """Regression anchor for the finding this rule caught in this
    PR: the real task_runner forwards every build_task_env var."""
    ctx = analysis.AnalysisContext.from_tree()
    active, _ = analysis.run_rules(ctx, ["env-docker-unmapped"])
    assert not active, [f.render() for f in active]


# --------------------------- registry family ---------------------------

def test_registry_table_undeclared_fires():
    firing = {"batch_shipyard_tpu/mod.py": (
        "def f(store):\n"
        "    store.get_entity('nosuchtable', 'p', 'r')\n")}
    assert len(_rules_of(firing, "registry-table-undeclared")) == 1
    attr = {"batch_shipyard_tpu/mod.py": (
        "from batch_shipyard_tpu.state import names\n"
        "T = names.TABLE_BOGUS\n")}
    assert len(_rules_of(attr, "registry-table-undeclared")) == 1
    blessed = {"batch_shipyard_tpu/mod.py": (
        "from batch_shipyard_tpu.state import names\n"
        "_T = 'tasks'\n"
        "def f(store):\n"
        "    store.get_entity(names.TABLE_TASKS, 'p', 'r')\n"
        "    store.get_entity(_T, 'p', 'r')\n")}
    assert not _rules_of(blessed, "registry-table-undeclared")


def test_registry_state_literal_fires():
    firing = {"batch_shipyard_tpu/mod.py": (
        "def f(row):\n"
        "    if row.get('state') == 'zombie':\n"
        "        return {'state': 'zombie'}\n")}
    assert len(_rules_of(firing, "registry-state-literal")) == 2
    blessed = {"batch_shipyard_tpu/mod.py": (
        "def f(row):\n"
        "    if row.get('state') in ('pending', 'RUNNING'):\n"
        "        return {'state': 'completed'}\n")}
    assert not _rules_of(blessed, "registry-state-literal")


def test_goodput_kind_undeclared_fires_via_alias():
    firing = {"batch_shipyard_tpu/mod.py": (
        "from batch_shipyard_tpu.goodput import events as gp\n"
        "def f(store):\n"
        "    gp.emit(store, 'p', gp.TASK_NOPE)\n")}
    found = _rules_of(firing, "goodput-kind-undeclared")
    assert len(found) == 1 and "TASK_NOPE" in found[0].message
    blessed = {"batch_shipyard_tpu/mod.py": (
        "from batch_shipyard_tpu.goodput import events as gp\n"
        "def f(store):\n"
        "    gp.emit(store, 'p', gp.TASK_QUEUED)\n"
        "    path = gp.GOODPUT_FILE_ENV\n")}
    assert not _rules_of(blessed, "goodput-kind-undeclared")


def test_goodput_kind_unpriced_fires_when_marker_unregistered(
        monkeypatch):
    events_stub = {"batch_shipyard_tpu/goodput/events.py": (
        "EVENT_KINDS = frozenset()\n")}
    # Every real kind is priced or a declared marker.
    assert not _rules_of(events_stub, "goodput-kind-unpriced")
    # Un-declare the markers: the rule must catch the now-unpriced
    # interval kinds (this is what happens when someone registers a
    # new kind without teaching accounting about it).
    monkeypatch.setattr(rules_registry, "MARKER_EVENT_KINDS",
                        frozenset())
    found = _rules_of(events_stub, "goodput-kind-unpriced")
    # retry, preempt notice/exit, evicted, gang resize
    assert len(found) == 5


def test_trace_span_undeclared_fires_via_alias():
    firing = {"batch_shipyard_tpu/mod.py": (
        "from batch_shipyard_tpu.trace import spans as tr\n"
        "K = tr.SPAN_NOPE\n")}
    assert len(_rules_of(firing, "trace-span-undeclared")) == 1
    blessed = {"batch_shipyard_tpu/mod.py": (
        "from batch_shipyard_tpu.trace import spans as tr\n"
        "K = tr.SPAN_SUBMIT\n")}
    assert not _rules_of(blessed, "trace-span-undeclared")


def test_trace_span_no_with_fires_on_bare_call():
    firing = {"batch_shipyard_tpu/mod.py": (
        "from batch_shipyard_tpu.goodput import events as gp\n"
        "def f():\n"
        "    gp.phase('compile')\n")}
    assert len(_rules_of(firing, "trace-span-no-with")) == 1
    blessed = {"batch_shipyard_tpu/mod.py": (
        "from batch_shipyard_tpu.goodput import events as gp\n"
        "def f():\n"
        "    with gp.phase('compile'):\n"
        "        pass\n")}
    assert not _rules_of(blessed, "trace-span-no-with")


# ----------------------------- jax family ------------------------------

def test_jax_impure_pure_fn_fires_in_contract_scope():
    firing = {"batch_shipyard_tpu/chaos/plan.py": (
        "import time\n"
        "class ChaosPlan:\n"
        "    def generate(cls, seed):\n"
        "        return time.time()\n")}
    assert len(_rules_of(firing, "jax-impure-pure-fn")) == 1
    # Seeded RNG is the mechanism, not a violation; and the same
    # call OUTSIDE a contract function is fine.
    blessed = {"batch_shipyard_tpu/chaos/plan.py": (
        "import random, time\n"
        "class ChaosPlan:\n"
        "    def generate(cls, seed):\n"
        "        rng = random.Random(seed)\n"
        "        return rng.uniform(0, 1)\n"
        "def run_drill():\n"
        "    return time.time()\n")}
    assert not _rules_of(blessed, "jax-impure-pure-fn")


def test_jax_donated_reuse_fires_on_stale_read():
    firing = {"batch_shipyard_tpu/mod.py": (
        "import jax\n"
        "step = jax.jit(lambda p, b: p, donate_argnums=(0,))\n"
        "def loop(params, batch):\n"
        "    loss = step(params, batch)\n"
        "    norm = params['w']\n"
        "    return loss, norm\n")}
    found = _rules_of(firing, "jax-donated-reuse")
    assert len(found) == 1 and found[0].line == 5
    # The blessed rebind-in-one-statement shape (multi-line call
    # included — the real train.py step_wrapper layout).
    blessed = {"batch_shipyard_tpu/mod.py": (
        "import functools, jax\n"
        "@functools.partial(jax.jit, donate_argnums=(0, 1))\n"
        "def step(params, opt, batch):\n"
        "    return params, opt\n"
        "def loop(params, opt, batch):\n"
        "    params, opt = step(\n"
        "        params, opt, batch)\n"
        "    return params, opt\n")}
    assert not _rules_of(blessed, "jax-donated-reuse")


def test_jax_donated_reuse_reads_donate_argnames():
    """The spelling of the serving step programs: names, resolved
    against the jitted function's own parameters (statics counted)."""
    header = (
        "import functools, jax\n"
        "@functools.partial(jax.jit, static_argnames=('model',),\n"
        "                   donate_argnames=('cache',))\n"
        "def step(model, params, cache, tokens):\n"
        "    return cache, tokens\n")
    firing = {"batch_shipyard_tpu/mod.py": header + (
        "def loop(model, params, cache, tokens):\n"
        "    new, tokens = step(model, params, cache, tokens)\n"
        "    return new, cache['k']\n")}
    found = _rules_of(firing, "jax-donated-reuse")
    assert len(found) == 1 and found[0].line == 8
    blessed = {"batch_shipyard_tpu/mod.py": header + (
        "def loop(model, params, cache, tokens):\n"
        "    cache, tokens = step(model, params, cache, tokens)\n"
        "    return cache, params\n")}
    assert not _rules_of(blessed, "jax-donated-reuse")
    # The assignment idiom over a function the module defines.
    assigned = {"batch_shipyard_tpu/mod.py": (
        "import jax\n"
        "def _step(params, cache):\n"
        "    return cache\n"
        "step = jax.jit(_step, donate_argnames='cache')\n"
        "def loop(params, cache):\n"
        "    new = step(params, cache)\n"
        "    return new, cache\n")}
    found = _rules_of(assigned, "jax-donated-reuse")
    assert len(found) == 1 and found[0].line == 7


def test_jax_restore_no_drain_fires_without_wait():
    firing = {"batch_shipyard_tpu/workloads/mod.py": (
        "from batch_shipyard_tpu.workloads.checkpoint import (\n"
        "    AsyncCheckpointManager, restore)\n"
        "def resume(manager, tmpl):\n"
        "    return restore('dir', tmpl)\n")}
    assert len(_rules_of(firing, "jax-restore-no-drain")) == 1
    drained = {"batch_shipyard_tpu/workloads/mod.py": (
        "from batch_shipyard_tpu.workloads.checkpoint import (\n"
        "    AsyncCheckpointManager, restore)\n"
        "def resume(manager, tmpl):\n"
        "    manager.wait_until_finished()\n"
        "    return restore('dir', tmpl)\n")}
    assert not _rules_of(drained, "jax-restore-no-drain")
    guarded = {"batch_shipyard_tpu/workloads/mod.py": (
        "from batch_shipyard_tpu.workloads.checkpoint import (\n"
        "    AsyncCheckpointManager, restore)\n"
        "def resume(manager, tmpl):\n"
        "    if manager is not None:\n"
        "        return manager.restore(tmpl)\n"
        "    else:\n"
        "        return restore('dir', tmpl)\n")}
    assert not _rules_of(guarded, "jax-restore-no-drain")


def test_jax_blocking_save_in_train_fires():
    firing = {"batch_shipyard_tpu/workloads/train_foo.py": (
        "from batch_shipyard_tpu.workloads import checkpoint\n"
        "def main(params, opt):\n"
        "    checkpoint.save('dir', 1, params, opt)\n")}
    assert len(_rules_of(firing, "jax-blocking-save-in-train")) == 1
    blessed = {"batch_shipyard_tpu/workloads/train_foo.py": (
        "from batch_shipyard_tpu.workloads import checkpoint\n"
        "def main(ckpt, params, opt):\n"
        "    ckpt.step_save(1, params, opt)\n")}
    assert not _rules_of(blessed, "jax-blocking-save-in-train")


# ---------------------------- wiring family ----------------------------

def test_preempt_grace_unbounded_fires_and_blessed():
    """A sweep-cadence function stamping preemption notices with no
    escalate/evict call in reach = an unbounded grace window (the
    PR 12 bug class); the blessed shape calls an escalation helper.
    Non-sweep callers (manual CLI preempt, chaos injectors) are out
    of scope."""
    firing = {"batch_shipyard_tpu/mod.py": (
        "def _sweep_preemptions(self):\n"
        "    for row in rows:\n"
        "        request_preemption(store, 'p', 'j', 't')\n")}
    found = _rules_of(firing, "preempt-grace-unbounded")
    assert len(found) == 1
    assert "escalation" in found[0].message
    blessed = {"batch_shipyard_tpu/mod.py": (
        "def _sweep_preemptions(self):\n"
        "    for row in rows:\n"
        "        if overdue(row):\n"
        "            self._maybe_escalate_eviction(row)\n"
        "            continue\n"
        "        request_preemption(store, 'p', 'j', 't')\n")}
    assert not _rules_of(blessed, "preempt-grace-unbounded")
    # A non-sweep function stamping a notice (the manual override,
    # the chaos injector) is out of the rule's scope.
    manual = {"batch_shipyard_tpu/mod.py": (
        "def action_jobs_preempt(ctx):\n"
        "    request_preemption(ctx.store, 'p', 'j', 't')\n")}
    assert not _rules_of(manual, "preempt-grace-unbounded")


def test_wiring_cli_action_unwired_fires():
    firing = {
        "batch_shipyard_tpu/fleet.py": (
            "def action_orphan(ctx):\n"
            "    pass\n"),
        "batch_shipyard_tpu/cli/main.py": "x = 1\n"}
    found = _rules_of(firing, "wiring-cli-action-unwired")
    assert len(found) == 1 and "action_orphan" in found[0].message
    wired = {
        "batch_shipyard_tpu/fleet.py": (
            "def action_orphan(ctx):\n"
            "    pass\n"),
        "batch_shipyard_tpu/cli/main.py": (
            "from batch_shipyard_tpu import fleet\n"
            "def cmd():\n"
            "    fleet.action_orphan(None)\n")}
    assert not _rules_of(wired, "wiring-cli-action-unwired")


def test_wiring_kinds_help_stale_fires_on_hardcoded_help():
    firing = {"batch_shipyard_tpu/cli/main.py": (
        "import click\n"
        "@click.option('--kinds', help='store_delay,task_kill')\n"
        "def cmd(kinds):\n"
        "    pass\n")}
    assert len(_rules_of(firing, "wiring-kinds-help-stale")) == 1
    derived = {"batch_shipyard_tpu/cli/main.py": (
        "import click\n"
        "from batch_shipyard_tpu.chaos import plan as p\n"
        "@click.option('--kinds',\n"
        "              help=','.join(p.INJECTION_KINDS))\n"
        "def cmd(kinds):\n"
        "    pass\n"
        "@click.option('--kinds',\n"
        "              help=','.join(p.INJECTION_KINDS))\n"
        "def cmd2(kinds):\n"
        "    pass\n")}
    assert not _rules_of(derived, "wiring-kinds-help-stale")
    # A THIRD --kinds option with hand-written help must not hide
    # behind the two derived ones: one join per option.
    mixed = dict(derived)
    mixed["batch_shipyard_tpu/cli/main.py"] += (
        "@click.option('--kinds', help='store_delay,task_kill')\n"
        "def cmd3(kinds):\n"
        "    pass\n")
    assert len(_rules_of(mixed, "wiring-kinds-help-stale")) == 1


def test_wiring_compile_cache_optout_fires():
    firing = {"batch_shipyard_tpu/workloads/train_foo.py": (
        "from batch_shipyard_tpu.parallel import train\n"
        "def main():\n"
        "    train.TrainHarness\n")}
    assert len(_rules_of(firing, "wiring-compile-cache-optout")) == 2
    blessed = {"batch_shipyard_tpu/workloads/train_foo.py": (
        "from batch_shipyard_tpu.parallel import train\n"
        "from batch_shipyard_tpu import compilecache\n"
        "def main(args, parser):\n"
        "    compilecache.add_compile_cache_args(parser)\n"
        "    compilecache.enable_from_args(args)\n")}
    assert not _rules_of(blessed, "wiring-compile-cache-optout")


# ------------------------------ sim family -----------------------------

def test_sim_wall_clock_fires_on_time_reads_in_sim():
    """Wall-clock reads inside sim/ break the determinism contract
    (same seed+trace+policy => byte-identical report); every banned
    source form must fire."""
    firing = {"batch_shipyard_tpu/sim/simulator.py": (
        "import time\n"
        "def run():\n"
        "    return time.time()\n")}
    assert len(_rules_of(firing, "sim-wall-clock")) == 1
    mono = {"batch_shipyard_tpu/sim/scenarios.py": (
        "import time\n"
        "def build():\n"
        "    return time.monotonic()\n")}
    assert len(_rules_of(mono, "sim-wall-clock")) == 1
    dt = {"batch_shipyard_tpu/sim/scenarios.py": (
        "import datetime\n"
        "def build():\n"
        "    return datetime.datetime.now()\n")}
    assert len(_rules_of(dt, "sim-wall-clock")) == 1


def test_sim_wall_clock_blessed_shapes_pass():
    """clock.py is the ONE module allowed near wall-clock sources;
    non-sim files are out of scope (the live agent is built on
    time.time()); suppression works like every other rule."""
    clock = {"batch_shipyard_tpu/sim/clock.py": (
        "import time\n"
        "def _debug_now():\n"
        "    return time.time()\n")}
    assert not _rules_of(clock, "sim-wall-clock")
    live = {"batch_shipyard_tpu/agent/mod.py": (
        "import time\n"
        "def heartbeat():\n"
        "    return time.time()\n")}
    assert not _rules_of(live, "sim-wall-clock")
    suppressed_src = {"batch_shipyard_tpu/sim/simulator.py": (
        "import time\n"
        "def run():\n"
        "    return time.time()  "
        "# shipyard-lint: disable=sim-wall-clock\n")}
    active, suppressed = _run(suppressed_src, "sim-wall-clock")
    assert not active and len(suppressed) == 1


# ----------------------------- shell family ----------------------------

def test_shell_strict_mode_fires_without_set_e():
    firing = {"tools/x.sh": "#!/bin/sh\nrm -rf \"$D\"\n"}
    assert len(_rules_of(firing, "shell-strict-mode")) == 1
    blessed = {"tools/x.sh":
               "#!/bin/sh\nset -euo pipefail\nrm -rf \"$D\"\n"}
    assert not _rules_of(blessed, "shell-strict-mode")


def test_shell_unquoted_var_fires_on_path_commands():
    firing = {"tools/x.sh":
              "#!/bin/sh\nset -e\nrm -rf $DIR\n"}
    assert len(_rules_of(firing, "shell-unquoted-var")) == 1
    blessed = {"tools/x.sh": (
        "#!/bin/sh\nset -e\n"
        "rm -rf \"$DIR\"\n"
        "echo \"run: source $VENV/bin/activate\"\n"
        "# rm -rf $COMMENTED\n")}
    assert not _rules_of(blessed, "shell-unquoted-var")


def test_shell_backtick_subst_fires():
    firing = {"tools/x.sh": "#!/bin/sh\nset -e\nTS=`date`\n"}
    assert len(_rules_of(firing, "shell-backtick-subst")) == 1
    blessed = {"tools/x.sh": "#!/bin/sh\nset -e\nTS=$(date)\n"}
    assert not _rules_of(blessed, "shell-backtick-subst")


# ---------------------------- serving family ---------------------------

def test_serving_page_refcount_fires_on_direct_free():
    """Every direct `_free_pages` mutation shape outside the pool's
    release helper fires, in the pool's own module (a method that
    frees without settling) and in the engine reaching through
    `self.pages`: mutating method calls, reassignment, item
    assignment, augassign, and del."""
    firing = {
        "batch_shipyard_tpu/models/kv_pages.py": (
            "class PagePool:\n"
            "    def release(self, slot):\n"
            "        self._free_pages.extend(self._slot_pages[slot])\n"
            "    def reset(self):\n"
            "        self._free_pages = []\n"
            "    def patch(self, k, v):\n"
            "        self._free_pages[k] = v\n"),
        "batch_shipyard_tpu/models/serving.py": (
            "class ContinuousBatcher:\n"
            "    def _preempt(self, pages):\n"
            "        self.pages._free_pages += pages\n"
            "    def _free_slot(self):\n"
            "        del self.pages._free_pages[0]\n")}
    found = _rules_of(firing, "serving-page-refcount")
    assert len(found) == 5, [f.render() for f in found]
    assert "_release_pages" in found[0].message
    assert {f.path for f in found} == set(firing)


def test_serving_page_refcount_blessed_shapes_pass():
    """The allowed owners — __init__ seeding, the allocator popping,
    the release helper returning — plus read-only uses stay silent,
    and so does models/kv_pages.py as committed; module-level
    mutation outside a def still fires."""
    blessed = {"batch_shipyard_tpu/models/kv_pages.py": (
        "class PagePool:\n"
        "    def __init__(self, n):\n"
        "        self._free_pages = list(range(n))\n"
        "    def _alloc_page(self):\n"
        "        return self._free_pages.pop()\n"
        "    def _release_pages(self, pages):\n"
        "        self._free_pages.extend(pages)\n"
        "    def release(self, slot):\n"
        "        self._release_pages(self._slot_pages[slot])\n"
        "    def occupancy(self):\n"
        "        return len(self._free_pages)\n"
        "    def check(self):\n"
        "        return list(self._free_pages)\n")}
    assert not _rules_of(blessed, "serving-page-refcount")
    committed = "batch_shipyard_tpu/models/kv_pages.py"
    source = (core.repo_root() / committed).read_text()
    assert source.count("self._free_pages") >= 4
    assert not _rules_of({committed: source}, "serving-page-refcount")
    module_level = {"batch_shipyard_tpu/models/mod.py": (
        "pool._free_pages.clear()\n")}
    found = _rules_of(module_level, "serving-page-refcount")
    assert len(found) == 1 and "<module>" in found[0].message
    suppressed_src = {"batch_shipyard_tpu/models/mod.py": (
        "class Pool:\n"
        "    def drain(self):\n"
        "        self._free_pages.clear()  "
        "# shipyard-lint: disable=serving-page-refcount\n")}
    active, suppressed = _run(suppressed_src,
                              "serving-page-refcount")
    assert not active and len(suppressed) == 1


def test_serving_drain_no_admit_fires_on_unchecked_admission():
    """Both admission shapes — firing the on_admit hook and
    submitting into the engine — fire when the enclosing function
    never consults the draining flag."""
    firing = {"batch_shipyard_tpu/models/mod.py": (
        "class Front:\n"
        "    def fast_path(self, req):\n"
        "        self.engine.submit(req)\n"
        "    def seat(self, req):\n"
        "        self.on_admit(req.request_id)\n")}
    found = _rules_of(firing, "serving-drain-no-admit")
    assert len(found) == 2, [f.render() for f in found]
    assert "draining" in found[0].message


def test_serving_drain_no_admit_blessed_shapes_pass():
    """An admission path that checks the draining flag (attribute or
    bare name, anywhere in the function body) stays silent; inline
    suppression works; non-admitting engine calls never fire."""
    blessed = {"batch_shipyard_tpu/models/mod.py": (
        "class Front:\n"
        "    def submit(self, req):\n"
        "        if self.draining:\n"
        "            raise RuntimeError('draining')\n"
        "        self.engine.submit(req)\n"
        "    def seat(self, req, draining):\n"
        "        if draining:\n"
        "            return\n"
        "        self.on_admit(req.request_id)\n"
        "    def stats(self):\n"
        "        return self.engine.stats()\n")}
    assert not _rules_of(blessed, "serving-drain-no-admit")
    suppressed_src = {"batch_shipyard_tpu/models/mod.py": (
        "class Front:\n"
        "    def fast_path(self, req):\n"
        "        self.engine.submit(req)  "
        "# shipyard-lint: disable=serving-drain-no-admit\n")}
    active, suppressed = _run(suppressed_src,
                              "serving-drain-no-admit")
    assert not active and len(suppressed) == 1


# ------------------------------ the gate -------------------------------

def test_repo_is_lint_clean():
    """The tier-1 lint gate: every rule over the real tree, judged
    against the checked-in baseline. New findings fail here exactly
    as `shipyard lint` would fail in CI; stale baseline entries fail
    too, so triage debt only shrinks."""
    report = analysis.analyze()
    assert not report.new, "\n".join(
        f.render() for f in report.new)
    assert not report.stale_baseline, (
        f"baseline lists fixed findings "
        f"{report.stale_baseline}; run "
        f"`shipyard lint --baseline-update`")


def test_repo_baseline_is_fully_triaged():
    """Acceptance: the committed baseline is empty — every finding
    the analyzer raised during this PR was fixed or inline-suppressed
    with a justification, not parked."""
    baseline = analysis.load_baseline(
        core.repo_root() / analysis.BASELINE_FILENAME)
    assert sum(baseline.values()) == 0


def test_action_lint_list_rules_and_gate(capsys):
    """The CLI surface: --list-rules inventories every registered
    rule; a plain run over this tree reports clean; the footgun
    combination --rules + --baseline-update is refused (it would
    rewrite the WHOLE baseline from a partial run, deleting every
    other rule's triaged entries)."""
    from batch_shipyard_tpu import fleet
    payload = fleet.action_lint(None, list_rules=True, raw=True)
    assert len(payload["rules"]) == len(analysis.RULES)
    capsys.readouterr()
    payload = fleet.action_lint(None, raw=True)
    assert payload["clean"] is True
    capsys.readouterr()
    with pytest.raises(ValueError):
        fleet.action_lint(None, baseline_update=True,
                          rules=("store-blind-upsert",))


def test_cli_lint_rejects_unknown_rule_as_usage_error():
    """A typo'd --rules id must read as a usage error (exit 2 with
    the flag named), never as lint findings or a raw traceback."""
    from click.testing import CliRunner

    from batch_shipyard_tpu.cli import main as cli_main
    result = CliRunner().invoke(cli_main.cli,
                                ["lint", "--rules", "bogus-rule"])
    assert result.exit_code == 2
    assert "unknown rule" in result.output
    assert "bogus-rule" in result.output


def test_stale_baseline_fails_cli_gate_too(tmp_path, monkeypatch):
    """Gate parity: a stale baseline entry (finding fixed but still
    listed) must flip the CLI's clean verdict exactly like the tier-1
    pytest gate — the operator and CI can never disagree."""
    import json

    from batch_shipyard_tpu import fleet
    fake_root = tmp_path / "repo"
    (fake_root / "batch_shipyard_tpu").mkdir(parents=True)
    (fake_root / "batch_shipyard_tpu" / "ok.py").write_text("x = 1\n")
    (fake_root / analysis.BASELINE_FILENAME).write_text(json.dumps({
        "version": 1,
        "findings": [{"rule": "store-blind-upsert",
                      "path": "batch_shipyard_tpu/gone.py",
                      "message": "fixed long ago"}]}))
    monkeypatch.setattr(analysis, "repo_root", lambda: fake_root)
    payload = fleet.action_lint(None, raw=True)
    assert payload["clean"] is False
    assert payload["stale_baseline"]
