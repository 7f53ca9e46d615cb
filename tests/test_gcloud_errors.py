"""gcloud error-classification corpus: real captured payload shapes
from `gcloud compute tpus tpu-vm create` / queued-resources failures
pinned against the table-driven classifier (VERDICT r1 weak #7: the
classification logic must be table-driven and tested against real
payloads, not ad-hoc substring checks)."""

import pytest

from batch_shipyard_tpu.substrate import gcloud_errors as ge

# (payload, expected kind, expected fatal, expected retry)
CORPUS = [
    # --- quota: CLI text form ---
    ("ERROR: (gcloud.compute.tpus.tpu-vm.create) Could not fetch "
     "resource:\n - Quota exceeded for quota metric 'TPUV5sLitepod"
     "Cores' and limit 'TPUV5sLitepodCoresPerProjectPerZone' of "
     "service 'tpu.googleapis.com' for consumer "
     "'project_number:123456789'.",
     "quota", True, "none"),
    # --- quota: JSON API form ---
    ('{"error": {"code": 429, "message": "Quota exceeded for quota '
     'metric \'TPU v4 cores\'", "status": "RESOURCE_EXHAUSTED", '
     '"details": [{"reason": "RATE_LIMIT_EXCEEDED"}]}}',
     "quota", True, "none"),
    # --- stockout: the classic zone-dry message ---
    ("ERROR: (gcloud.compute.tpus.tpu-vm.create) {\"code\": 8, "
     "\"message\": \"There is no more capacity in the zone "
     "\\\"us-central2-b\\\"; you can try in another zone where "
     "Cloud TPU Nodes are offered\"}",
     "stockout", False, "other_zone"),
    # --- stockout: RESOURCE_EXHAUSTED without quota wording ---
    ('{"error": {"code": 8, "status": "RESOURCE_EXHAUSTED", '
     '"message": "Not enough available capacity for request."}}',
     "stockout", False, "other_zone"),
    # --- stockout: GCE-style resources message ---
    ("ERROR: The zone 'projects/p/zones/us-east1-d' does not have "
     "enough resources available to fulfill the request. Try a "
     "different zone, or try again later.",
     "stockout", False, "other_zone"),
    # --- permission ---
    ("ERROR: (gcloud.compute.tpus.tpu-vm.create) User "
     "[sa@project.iam.gserviceaccount.com] does not have permission "
     "to access projects instance [my-project] (or it may not "
     "exist): Permission 'tpu.nodes.create' denied on "
     "'projects/my-project/locations/us-central2-b'",
     "permission", True, "none"),
    ('{"error": {"code": 401, "message": "Request had insufficient '
     'authentication scopes.", "status": "UNAUTHENTICATED"}}',
     "permission", True, "none"),
    # --- invalid argument ---
    ("ERROR: (gcloud.compute.tpus.tpu-vm.create) INVALID_ARGUMENT: "
     "v5litepod-3 is not a valid accelerator-type for this project "
     "in zone us-central2-b.",
     "invalid_argument", True, "none"),
    ('{"error": {"code": 400, "message": "Invalid value for field '
     "'runtime_version': 'tpu-ubuntu2204-base-nonexistent'.\", "
     '"status": "INVALID_ARGUMENT"}}',
     "invalid_argument", True, "none"),
    # --- conflict (idempotent create race) ---
    ("ERROR: (gcloud.compute.tpus.tpu-vm.create) ALREADY_EXISTS: "
     "Resource 'projects/p/locations/z/nodes/shipyard-pool-s0' "
     "already exists",
     "conflict", False, "none"),
    # --- not found on delete ---
    ("ERROR: (gcloud.compute.tpus.tpu-vm.delete) NOT_FOUND: Resource "
     "'projects/p/locations/z/nodes/shipyard-pool-s0' was not found",
     "not_found", False, "none"),
    # --- transient service errors ---
    ('{"error": {"code": 503, "message": "The service is currently '
     'unavailable.", "status": "UNAVAILABLE"}}',
     "unavailable", False, "backoff"),
    ("ERROR: gcloud crashed (ConnectionError): ('Connection aborted."
     "', ConnectionResetError(104, 'Connection reset by peer'))",
     "unavailable", False, "backoff"),
    ('{"error": {"code": 500, "message": "Internal error encountered'
     '.", "status": "INTERNAL"}}',
     "internal", False, "backoff"),
    ('{"error": {"code": 504, "status": "DEADLINE_EXCEEDED", '
     '"message": "Timed out waiting for operation."}}',
     "unavailable", False, "backoff"),
]


@pytest.mark.parametrize(
    "payload,kind,fatal,retry", CORPUS,
    ids=[f"{row[1]}-{i}" for i, row in enumerate(CORPUS)])
def test_corpus_classification(payload, kind, fatal, retry):
    got = ge.classify(payload)
    assert got.kind == kind, (got, payload[:80])
    assert got.fatal == fatal
    assert got.retry == retry


def test_unknown_payload_defaults_to_retryable():
    got = ge.classify("ERROR: something nobody has seen before")
    assert got.kind == "unknown"
    assert not got.fatal          # never brick a pool on new wording
    assert got.retry == "backoff"


def test_quota_beats_resource_exhausted():
    """A quota error often carries RESOURCE_EXHAUSTED status; the
    quota rule must win (it is fatal, stockout is not)."""
    got = ge.classify(
        '{"status": "RESOURCE_EXHAUSTED", "message": "Quota exceeded '
        "for quota metric 'TPU v5 cores'\"}")
    assert got.kind == "quota"
    assert got.fatal


def test_preemption_states():
    assert ge.is_preemption_state("PREEMPTED")
    assert ge.is_preemption_state("terminated")
    assert ge.is_preemption_state("SUSPENDED")
    assert not ge.is_preemption_state("READY")
    assert not ge.is_preemption_state(None)


def test_substrate_records_classification(tmp_path, monkeypatch):
    """_create_slice failure writes kind/fatal/retry into the pool
    entity (the _block_for_nodes_ready consumer surface)."""
    from batch_shipyard_tpu.config import settings as S
    from batch_shipyard_tpu.state.memory import MemoryStateStore
    from batch_shipyard_tpu.substrate import gcp_tpu

    monkeypatch.setattr(gcp_tpu.shutil, "which",
                        lambda _name: "/usr/bin/gcloud")
    creds = S.credentials_settings({"credentials": {
        "storage": {"backend": "memory"},
        "gcp": {"project": "p", "zone": "us-central2-b"}}})
    store = MemoryStateStore()
    sub = gcp_tpu.GcpTpuSubstrate(store, creds)
    stderr = ("ERROR: There is no more capacity in the zone "
              '"us-central2-b"; you can try in another zone')
    monkeypatch.setattr(
        gcp_tpu.util, "subprocess_capture",
        lambda cmd: (1, "", stderr))
    pool = S.pool_settings({"pool_specification": {
        "id": "errpool", "substrate": "tpu_vm",
        "tpu": {"accelerator_type": "v5litepod-16"}}})
    store.insert_entity("pools", "pools", "errpool", {})
    with pytest.raises(RuntimeError):
        sub.allocate_pool(pool)
    row = store.get_entity("pools", "pools", "errpool")
    assert row["allocation_error_kind"] == "stockout"
    assert row["allocation_error_fatal"] is False
    assert row["allocation_error_retry"] == "other_zone"


def test_manager_fails_fast_on_stockout(tmp_path, monkeypatch):
    """A dry zone (retry=other_zone) must fail the pool wait
    immediately — the zone is fixed by credentials, so waiting out
    max_wait_time_seconds cannot help (review follow-up: the old
    marker list treated stockout as fatal; the classification keeps it
    non-fatal but the manager still fails fast on it)."""
    import time

    from batch_shipyard_tpu.config import settings as S
    from batch_shipyard_tpu.pool import manager as pool_mgr
    from batch_shipyard_tpu.state.memory import MemoryStateStore

    store = MemoryStateStore()
    store.insert_entity("pools", "pools", "drypool", {
        "allocation_error": "no more capacity in the zone",
        "allocation_error_kind": "stockout",
        "allocation_error_fatal": False,
        "allocation_error_retry": "other_zone",
    })
    pool = S.pool_settings({"pool_specification": {
        "id": "drypool", "substrate": "fake",
        "tpu": {"accelerator_type": "v5litepod-16"},
        "max_wait_time_seconds": 300}})
    class _NullSubstrate:
        def list_nodes(self, pool_id):
            return []

        def recreate_slice(self, pool, slice_index):
            raise AssertionError("not expected")

    start = time.monotonic()
    with pytest.raises(pool_mgr.PoolAllocationError) as exc:
        pool_mgr.wait_for_pool_ready(store, _NullSubstrate(), pool,
                                     poll_interval=0.05)
    assert time.monotonic() - start < 10  # not the 300 s timeout
    assert "stockout" in str(exc.value)


def test_bare_resource_exhausted_backs_off():
    """RESOURCE_EXHAUSTED with no capacity wording is GCP's API
    rate-limit shape (HTTP 429); other_zone would abort allocation on
    a transient, so it must back off instead (advisor r2 #1)."""
    got = ge.classify(
        '{"error": {"code": 429, "status": "RESOURCE_EXHAUSTED", '
        '"message": "Too many requests; try again later."}}')
    assert got.kind == "unavailable"
    assert not got.fatal
    assert got.retry == "backoff"


def test_capacity_worded_resource_exhausted_is_stockout():
    got = ge.classify(
        '{"status": "RESOURCE_EXHAUSTED", "message": "There is no '
        'more capacity in the zone \"us-central2-b\"."}')
    assert got.kind == "stockout"
    assert got.retry == "other_zone"


def test_accelerator_not_found_beats_generic_not_found():
    """'Accelerator type X was not found' is a fatal config error;
    the generic 'was not found' rule must not swallow it into a
    non-fatal not_found that polls to timeout (advisor r2 #2)."""
    got = ge.classify(
        "ERROR: (gcloud.compute.tpus.tpu-vm.create) Accelerator type "
        "v5litepod-4 was not found in zone us-east1-d")
    assert got.kind == "invalid_argument"
    assert got.fatal
    assert got.retry == "none"
