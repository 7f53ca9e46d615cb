"""GCP TPU VM substrate: provisions real Cloud TPU pod slices.

Reference analog: Azure Batch pool allocation (batch.py:921 create_pool
-> service allocates VMs -> start task). Cloud TPU has no hosted task
scheduler, so this substrate provisions slices with ``gcloud compute
tpus tpu-vm`` and bootstraps our node agent on every worker — the agent
then pulls work from the state store exactly like the fake/localhost
substrates.

Allocation model (SURVEY.md section 7 hard parts):
  - one pool = ``num_slices`` queued-resource/TPU-VM creations, each an
    atomic slice of ``accelerator_type``;
  - node recovery = slice recreation (there is no per-worker reboot of
    a slice member that preserves ICI);
  - stockout/quota errors surface in the pool entity for
    _block_for_nodes_ready-style classification (batch.py:661 analog).

Requires the ``gcloud`` CLI and network access; constructing the
substrate without them raises, so the rest of the framework (and all
tests) never touch this path.
"""

from __future__ import annotations

import json
import shutil
import subprocess
import time as _time
from typing import Optional

from batch_shipyard_tpu.config.settings import (
    CredentialsSettings, PoolSettings)
from batch_shipyard_tpu.state import names
from batch_shipyard_tpu.state.base import StateStore
from batch_shipyard_tpu.substrate import base
from batch_shipyard_tpu.utils import util

logger = util.get_logger(__name__)

# Allocation-error classification lives in substrate/gcloud_errors.py — a
# table-driven classifier tested against captured real gcloud payloads
# (the resize error classification of the reference, batch.py:661-672).
from batch_shipyard_tpu.substrate import gcloud_errors  # noqa: E402


class GcpTpuSubstrate(base.ComputeSubstrate):
    def __init__(self, store: StateStore,
                 credentials: CredentialsSettings,
                 bootstrap_bundle_key: Optional[str] = None) -> None:
        if shutil.which("gcloud") is None:
            raise RuntimeError(
                "gcloud CLI is required for the tpu_vm substrate; use "
                "substrate: fake or localhost without it")
        if credentials.gcp is None:
            raise ValueError(
                "credentials.gcp is required for the tpu_vm substrate")
        self.store = store
        self.credentials = credentials
        self.project = credentials.gcp.project
        self.zone = credentials.gcp.zone
        self.bootstrap_bundle_key = bootstrap_bundle_key

    # ------------------------------ gcloud -----------------------------

    def _gcloud(self, *args: str, parse_json: bool = False,
                zone: Optional[str] = None):
        cmd = ["gcloud", "compute", "tpus", "tpu-vm", *args,
               f"--project={self.project}"]
        zone = zone or self.zone
        if zone:
            cmd.append(f"--zone={zone}")
        if parse_json:
            cmd.append("--format=json")
        rc, out, err = util.subprocess_capture(cmd)
        if rc != 0:
            raise RuntimeError(f"gcloud failed ({rc}): {err.strip()}")
        return json.loads(out) if parse_json else out

    @staticmethod
    def slice_name(pool_id: str, slice_index: int) -> str:
        return f"shipyard-{pool_id}-s{slice_index}"

    # ---------------------------- interface ----------------------------

    def allocate_pool(self, pool: PoolSettings) -> None:
        assert pool.tpu is not None, "tpu_vm substrate requires tpu block"
        for s in range(pool.tpu.num_slices):
            self._create_slice(pool, s)

    def _create_slice(self, pool: PoolSettings, slice_index: int) -> None:
        tpu = pool.tpu
        name = self.slice_name(pool.id, slice_index)
        args = ["create", name,
                f"--accelerator-type={tpu.accelerator_type}",
                f"--version={tpu.runtime_version}"]
        if tpu.provisioning_model == "spot":
            args.append("--spot")
        elif tpu.provisioning_model == "reserved":
            args.append(f"--reserved")
            if tpu.reservation_name:
                args.append(f"--reservation={tpu.reservation_name}")
        if tpu.network:
            args.append(f"--network={tpu.network}")
        if tpu.subnetwork:
            args.append(f"--subnetwork={tpu.subnetwork}")
        try:
            self._gcloud(*args, zone=pool.zone)
        except RuntimeError as exc:
            err = gcloud_errors.classify(str(exc))
            record = {
                "allocation_error": str(exc),
                "allocation_error_kind": err.kind,
                "allocation_error_fatal": err.fatal,
                "allocation_error_retry": err.retry}
            if err.retry == "other_zone":
                advisory = self._stockout_advisory(pool)
                if advisory:
                    record["allocation_error_advisory"] = advisory
            self.store.merge_entity(
                names.TABLE_POOLS, "pools", pool.id, record)
            raise
        self._register_workers(pool, slice_index)
        self._bootstrap_agents(pool, slice_index)

    def _stockout_advisory(self, pool: PoolSettings) -> Optional[str]:
        """On stockout, name sibling zones still offering the type
        (substrate/quota.py; advisory only — never raises).
        ``quota_client`` attribute injects a fake for tests."""
        try:
            from batch_shipyard_tpu.substrate import quota as quota_mod
            client = getattr(self, "quota_client", None)
            if client is None:
                client = quota_mod.TpuQuotaClient(self.project)
            failed_zone = pool.zone or self.zone or ""
            region = quota_mod._zone_region(failed_zone)
            candidates = [f"{region}-{s}" for s in "abcdef"]
            return quota_mod.stockout_advisory(
                client, pool.tpu.accelerator_type, failed_zone,
                candidates)
        except Exception:  # noqa: BLE001 - advisory only
            return None

    def _register_workers(self, pool: PoolSettings,
                          slice_index: int) -> None:
        name = self.slice_name(pool.id, slice_index)
        desc = self._gcloud("describe", name, parse_json=True,
                            zone=pool.zone)
        endpoints = desc.get("networkEndpoints", [])
        workers = pool.tpu.workers_per_slice
        for w, endpoint in enumerate(endpoints[:workers]):
            node_id = f"{pool.id}-s{slice_index}-w{w}"
            self.store.upsert_entity(
                names.TABLE_NODES, pool.id, node_id, {
                    "state": "creating",
                    "hostname": f"{name}-w{w}",
                    "internal_ip": endpoint.get("ipAddress", ""),
                    "external_ip": endpoint.get(
                        "accessConfig", {}).get("externalIp", ""),
                    "node_index": slice_index * workers + w,
                    "slice_index": slice_index, "worker_index": w,
                    "tpu_name": name, "zone": pool.zone or self.zone,
                    "registered_at": _time.time()})

    def _bootstrap_agents(self, pool: PoolSettings,
                          slice_index: int) -> None:
        """Install + systemd-launch the node agent on every worker via
        ``gcloud ... ssh --worker=all`` (the start-task analog,
        fleet.py:1317-1437)."""
        name = self.slice_name(pool.id, slice_index)
        storage = self.credentials.storage
        workers = pool.tpu.workers_per_slice
        script = _bootstrap_script(
            pool, storage_backend=storage.backend,
            storage_bucket=storage.bucket or "",
            storage_prefix=storage.prefix,
            slice_index=slice_index, workers=workers,
            bundle_key=self.bootstrap_bundle_key or "")
        self._gcloud("ssh", name, "--worker=all",
                     f"--command={script}", zone=pool.zone)

    def deallocate_pool(self, pool_id: str) -> None:
        rows = list(self.store.query_entities(
            names.TABLE_NODES, partition_key=pool_id))
        slices = sorted({(row.get("tpu_name"), row.get("zone"))
                         for row in rows if row.get("tpu_name")})
        for name, zone in slices:
            try:
                self._gcloud("delete", name, "--quiet", zone=zone)
            except RuntimeError:
                logger.exception("failed deleting %s", name)
        for row in rows:
            self.store.delete_entity(
                names.TABLE_NODES, pool_id, row["_rk"])

    def resize_pool(self, pool: PoolSettings, num_slices: int) -> None:
        current = sorted({
            int(row["slice_index"]) for row in self.store.query_entities(
                names.TABLE_NODES, partition_key=pool.id)})
        have = len(current)
        if num_slices > have:
            for s in range(have, num_slices):
                self._create_slice(pool, s)
        else:
            for s in current[num_slices:]:
                self._delete_slice(pool.id, s)

    def _delete_slice(self, pool_id: str, slice_index: int) -> None:
        name = self.slice_name(pool_id, slice_index)
        zone = None
        for row in self.store.query_entities(
                names.TABLE_NODES, partition_key=pool_id):
            if int(row.get("slice_index", -1)) == slice_index:
                zone = row.get("zone")
                break
        self._gcloud("delete", name, "--quiet", zone=zone)
        for row in list(self.store.query_entities(
                names.TABLE_NODES, partition_key=pool_id)):
            if int(row.get("slice_index", -1)) == slice_index:
                self.store.delete_entity(
                    names.TABLE_NODES, pool_id, row["_rk"])

    def recreate_slice(self, pool: PoolSettings, slice_index: int) -> None:
        try:
            self._delete_slice(pool.id, slice_index)
        except RuntimeError:
            logger.warning("delete of slice %d failed; recreating anyway",
                           slice_index)
        self._create_slice(pool, slice_index)

    def deallocate_slice(self, pool: PoolSettings,
                         slice_index: int) -> None:
        self._delete_slice(pool.id, slice_index)

    def refresh_node_states(self, pool: PoolSettings) -> None:
        """Poll slice states and mark nodes of reclaimed slices
        'preempted' (gcloud_errors.is_preemption_state) — the
        $PreemptedNodeCount sample feeding autoscale
        rebalance_preemption_percentage and slice-recreate recovery.
        Called by the autoscale tick; cost is one describe per
        slice."""
        rows_by_slice: dict[int, list[dict]] = {}
        for row in self.store.query_entities(
                names.TABLE_NODES, partition_key=pool.id):
            rows_by_slice.setdefault(
                int(row.get("slice_index", -1)), []).append(row)
        for s in range(pool.tpu.num_slices if pool.tpu else 0):
            name = self.slice_name(pool.id, s)
            try:
                desc = self._gcloud("describe", name, parse_json=True,
                                    zone=pool.zone)
                state = desc.get("state")
            except RuntimeError as exc:
                if "not found" in str(exc).lower():
                    # Slice resource is gone: reclaimed.
                    state = "TERMINATED"
                else:
                    # Transient describe failure (network/API/auth) is
                    # NOT evidence of preemption — marking healthy
                    # nodes preempted would empty the pool's
                    # schedulable set on a blip.
                    logger.warning(
                        "describe of %s failed (%s); skipping "
                        "preemption check this tick", name, exc)
                    continue
            if not gcloud_errors.is_preemption_state(state):
                continue
            for row in rows_by_slice.get(s, []):
                if row.get("state") != "preempted":
                    logger.warning(
                        "slice %s is %s; marking node %s preempted",
                        name, state, row["_rk"])
                    self.store.merge_entity(
                        names.TABLE_NODES, pool.id, row["_rk"],
                        {"state": "preempted"})

    def suspend_pool(self, pool: PoolSettings) -> None:
        """gcloud tpu-vm stop on every slice (billing pause)."""
        for s in range(pool.tpu.num_slices):
            self._gcloud("stop", self.slice_name(pool.id, s),
                         zone=pool.zone)
        for row in list(self.store.query_entities(
                names.TABLE_NODES, partition_key=pool.id)):
            self.store.merge_entity(names.TABLE_NODES, pool.id,
                                    row["_rk"], {"state": "suspended"})

    def start_pool(self, pool: PoolSettings) -> None:
        for s in range(pool.tpu.num_slices):
            self._gcloud("start", self.slice_name(pool.id, s),
                         zone=pool.zone)
            self._bootstrap_agents(pool, s)

    def get_remote_login(self, pool_id: str,
                         node_id: str) -> Optional[tuple[str, int]]:
        try:
            row = self.store.get_entity(names.TABLE_NODES, pool_id,
                                        node_id)
        except KeyError:
            return None
        ip = row.get("external_ip") or row.get("internal_ip")
        return (ip, 22) if ip else None


def _bootstrap_script(pool: PoolSettings, storage_backend: str,
                      storage_bucket: str, storage_prefix: str,
                      slice_index: int, workers: int,
                      bundle_key: str) -> str:
    """Shell one-liner run on each worker to start the node agent.

    The boot template travels base64-encoded (no quoting hazards); a
    tiny remote python fills in the per-worker identity from
    TPU_WORKER_ID and hostname.
    """
    import base64
    template = {
        "storage": {"backend": storage_backend,
                    "bucket": storage_bucket,
                    "prefix": storage_prefix},
        "pool_config": {"pool_specification": {
            "id": pool.id,
            "substrate": "tpu_vm",
            "tpu": {
                "accelerator_type": pool.tpu.accelerator_type,
                "num_slices": pool.tpu.num_slices,
            },
            "task_slots_per_node": pool.task_slots_per_node,
            # Agents poll the queue fan-out; the shard count MUST
            # match what producers read from the stored pool spec or
            # messages on shards > 0 are never consumed.
            "task_queue_shards": pool.task_queue_shards,
        }},
        "identity": {
            "pool_id": pool.id,
            "node_id": f"{pool.id}-s{slice_index}-wWORKER",
            "node_index": slice_index * workers,  # + worker id remotely
            "hostname": "", "internal_ip": "",
            "slice_index": slice_index, "worker_index": 0,
        },
        "work_dir": "/var/shipyard",
        "run_nodeprep": True,
        "output_upload_cap_bytes": (
            pool.output_upload_cap_mb * 1024 * 1024
            if pool.output_upload_cap_mb else None),
    }
    b64 = base64.b64encode(json.dumps(template).encode()).decode()
    fill_py = (
        'import json,os,socket;'
        't=json.load(open("/tmp/shipyard_boot_t.json"));'
        'w=int(os.environ.get("TPU_WORKER_ID","0"));'
        'i=t["identity"];'
        'i["node_id"]=i["node_id"].replace("WORKER",str(w));'
        'i["worker_index"]=w;i["node_index"]=i["node_index"]+w;'
        'i["hostname"]=socket.gethostname();'
        'i["internal_ip"]=socket.gethostbyname(socket.gethostname());'
        'json.dump(t,open("/tmp/shipyard_boot.json","w"))')
    lines = [
        "sudo mkdir -p /var/shipyard",
        "sudo chmod 777 /var/shipyard",
        f"echo {b64} | base64 -d > /tmp/shipyard_boot_t.json",
        f"python3 -c '{fill_py}'",
        # Fetch the framework bundle from the state bucket if provided.
        (f"gsutil cp gs://{storage_bucket}/{bundle_key} /tmp/bst.tar.gz "
         "&& sudo tar xzf /tmp/bst.tar.gz -C /opt" if bundle_key else
         "true"),
        "sudo sh -c 'nohup python3 -m batch_shipyard_tpu.agent "
        "/tmp/shipyard_boot.json >/var/shipyard/agent.log 2>&1 &'",
    ]
    return " && ".join(lines)
