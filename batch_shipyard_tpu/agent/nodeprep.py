"""Node preparation: make a TPU VM worker ready to run tasks.

Reference analog: scripts/shipyard_nodeprep.sh (2078 lines of bash,
flag-driven, SURVEY.md section 2.2). Re-designed in Python and
TPU-native: instead of nvidia driver + container toolkit install
(nodeprep.sh:773) we verify/install libtpu + JAX; instead of
Infiniband/RDMA setup (:1661) we sanity-check TPU device visibility and
ICI metadata. Docker engine setup is shared capability.

Phases (each emits a perf event, mirroring the reference's perf
instrumentation of nodeprep/docker_install/global_resources):

  1. env probe        — TPU chips present? docker present?
  2. docker setup     — registry logins (config from credentials)
  3. jax/libtpu setup — ensure import works; optional pip install pin
  4. monitors         — node exporter / cadvisor launch (if enabled)
  5. cascade          — pull the pool's global images (lease-gated)

Idempotency marker handling lives in NodeAgent.start (reboot-resume
fast path, reference nodeprep.sh:1935-1970).
"""

from __future__ import annotations

import os
import shutil
import subprocess

from batch_shipyard_tpu.agent import perf
from batch_shipyard_tpu.agent.cascade import CascadeImageProvisioner
from batch_shipyard_tpu.utils import util

logger = util.get_logger(__name__)


def detect_tpu_chips() -> int:
    """Count locally visible TPU accelerator devices from /dev alone
    (no JAX: the agent must never take the chip its tasks need).
    Older TPU VMs expose one /dev/accelN per chip; v5e hosts expose
    none of those, only one numbered VFIO group per chip under
    /dev/vfio (next to the /dev/vfio/vfio control node)."""
    count = sum(os.path.exists(f"/dev/accel{idx}") for idx in range(16))
    if count == 0 and os.path.isdir("/dev/vfio"):
        count = sum(name.isdigit() for name in os.listdir("/dev/vfio"))
    return count


def ensure_jax(jax_version: str | None = None,
               libtpu_version: str | None = None) -> bool:
    """Verify JAX imports; attempt pinned install only if missing and a
    version was requested (no-op offline)."""
    try:
        import jax  # noqa: F401,PLC0415
        return True
    except ImportError:
        pass
    if jax_version:
        spec = f"jax[tpu]=={jax_version}"
        cmd = ["pip", "install", spec]
        if libtpu_version:
            cmd.append(f"libtpu=={libtpu_version}")
        rc = subprocess.call(cmd)
        return rc == 0
    return False


def run_node_prep(agent) -> None:
    """Full node prep for a real (or localhost) node agent."""
    store = agent.store
    pool_id = agent.identity.pool_id
    node_id = agent.identity.node_id
    pool = agent.pool

    perf.emit(store, pool_id, node_id, "nodeprep", "start")
    chips = detect_tpu_chips()
    perf.emit(store, pool_id, node_id, "nodeprep",
              f"tpu_chips:{chips}")

    if "docker" in pool.container_runtimes:
        if shutil.which("docker") is None:
            logger.warning(
                "docker runtime requested but docker not installed on "
                "%s; docker tasks will fail", node_id)
        perf.emit(store, pool_id, node_id, "nodeprep", "docker_install")
    if ("kata_containers" in pool.container_runtimes or
            pool.container_runtime_default == "kata_containers"):
        if shutil.which("kata-runtime") is None:
            logger.warning(
                "kata_containers runtime requested but kata-runtime "
                "not installed on %s; kata tasks will fail", node_id)
        perf.emit(store, pool_id, node_id, "nodeprep", "kata_install")

    if pool.is_tpu_pool:
        ok = ensure_jax(pool.jax_version, pool.libtpu_version)
        perf.emit(store, pool_id, node_id, "nodeprep",
                  f"jax_ready:{ok}")

    for idx, command in enumerate(pool.additional_node_prep_commands):
        rc = subprocess.call(["/bin/bash", "-c", command])
        perf.emit(store, pool_id, node_id, "nodeprep",
                  f"additional_command:{idx}", message=str(rc))
        if rc != 0:
            raise RuntimeError(
                f"additional node prep command {idx} failed rc={rc}")

    if pool.node_exporter.enabled or pool.cadvisor.enabled:
        _launch_monitors(agent)

    # Cascade: prefetch pool images (blocks if pool policy says so).
    provisioner = getattr(agent, "_image_provisioner", None)
    if provisioner is None:
        provisioner = CascadeImageProvisioner(store)
    if isinstance(provisioner, CascadeImageProvisioner) and (
            pool.block_until_all_global_resources_loaded):
        provisioner.distribute_global_resources(agent)

    perf.emit(store, pool_id, node_id, "nodeprep", "end")


def _launch_monitors(agent) -> None:
    """Start prometheus node_exporter / cadvisor if present on PATH
    (reference: nodeprep.sh:1752-1827)."""
    pool = agent.pool
    if pool.node_exporter.enabled and shutil.which("node_exporter"):
        subprocess.Popen(
            ["node_exporter", "--web.listen-address",
             f":{pool.node_exporter.port}"],
            stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL)
    if pool.cadvisor.enabled and shutil.which("cadvisor"):
        subprocess.Popen(
            ["cadvisor", "-port", str(pool.cadvisor.port)],
            stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL)
    perf.emit(agent.store, agent.identity.pool_id,
              agent.identity.node_id, "nodeprep", "monitors_launched")
