"""Shared bootstrap for distributed workload payloads.

Reads the gang env synthesized by jobs/launcher.py (the mpirun-env
analog) and initializes jax.distributed accordingly; single-instance
runs skip initialization. Every recipe payload calls setup() first.
"""

from __future__ import annotations

import os

import jax


def setup() -> dict:
    """Initialize jax.distributed from the SHIPYARD/JAX env contract;
    returns a context dict with process/topology info."""
    instances = int(os.environ.get("SHIPYARD_TASK_INSTANCES", "1"))
    instance = int(os.environ.get("SHIPYARD_TASK_INSTANCE", "0"))
    if instances > 1 and os.environ.get("JAX_COORDINATOR_ADDRESS"):
        # jax.distributed.initialize reads JAX_COORDINATOR_ADDRESS,
        # JAX_NUM_PROCESSES, JAX_PROCESS_ID from the env our launcher
        # synthesized (batch.py:4362 _construct_mpi_command analog).
        jax.distributed.initialize()
    return {
        "instances": instances,
        "instance": instance,
        "process_index": jax.process_index(),
        "process_count": jax.process_count(),
        "local_devices": jax.local_device_count(),
        "global_devices": jax.device_count(),
    }


def device_info() -> dict:
    """What this process runs on, as JAX reports it — every workload
    result names it, so a CPU run is never read as a chip run."""
    devices = jax.devices()
    return {"platform": devices[0].platform,
            "kind": devices[0].device_kind,
            "count": len(devices)}


def device_memory_mib() -> list:
    """bytes_in_use per local device in MiB (None where the backend
    reports no memory stats, as XLA's CPU client does)."""
    out = []
    for device in jax.local_devices():
        stats = device.memory_stats()
        out.append(None if not stats else
                   round(stats["bytes_in_use"] / 2 ** 20, 1))
    return out


def log(ctx: dict, message: str) -> None:
    print(f"[proc {ctx['process_index']}/{ctx['process_count']}] "
          f"{message}", flush=True)
