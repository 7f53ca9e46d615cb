"""TPU topology oracle: accelerator type -> pod slice shape.

This is the TPU-native replacement for the reference's Azure vm-size
capability oracles (convoy/settings.py:717 is_gpu_pool, :749
get_gpu_type_from_vm_size, :881 is_sriov_rdma_pool, :964 temp-disk map):
given a Cloud TPU accelerator type string (e.g. ``v5litepod-16``), answer
how many worker VMs the slice has, how many chips each worker hosts, the
ICI mesh shape, and per-chip capability numbers used for scheduling and
for building `jax.sharding.Mesh` axes.

Kept deliberately table-driven so new generations are one-line additions.
"""

from __future__ import annotations

import dataclasses
import math
import re
from typing import Optional


@dataclasses.dataclass(frozen=True)
class TpuGeneration:
    name: str
    chips_per_worker: int
    cores_per_chip: int
    hbm_gib_per_chip: int
    bf16_tflops_per_chip: float
    default_ici_axis: int  # chips per ICI torus axis for default topology


# Per-generation constants (public Cloud TPU documentation values).
_GENERATIONS: dict[str, TpuGeneration] = {
    "v2": TpuGeneration("v2", 4, 2, 8, 45.0, 4),
    "v3": TpuGeneration("v3", 4, 2, 16, 123.0, 4),
    "v4": TpuGeneration("v4", 4, 2, 32, 275.0, 4),
    "v5litepod": TpuGeneration("v5litepod", 4, 1, 16, 197.0, 4),
    "v5p": TpuGeneration("v5p", 4, 2, 95, 459.0, 4),
    "v6e": TpuGeneration("v6e", 4, 1, 32, 918.0, 4),
}

# Aliases accepted in pool configs.
_ALIASES = {
    "v5e": "v5litepod",
    "v5litepod": "v5litepod",
}


@dataclasses.dataclass(frozen=True)
class TpuTopology:
    """Resolved shape of one pod slice."""

    accelerator_type: str
    generation: TpuGeneration
    num_chips: int
    num_workers: int
    chips_per_worker: int
    mesh_shape: tuple[int, ...]  # physical ICI mesh (2D or 3D torus)

    @property
    def num_cores(self) -> int:
        return self.num_chips * self.generation.cores_per_chip

    @property
    def total_hbm_gib(self) -> int:
        return self.num_chips * self.generation.hbm_gib_per_chip

    @property
    def total_bf16_tflops(self) -> float:
        return self.num_chips * self.generation.bf16_tflops_per_chip

    @property
    def is_multi_worker(self) -> bool:
        return self.num_workers > 1


def _parse_topology_string(spec: str) -> tuple[int, ...]:
    parts = spec.lower().split("x")
    try:
        dims = tuple(int(p) for p in parts)
    except ValueError as exc:
        raise ValueError(f"bad topology string {spec!r}") from exc
    if not dims or any(d < 1 for d in dims):
        raise ValueError(f"bad topology string {spec!r}")
    return dims


def _default_mesh_shape(gen: TpuGeneration, num_chips: int) -> tuple[int, ...]:
    """Default physical mesh: square-ish 2D for <=256 chips, 3D for v4/v5p
    large slices (which are 3D tori)."""
    if num_chips == 1:
        return (1, 1)
    if gen.name in ("v4", "v5p") and num_chips >= 64:
        # 3D torus: factor into near-cube of multiples of 4.
        side = round(num_chips ** (1 / 3))
        for x in range(side, 0, -1):
            if num_chips % x:
                continue
            rest = num_chips // x
            y = round(math.sqrt(rest))
            for yy in range(y, 0, -1):
                if rest % yy == 0:
                    return (x, yy, rest // yy)
        return (num_chips, 1, 1)
    # 2D torus: near-square factorization.
    x = int(math.sqrt(num_chips))
    while x > 1 and num_chips % x:
        x -= 1
    return (x, num_chips // x)


def lookup(accelerator_type: str,
           topology: Optional[str] = None) -> TpuTopology:
    """Resolve an accelerator type like ``v5litepod-16``/``v5e-16``/
    ``v4-32`` into a TpuTopology.

    Note Cloud TPU naming: v2/v3/v4/v5p types count *cores* (v4-32 = 16
    chips); v5litepod/v6e count *chips* (v5litepod-16 = 16 chips).
    """
    m = re.fullmatch(r"([a-z0-9]+)-(\d+)", accelerator_type.strip().lower())
    if not m:
        raise ValueError(
            f"unrecognized accelerator type {accelerator_type!r}")
    gen_name, count = _ALIASES.get(m.group(1), m.group(1)), int(m.group(2))
    if count < 1:
        raise ValueError(f"{accelerator_type!r}: count must be >= 1")
    if gen_name not in _GENERATIONS:
        raise ValueError(
            f"unknown TPU generation {m.group(1)!r} in "
            f"{accelerator_type!r}; known: {sorted(_GENERATIONS)}")
    gen = _GENERATIONS[gen_name]
    if gen_name in ("v2", "v3", "v4", "v5p"):
        if count % gen.cores_per_chip:
            raise ValueError(
                f"{accelerator_type}: core count not divisible by "
                f"{gen.cores_per_chip}")
        num_chips = count // gen.cores_per_chip
    else:
        num_chips = count
    if topology is not None:
        mesh_shape = _parse_topology_string(topology)
        if math.prod(mesh_shape) != num_chips:
            raise ValueError(
                f"topology {topology} does not match chip count "
                f"{num_chips} for {accelerator_type}")
    else:
        mesh_shape = _default_mesh_shape(gen, num_chips)
    # Workers host a fixed number of chips; single-chip/partial-host
    # types (e.g. v5litepod-1/-4, v2-8) are one worker.
    if num_chips > gen.chips_per_worker and (
            num_chips % gen.chips_per_worker):
        raise ValueError(
            f"{accelerator_type}: {num_chips} chips is not a multiple of "
            f"{gen.chips_per_worker} chips per worker")
    num_workers = max(1, num_chips // gen.chips_per_worker)
    chips_per_worker = num_chips if num_workers == 1 else gen.chips_per_worker
    return TpuTopology(
        accelerator_type=accelerator_type,
        generation=gen,
        num_chips=num_chips,
        num_workers=num_workers,
        chips_per_worker=chips_per_worker,
        mesh_shape=mesh_shape,
    )


def is_tpu_accelerator(accelerator_type: str) -> bool:
    try:
        lookup(accelerator_type)
        return True
    except ValueError:
        return False


# jax device_kind substrings -> generation key, covering the public
# PJRT device_kind spellings ("TPU v4", "TPU v5 lite", "TPU v5p",
# "TPU v6 lite" / "TPU v6e" aka Trillium). No bare "v5"/"v6"
# catch-all: a spelling this table does not know is an error, not a
# guessed peak.
_DEVICE_KIND_PATTERNS: tuple[tuple[str, str], ...] = (
    ("v5 lite", "v5litepod"),
    ("v5lite", "v5litepod"),
    ("v5e", "v5litepod"),
    ("v5p", "v5p"),
    ("v6 lite", "v6e"),
    ("v6e", "v6e"),
    ("trillium", "v6e"),
    ("v2", "v2"),
    ("v3", "v3"),
    ("v4", "v4"),
)


def generation_for_device_kind(device_kind: str
                               ) -> Optional[TpuGeneration]:
    """Map a jax ``device.device_kind`` string (e.g. ``"TPU v5 lite"``)
    to its generation table entry, or None for non-TPU backends (cpu
    "cpu", gpu device names). A TPU kind the table does not list
    raises: its peak would be a guess. Used by bench MFU accounting to
    pick the peak-FLOPs denominator for whatever chip answered."""
    kind = device_kind.strip().lower()
    if "tpu" not in kind:
        return None
    for pattern, gen_name in _DEVICE_KIND_PATTERNS:
        if pattern in kind:
            return _GENERATIONS[gen_name]
    raise ValueError(
        f"unknown TPU device_kind {device_kind!r}: add it to "
        f"parallel/topology.py with its published peaks")


def peak_bf16_tflops_for_device_kind(device_kind: str
                                     ) -> Optional[float]:
    """Per-chip bf16 peak TFLOP/s for a jax device_kind, or None when
    the backend is not a recognized TPU (MFU is then unreportable)."""
    gen = generation_for_device_kind(device_kind)
    return None if gen is None else gen.bf16_tflops_per_chip
