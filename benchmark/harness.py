"""What every driver shares: the run's context, the look for the
chip, the compile cache's fixed place and its entry count, the peak
device memory, and the profiler slice with its reduction."""

from __future__ import annotations

import dataclasses
import os
import pathlib
import shutil
import time
from typing import Optional

from benchmark import peaks, spec, tracered

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
OUT_DIR = ".bench_out"          # spans, profiler traces, loadgen files


class NoChip(RuntimeError):
    pass


@dataclasses.dataclass
class RunContext:
    cell: spec.Cell
    seed: int
    seconds: float
    trace: bool
    tiny: bool                  # --rehearse-tiny: CPU control flow only
    root: pathlib.Path
    started: float              # time.monotonic() at process start
    out_dir: pathlib.Path = None
    devices: list = None
    peaks: Optional[dict] = None
    details: list = dataclasses.field(default_factory=list)

    def note(self, line: str) -> None:
        """A line of the run's printed account (numbers compared and
        their limits, counts, set-up split)."""
        self.details.append(line)
        print(line, flush=True)


def merged(data: dict, tiny: bool) -> dict:
    """A configuration or traffic file as it is run: with --rehearse-
    tiny its ``rehearse_tiny`` section is laid over it."""
    out = {k: v for k, v in data.items() if k != "rehearse_tiny"}
    if tiny:
        for key, value in data.get("rehearse_tiny", {}).items():
            if isinstance(value, dict) and isinstance(out.get(key),
                                                      dict):
                out[key] = {**out[key], **value}
            else:
                out[key] = value
    return out


def place_compile_cache(root: pathlib.Path) -> str:
    """Before jax is imported: the persistent compile cache stays where
    JAX_COMPILATION_CACHE_DIR says, else at the fixed
    .jax_compile_cache/ of the checkout. Every program is cached,
    however small or quick, so that a second run compiles nothing."""
    path = os.environ.setdefault(
        CACHE_ENV, str(root / ".jax_compile_cache"))
    os.makedirs(path, exist_ok=True)
    os.environ.setdefault(
        "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0")
    os.environ.setdefault(
        "JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES", "-1")
    return path


def cache_entries() -> int:
    path = os.environ[CACHE_ENV]
    return sum(1 for name in os.listdir(path)
               if not name.endswith(("-atime", ".tmp")))


def find_devices(ctx: RunContext) -> None:
    """The cell's chips, or NoChip: never a fallback to the CPU. With
    --rehearse-tiny any backend passes and no peak is known."""
    import jax
    devices = jax.devices()
    platform = devices[0].platform
    if ctx.tiny:
        if len(devices) < ctx.cell.chips:
            raise NoChip(f"rehearsal needs {ctx.cell.chips} devices, "
                         f"jax has {len(devices)}")
        ctx.devices = devices[:ctx.cell.chips]
        return
    if platform != "tpu":
        raise NoChip(f"jax found no accelerator: platform {platform!r}")
    if len(devices) < ctx.cell.chips:
        raise NoChip(f"cell {ctx.cell.name} needs {ctx.cell.chips} "
                     f"chips, jax has {len(devices)}")
    ctx.peaks = peaks.for_device_kind(devices[0].device_kind)
    ctx.devices = devices[:ctx.cell.chips]


def device_report(ctx: RunContext, memory_peak_bytes: int) -> dict:
    first = ctx.devices[0]
    return {"platform": first.platform, "kind": first.device_kind,
            "count": len(ctx.devices),
            "memory_peak_bytes": int(memory_peak_bytes)}


def memory_peak_bytes(ctx: RunContext) -> int:
    """Peak bytes in use on the fullest chip (0 where the backend does
    not say, as on the CPU)."""
    peak = 0
    for device in ctx.devices:
        stats = device.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return peak


def fresh_out_dir(root: pathlib.Path) -> pathlib.Path:
    out = root / OUT_DIR
    shutil.rmtree(out, ignore_errors=True)
    out.mkdir(parents=True)
    return out


class ProfilerSlice:
    """jax.profiler around a steady slice of the window (traced runs
    only), reduced with tracered once the window has closed. The
    slice is marked in the trace by an annotation of its own, so that
    the host's two stamps can be found on the trace's clock."""

    def __init__(self, ctx: RunContext) -> None:
        self.ctx = ctx
        self.dir = str(ctx.out_dir / "profile")
        self.started: Optional[float] = None
        self.stopped: Optional[float] = None

    def run_between(self, begin: float, end: float) -> None:
        """Sleep until ``begin`` (monotonic), trace until ``end``.
        Both stamps are taken with tracing on, inside the mark."""
        import jax
        time.sleep(max(0.0, begin - time.monotonic()))
        jax.profiler.start_trace(self.dir)
        with jax.profiler.TraceAnnotation(
                tracered.HOST_SPAN_PREFIX + tracered.SLICE_MARK):
            self.started = time.monotonic()
            time.sleep(max(0.0, end - time.monotonic()))
            self.stopped = time.monotonic()
        jax.profiler.stop_trace()

    def reduce(self) -> Optional[dict]:
        path = tracered.newest_xplane(self.dir)
        if path is None:
            return None
        profile = tracered.reduce_slice(
            tracered.from_xplane(path), self.stopped - self.started)
        self.ctx.note(tracered.describe_slice(profile))
        return profile
