"""run.py end to end at a tiny size on the CPU for EVERY cell of
BENCHMARK.json, traced and not (--rehearse-tiny; the cases are read
from ``workloads``, so a cell that a later PR adds is rehearsed with no
edit here): the last line is one JSON object with
exactly the contract's keys and no metric at all (a CPU run is never
printed under a device metric's name); without a TPU run.py exits
non-zero and prints no result; so it does where only the benchmark's
own files are present. And one run with the timed path broken
underneath comes out ``correct: false``."""

import json
import os
import shutil
import subprocess
import sys

import pytest

from benchmark import spec

RUN = str(spec.ROOT / "benchmark" / "run.py")
KEYS = {"correct", "attempted", "failed", "metrics", "device", "check"}
WORKLOADS = spec.load_benchmark()["workloads"]
CELLS = [w["name"] for w in WORKLOADS]
# one cell of each configuration, for what is shown once a model
CELL_OF_CONFIG = {w["config"]: w["name"] for w in WORKLOADS}


def _run(args, cwd=spec.ROOT, devices=1, script=RUN):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS=f"--xla_force_host_platform_device_count="
                         f"{devices}")
    env.pop("BENCH_RUN", None)
    env.pop("PYTHONPATH", None)
    return subprocess.run([sys.executable, script, *args], cwd=cwd,
                          env=env, capture_output=True, text=True,
                          timeout=900)


@pytest.mark.parametrize("trace", (0, 1))
@pytest.mark.parametrize("workload", WORKLOADS,
                         ids=lambda w: w["name"])
def test_rehearsal_runs_end_to_end(workload, trace):
    cell, devices = workload["name"], workload["chips"]
    done = _run(["--workload", cell, "--seed", str(2**31 + 77),
                 "--seconds", "3", "--trace", str(trace),
                 "--rehearse-tiny"], devices=devices)
    assert done.returncode == 0, done.stderr[-3000:]
    lines = [l for l in done.stdout.splitlines() if l.strip()]
    result = json.loads(lines[-1])
    assert set(result) == KEYS          # no breakdown off the chip
    assert result["correct"] is True, done.stdout[-3000:]
    assert result["attempted"] > 0 and result["failed"] == 0
    # a CPU run prints no metric, and no device time
    assert result["metrics"] == {}
    assert set(result["device"]) == {"platform", "kind", "count",
                                     "memory_peak_bytes"}
    assert result["device"]["platform"] == "cpu"
    assert result["device"]["count"] == devices
    assert any(l.startswith("check ") and "(limit" in l
               for l in lines)       # each number beside its limit
    # a traced run prints its slice's edges once
    assert sum(l.startswith("traced slice: host ")
               for l in lines) == trace
    # ... as the last key of the result, and the last lines of stderr
    assert list(result)[-1] == "check"
    limits = spec.load_cell(cell).config["rehearse_tiny"]["check"][
        "limits"]
    assert {name: pair["limit"] for name, pair in
            result["check"].items()} == limits
    last = done.stderr.splitlines()[-len(limits):]
    assert all(line.startswith(f"check {name}: ") and "(limit" in line
               for line, name in zip(last, sorted(limits)))


def test_without_a_tpu_it_exits_non_zero_and_prints_no_result():
    done = _run(["--workload", CELLS[0], "--seed", "1",
                 "--seconds", "3", "--trace", "0"])
    assert done.returncode != 0
    assert "no accelerator" in done.stderr
    assert not any(l.startswith("{") for l in done.stdout.splitlines())


def test_with_only_its_own_files_it_exits_non_zero(tmp_path):
    bench = spec.load_benchmark()
    shutil.copy(spec.ROOT / "BENCHMARK.json", tmp_path)
    for path in bench["paths"]:
        shutil.copytree(spec.ROOT / path, tmp_path / path,
                        ignore=shutil.ignore_patterns("__pycache__"))
    done = _run(["--workload", CELLS[0], "--seed", "1",
                 "--seconds", "3", "--trace", "0", "--rehearse-tiny"],
                cwd=tmp_path,
                script=str(tmp_path / "benchmark" / "run.py"))
    assert done.returncode != 0
    assert not any(l.startswith("{") for l in done.stdout.splitlines())


@pytest.mark.parametrize("cell", sorted(CELL_OF_CONFIG.values()))
def test_a_broken_timed_path_comes_out_not_correct(cell, monkeypatch,
                                                   capsys):
    """The rest of a run driven in-process (the look for a chip
    skipped by --rehearse-tiny), with the decode step altering every
    token it produces: one cell of every configuration, so that each
    model's reference is shown to catch it."""
    import importlib.util
    import jax.numpy as jnp
    from batch_shipyard_tpu.models import serving

    sound = serving._decode_step

    def altered(*args, **kwargs):
        # whatever the step takes; of what it gives, the two token
        # results are altered (every token moves to a neighbour, still
        # inside any vocabulary) and any further result is left as it
        # is: a record of decisions that rides in the cache tree, or
        # behind these four, keeps this wrapper whole
        cache, _next, positions, tok, *rest = sound(*args, **kwargs)
        tok = jnp.where(tok > 0, tok - 1, tok + 1)
        return (cache, tok[:, None], positions, tok, *rest)

    monkeypatch.setattr(serving, "_decode_step", altered)
    module_spec = importlib.util.spec_from_file_location("bench_run", RUN)
    run = importlib.util.module_from_spec(module_spec)
    module_spec.loader.exec_module(run)
    code = run.main(["--workload", cell,
                     "--seed", "9", "--seconds", "2", "--trace", "0",
                     "--rehearse-tiny"])
    assert code == 0
    lines = [l for l in capsys.readouterr().out.splitlines()
             if l.strip()]
    result = json.loads(lines[-1])
    assert result["correct"] is False
    assert any("FAILED" in l for l in lines if l.startswith("check "))
