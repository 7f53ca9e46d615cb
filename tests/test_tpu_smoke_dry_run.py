"""chip_smoke.py off the chip: it must refuse to produce a result, and
its control flow (the serve and train entry points it drives, the
reference check, the summary and result lines) must still run — at tiny widths, so
an API drift shows up here instead of on a metered chip call."""

import json
import os
import pathlib
import subprocess
import sys

REPO_ROOT = pathlib.Path(__file__).resolve().parent.parent


def _smoke(*flags):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)  # one device, as on the one-chip box
    return subprocess.run(
        [sys.executable, str(REPO_ROOT / "chip_smoke.py"), *flags],
        capture_output=True, text=True, timeout=900, env=env)


def test_refuses_without_a_tpu():
    proc = _smoke()
    assert proc.returncode != 0
    assert "no TPU" in proc.stderr
    assert proc.stdout.strip() == ""


def test_cpu_tiny_dry_run_drives_both_legs():
    proc = _smoke("--cpu-tiny")
    assert proc.returncode == 0, proc.stdout[-2000:] + proc.stderr[-4000:]
    lines = proc.stdout.strip().splitlines()
    # Last line: the driver's contract — these keys and no others.
    device = {"platform": "cpu", "kind": "cpu", "count": 1}
    assert json.loads(lines[-1]) == {"ok": True, "device": device}
    summary = json.loads(lines[-2])
    assert summary["ok"] is True
    assert summary["device"] == device
    assert summary["size"].startswith("tiny")
    assert summary["serve"]["failed"] == 0
    assert summary["serve"]["requests_completed"] >= 8
    assert summary["serve"]["prefix_hit_tokens"] > 0
    assert len(summary["train"]["losses"]) == 4
    assert list(summary)[-1] == "claim" and summary["claim"] is None


_WRONG_DECODE = """
import sys
sys.path.insert(0, {root!r})
import jax.numpy as jnp
import chip_smoke
from batch_shipyard_tpu.ops import paged_attention as pa

right = pa.paged_decode_attention_xla


def misses_newest_keys(q, k_pages, v_pages, block_table, lengths, **kw):
    return right(q, k_pages, v_pages, block_table,
                 jnp.maximum(lengths - 8, 1), **kw)


pa.paged_decode_attention_xla = misses_newest_keys
chip_smoke.serve_leg(chip_smoke.TINY, on_chip=False)
"""


def test_reference_judge_fails_a_wrong_decode_kernel():
    """The serve leg's reference check has teeth: a paged decode that
    runs, returns finite values of the right shape, and merely ignores
    each slot's newest eight keys must fail it."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run(
        [sys.executable, "-c",
         _WRONG_DECODE.format(root=str(REPO_ROOT))],
        capture_output=True, text=True, timeout=900, env=env)
    assert proc.returncode != 0
    assert "below the dense-cache reference's best logit" in proc.stderr
    assert proc.stdout.strip() == ""
