"""Where the persistent compile cache lives — the contract
(docs/29-compile-cache.md), checked in fresh processes because JAX
reads JAX_COMPILATION_CACHE_DIR once, at import:

  * placed from outside (the variable set): the library hook and the
    serve/train entry points leave ``jax_compilation_cache_dir`` at
    exactly that directory, never write it, and entries land there;
  * not placed: one fixed, git-ignored directory inside the checkout,
    the same in every process.
"""

import json
import os
import pathlib
import subprocess
import sys

REPO_ROOT = pathlib.Path(__file__).resolve().parent.parent

# Records every jax.config.update of the cache dir, then drives the
# library hook and both entry points at tiny widths.
_CHILD = r"""
import json, os, sys
sys.path.insert(0, os.environ["REPO_ROOT"])
import jax
dir_updates = []
_update = jax.config.update
def recording_update(name, value):
    if name == "jax_compilation_cache_dir":
        dir_updates.append(value)
    return _update(name, value)
jax.config.update = recording_update

import argparse
from batch_shipyard_tpu import compilecache
parser = argparse.ArgumentParser()
compilecache.add_compile_cache_args(parser)
mgr = compilecache.enable_from_args(parser.parse_args([]))
dirs = [mgr.cache_dir]

if os.environ.get("DRIVE_ENTRY_POINTS"):
    from batch_shipyard_tpu.workloads import serve, train_transformer
    tiny = ["--d-model", "32", "--n-layers", "1", "--n-heads", "2",
            "--d-ff", "64", "--vocab", "97"]
    assert train_transformer.main(
        tiny + ["--seq-len", "16", "--batch", "8", "--steps", "1",
                "--warmup", "1"]) == 0
    dirs.append(compilecache.current().cache_dir)
    assert serve.main(
        tiny + ["--num-slots", "2", "--max-decode-len", "32",
                "--kv-page-size", "8", "--loadgen", "2", "--rate",
                "50", "--prompt-len", "2", "4", "--gen-tokens", "2",
                "3", "--port", "0", "--report",
                os.environ["REPORT"]]) == 0
    dirs.append(compilecache.current().cache_dir)
print(json.dumps({
    "tracked_dirs": dirs,
    "jax_config_dir": jax.config.jax_compilation_cache_dir,
    "dir_updates": dir_updates,
}))
"""


def _run_child(tmp_path, **env):
    full_env = {k: v for k, v in os.environ.items()
                if k not in ("JAX_COMPILATION_CACHE_DIR",
                             "SHIPYARD_COMPILE_CACHE_DIR")}
    full_env.update(JAX_PLATFORMS="cpu", REPO_ROOT=str(REPO_ROOT),
                    REPORT=str(tmp_path / "report.json"), **env)
    proc = subprocess.run(
        [sys.executable, "-c", _CHILD], capture_output=True, text=True,
        timeout=600, env=full_env, cwd=str(tmp_path))
    assert proc.returncode == 0, proc.stdout[-2000:] + proc.stderr[-4000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


def test_cache_placed_from_outside_is_never_moved(tmp_path):
    placed = tmp_path / "placed-cache"
    out = _run_child(tmp_path, JAX_COMPILATION_CACHE_DIR=str(placed),
                     DRIVE_ENTRY_POINTS="1")
    assert out["dir_updates"] == []
    assert out["jax_config_dir"] == str(placed)
    assert out["tracked_dirs"] == [str(placed)] * 3
    entries = [p for p in placed.iterdir()
               if p.name not in ("identity.json", "cache_meta.json")
               and not p.name.endswith("-atime")]
    assert entries, "no compile-cache entry landed in the placed dir"
    # Flat: nothing was namespaced underneath it.
    assert not [p for p in placed.iterdir() if p.is_dir()]


def test_unplaced_cache_is_one_fixed_path_in_the_checkout(tmp_path):
    from batch_shipyard_tpu.compilecache import manager
    first = _run_child(tmp_path)
    second = _run_child(tmp_path)
    assert first["tracked_dirs"] == second["tracked_dirs"]
    (tracked,) = first["tracked_dirs"]
    assert first["jax_config_dir"] == tracked
    root = pathlib.Path(manager.DEFAULT_CACHE_ROOT)
    assert root == REPO_ROOT / ".jax_compile_cache"
    assert pathlib.Path(tracked).parent == root
    ignored = (REPO_ROOT / ".gitignore").read_text().split()
    assert ".jax_compile_cache/" in ignored


def test_no_cache_path_is_built_from_a_temporary_name():
    """No mkdtemp/pid/time anywhere a compile-cache path is made."""
    import re
    sources = [
        REPO_ROOT / "batch_shipyard_tpu/compilecache/manager.py",
        REPO_ROOT / "batch_shipyard_tpu/substrate/localhost.py",
        REPO_ROOT / "chip_smoke.py",
    ]
    for path in sources:
        text = path.read_text()
        assert not re.search(r"mkdtemp|getpid|TemporaryDirectory",
                             text), path
