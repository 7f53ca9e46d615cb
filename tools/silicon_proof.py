#!/usr/bin/env python3
"""One-shot on-chip proof pipeline.

Runs every on-chip proof in ONE unattended pass. This parent process
never touches JAX: each phase runs in a child of its own, one at a
time, so each child has the chip to itself. Phases:

  1. kernel_checks  — tools/tpu_checks.py: every Pallas kernel (flash
                      fwd/bwd, flash-ring, paged attention, int8,
                      fused norm, chunked cross-entropy) vs its oracle
                      ON THE CHIP.
  2. ring_collectives — async-DMA ring collective kernels
                      (ops/ring_collectives.py): bandwidth per message
                      size vs the lax collectives plus numeric parity,
                      remote-DMA ring when >1 chip answers, the
                      virtual-ring kernels on a single chip.
  3. tuning_ab      — bench.py --quick per parallel/tuning.py profile
                      (fresh subprocess each: XLA_FLAGS are read at
                      backend init); winner by throughput geomean,
                      handed to the final bench of THIS run only.
  4. final_bench    — full bench.py under the winning profile; the
                      one-line JSON lands in BENCH_LATEST.json and
                      BENCH_DETAILS.json carries explicit per-workload
                      MFU%% (parallel/mfu.py).
  5. serving_speculative — speculative continuous-batching serving
                      (dense + paged KV): tokens/s, TTFT/TPOT, and
                      the measured draft acceptance rate per variant.
  6. checkpoint_overhead — zero-stall checkpointing proof: blocking
                      ms/save of the sync full-durability save vs the
                      async double-buffered pipeline on a synthetic
                      large pytree (workloads/checkpoint.py).
  7. goodput        — ML-productivity goodput decomposition of the
                      bench pool's event log (goodput/accounting.py):
                      goodput_ratio plus badput seconds per category,
                      persisted as GOODPUT_REPORT.json.
  8. compile_warm   — warm-start compilation proof: first vs second
                      process time-to-first-step through the
                      persistent compile cache for the transformer
                      train step, plus the AOT-precompile first-step
                      spike check (batch_shipyard_tpu/compilecache/).
  9. chaos_drill    — self-healing proof: a seeded fault schedule
                      (wedge, mid-run kill, node preemption,
                      heartbeat blackout, store faults) replayed
                      against a fakepod pool via tools/chaos_drill.py
                      with every recovery invariant asserted (all
                      tasks complete exactly once, no orphaned
                      coordination state, goodput partition exact).

Every phase's outcome is recorded in SILICON_PROOF.json; --dry-run
writes the complete report skeleton on CPU (each phase records the
exact command it would run) so the pipeline itself is CI-testable.
"""

from __future__ import annotations

import argparse
import json
import os
import pathlib
import subprocess
import sys
import time

REPO_ROOT = pathlib.Path(__file__).resolve().parent.parent
if str(REPO_ROOT) not in sys.path:
    sys.path.insert(0, str(REPO_ROOT))

CHECKS_TIMEOUT = 1800
BENCH_QUICK_TIMEOUT = 1800
BENCH_FULL_TIMEOUT = 2400


def _run(cmd: list[str], timeout: int, env: dict | None = None,
         log_path: pathlib.Path | None = None) -> tuple[int, str]:
    """Run a child with a hard timeout, capturing combined output."""
    full_env = dict(os.environ)
    if env:
        full_env.update(env)
    try:
        proc = subprocess.run(
            cmd, cwd=str(REPO_ROOT), env=full_env,
            capture_output=True, timeout=timeout, text=True)
        out = proc.stdout + proc.stderr
        rc = proc.returncode
    except subprocess.TimeoutExpired as exc:
        out = ((exc.stdout or b"").decode(errors="replace")
               if isinstance(exc.stdout, bytes) else (exc.stdout or "")
               ) + f"\nTIMEOUT after {timeout}s"
        rc = 124
    if log_path is not None:
        log_path.write_text(out, encoding="utf-8")
    return rc, out


class Pipeline:
    def __init__(self, out_dir: pathlib.Path, dry_run: bool,
                 skip_tuning: bool):
        self.out = out_dir
        self.dry = dry_run
        self.skip_tuning = skip_tuning
        self.phases: list[dict] = []

    def record(self, name: str, status: str, **extra) -> dict:
        entry = {"phase": name, "status": status, **extra}
        self.phases.append(entry)
        print(f"[silicon-proof] {name}: {status} "
              + json.dumps({k: v for k, v in extra.items()
                            if k != "output_tail"}))
        return entry

    # -- phases ----------------------------------------------------
    def kernel_checks(self) -> None:
        results_path = self.out / "TPU_CHECKS.json"
        cmd = [sys.executable, "tools/tpu_checks.py",
               "--json-out", str(results_path)]
        if self.dry:
            self.record("kernel_checks", "dry_run",
                        command=" ".join(cmd))
            return
        rc, out = _run(cmd, CHECKS_TIMEOUT,
                       log_path=self.out / "TPU_CHECKS.txt")
        try:
            with open(results_path, encoding="utf-8") as fh:
                results = json.load(fh)
        except (OSError, ValueError):
            results = {}
        self.record(
            "kernel_checks", "ok" if rc == 0 else "partial",
            rc=rc, results={k: v.get("ok") for k, v in
                            results.items()},
            output_tail=out[-2000:])

    def ring_collectives(self) -> None:
        """Async-DMA ring collective kernels
        (ops/ring_collectives.py) via bench.py's ring_collectives
        workload: per-size bandwidth rows plus a numeric parity flag
        against the lax collectives. Runs the remote-DMA shard_map
        ring when more than one chip answers, the virtual-ring
        kernels (same Mosaic DMA/semaphore lowering, no ICI) on a
        single chip — `mode` records which. The dry-run skeleton
        names every metric and carries the explicit not-measured
        marker tools/benchgen.py renders."""
        details_path = self.out / "RING_COLLECTIVES_DETAILS.json"
        cmd = [sys.executable, "bench.py", "--workloads",
               "ring_collectives", "--details-out",
               str(details_path)]
        metric_keys = ("mode", "ring", "chips", "numeric_ok",
                       "best_all_gather_gbps",
                       "best_reduce_scatter_gbps")
        if self.dry:
            self.record(
                "ring_collectives", "dry_run",
                command=" ".join(cmd),
                note="not measured — dry-run skeleton",
                metrics={k: None for k in metric_keys})
            return
        rc, out = _run(cmd, BENCH_QUICK_TIMEOUT)
        try:
            with open(details_path, encoding="utf-8") as fh:
                det = json.load(fh)
        except (OSError, ValueError):
            det = {}
        rep = det.get("ring_collectives") or {}
        if "error" in rep:
            summary = {"error": rep["error"]}
        else:
            summary = {k: rep.get(k) for k in metric_keys}
        ok = (rc == 0 and "error" not in summary
              and summary.get("numeric_ok") is True)
        self.record("ring_collectives", "ok" if ok else "failed",
                    rc=rc, metrics=summary, output_tail=out[-800:])

    def tuning_ab(self) -> str | None:
        from batch_shipyard_tpu.parallel.tuning import PROFILES
        plan = {
            profile: (f"SHIPYARD_XLA_TUNING={profile} {sys.executable}"
                      f" bench.py --quick --workloads "
                      f"resnet,transformer --details-out "
                      f"{self.out}/tuning_{profile}.json")
            for profile in PROFILES
        }
        if self.dry or self.skip_tuning:
            self.record("tuning_ab",
                        "dry_run" if self.dry else "skipped",
                        plan=plan)
            return None
        measurements: dict = {}
        for profile in PROFILES:
            details_path = self.out / f"tuning_{profile}.json"
            rc, out = _run(
                [sys.executable, "bench.py", "--quick", "--workloads",
                 "resnet,transformer", "--details-out",
                 str(details_path)],
                BENCH_QUICK_TIMEOUT,
                env={"SHIPYARD_XLA_TUNING": profile})
            entry: dict = {"rc": rc}
            try:
                with open(details_path, encoding="utf-8") as fh:
                    det = json.load(fh)
                entry["resnet_img_s"] = det.get("resnet50", {}).get(
                    "images_per_sec_per_chip")
                entry["transformer_tok_s"] = det.get(
                    "transformer", {}).get("tokens_per_sec_per_chip")
            except (OSError, ValueError):
                entry["error"] = out[-400:]
            measurements[profile] = entry

        def score(m: dict) -> float:
            r = m.get("resnet_img_s") or 0.0
            t = m.get("transformer_tok_s") or 0.0
            return (r * t) ** 0.5 if r and t else max(r, t)

        winner = max(measurements, key=lambda p:
                     score(measurements[p]))
        if score(measurements[winner]) <= 0:
            self.record("tuning_ab", "failed",
                        measurements=measurements)
            return None
        self.record("tuning_ab", "ok", winner=winner,
                    measurements=measurements)
        return winner

    def final_bench(self, winner: str | None) -> None:
        env = {"SHIPYARD_XLA_TUNING": winner} if winner else None
        cmd = [sys.executable, "bench.py", "--details-out",
               str(self.out / "BENCH_DETAILS.json")]
        if self.dry:
            self.record("final_bench", "dry_run",
                        command=" ".join(cmd))
            return
        rc, out = _run(cmd, BENCH_FULL_TIMEOUT, env=env)
        last = out.strip().splitlines()[-1] if out.strip() else ""
        parsed = None
        try:
            parsed = json.loads(last)
            with open(self.out / "BENCH_LATEST.json", "w",
                      encoding="utf-8") as fh:
                fh.write(last + "\n")
        except ValueError:
            pass
        mfu = {}
        try:
            with open(self.out / "BENCH_DETAILS.json",
                      encoding="utf-8") as fh:
                det = json.load(fh)
            for k in ("resnet50", "transformer", "transformer_int8"):
                if isinstance(det.get(k), dict):
                    mfu[k] = det[k].get("mfu_pct")
        except (OSError, ValueError):
            pass
        self.record("final_bench",
                    "ok" if rc == 0 and parsed else "failed",
                    rc=rc, headline=parsed, mfu_pct=mfu,
                    output_tail=out[-1000:])
        # Regenerate the measured-numbers docs page from the fresh
        # artifacts (docs/26-benchmarks.md cannot rot by design).
        _run([sys.executable, "tools/benchgen.py",
              "--artifacts-dir", str(self.out)], 120)

    def serving_speculative(self) -> None:
        """Speculative continuous-batching serving (dense + paged KV):
        per-variant tokens/s, TTFT/TPOT p50, and the engine's measured
        acceptance rate, via bench.py's serving_speculative workload
        (models/serving.py draft/verify engine steps)."""
        details_path = self.out / "SPEC_SERVING_DETAILS.json"
        cmd = [sys.executable, "bench.py", "--workloads",
               "serving_speculative", "--details-out",
               str(details_path)]
        metric_keys = ("tokens_per_second", "ttft_ms_p50",
                       "tpot_ms_p50", "acceptance_rate")
        if self.dry:
            self.record(
                "serving_speculative", "dry_run",
                command=" ".join(cmd),
                metrics={variant: {k: None for k in metric_keys}
                         for variant in ("dense", "paged")})
            return
        rc, out = _run(cmd, BENCH_QUICK_TIMEOUT)
        summary: dict = {}
        try:
            with open(details_path, encoding="utf-8") as fh:
                det = json.load(fh)
        except (OSError, ValueError):
            det = {}
        for variant, key in (("dense", "serving_speculative"),
                             ("paged", "serving_speculative_paged")):
            rep = det.get(key) or {}
            if "error" in rep:
                summary[variant] = {"error": rep["error"]}
                continue
            spec = rep.get("speculative") or {}
            summary[variant] = {
                "tokens_per_second": rep.get("tokens_per_second"),
                "ttft_ms_p50": (rep.get("ttft_ms") or {}).get("p50"),
                "tpot_ms_p50": (rep.get("tpot_ms") or {}).get("p50"),
                "acceptance_rate": spec.get("acceptance_rate"),
            }
        ok = (rc == 0 and summary
              and all("error" not in v for v in summary.values()))
        self.record("serving_speculative",
                    "ok" if ok else "failed", rc=rc,
                    metrics=summary, output_tail=out[-800:])

    def checkpoint_overhead(self) -> None:
        """Sync vs async blocking ms/save (bench.py's
        checkpoint_overhead workload): the training loop's measured
        stall per checkpoint, before and after the async
        double-buffered save pipeline. The dry-run skeleton names
        every metric so report consumers bind to the shape on CPU."""
        details_path = self.out / "CKPT_OVERHEAD_DETAILS.json"
        cmd = [sys.executable, "bench.py", "--workloads",
               "checkpoint_overhead", "--details-out",
               str(details_path)]
        metric_keys = ("sync_blocking_ms_per_save",
                       "async_blocking_ms_per_save",
                       "blocking_speedup", "payload_mb", "saves")
        if self.dry:
            self.record("checkpoint_overhead", "dry_run",
                        command=" ".join(cmd),
                        metrics={k: None for k in metric_keys})
            return
        rc, out = _run(cmd, BENCH_QUICK_TIMEOUT)
        try:
            with open(details_path, encoding="utf-8") as fh:
                det = json.load(fh)
        except (OSError, ValueError):
            det = {}
        rep = det.get("checkpoint_overhead") or {}
        if "error" in rep:
            summary = {"error": rep["error"]}
        else:
            summary = {k: rep.get(k) for k in metric_keys}
        ok = (rc == 0 and "error" not in summary
              and summary.get("sync_blocking_ms_per_save")
              is not None)
        self.record("checkpoint_overhead",
                    "ok" if ok else "failed", rc=rc,
                    metrics=summary, output_tail=out[-800:])

    def compile_warm(self) -> None:
        """Cold vs warm compile wall time through the persistent
        compilation cache (bench.py's compile_warm workload): run 1
        compiles the transformer train step cold into a fresh cache
        dir, run 2 deserializes warm with AOT precompile — the per
        node, per-restart badput that pool-wide cache seeding
        removes. The dry-run skeleton names every metric."""
        details_path = self.out / "COMPILE_WARM_DETAILS.json"
        cmd = [sys.executable, "bench.py", "--workloads",
               "compile_warm", "--details-out", str(details_path)]
        metric_keys = ("cold_ms", "warm_ms", "speedup", "cache_hits",
                       "aot_first_step_ms", "steady_step_ms")
        if self.dry:
            self.record("compile_warm", "dry_run",
                        command=" ".join(cmd),
                        metrics={k: None for k in metric_keys})
            return
        rc, out = _run(cmd, BENCH_QUICK_TIMEOUT)
        try:
            with open(details_path, encoding="utf-8") as fh:
                det = json.load(fh)
        except (OSError, ValueError):
            det = {}
        rep = det.get("compile_warm") or {}
        if "error" in rep:
            summary = {"error": rep["error"]}
        else:
            summary = {k: rep.get(k) for k in metric_keys}
        ok = (rc == 0 and "error" not in summary
              and summary.get("cold_ms") is not None
              and summary.get("warm_ms") is not None
              and summary["warm_ms"] < summary["cold_ms"])
        self.record("compile_warm", "ok" if ok else "failed", rc=rc,
                    metrics=summary, output_tail=out[-800:])

    def goodput(self) -> None:
        """Decompose whatever goodput events the bench run's state
        store accumulated into the paper's availability x resource x
        program legs. The dry-run skeleton names goodput_ratio, each
        decomposition leg, and every badput category so report
        consumers (tools/benchgen.py) can bind to the shape on CPU."""
        from batch_shipyard_tpu.goodput import accounting
        skeleton = {
            "goodput_ratio": None,
            "availability_goodput": None,
            "resource_goodput": None,
            "program_goodput": None,
            "badput_seconds": {category: None for category in
                               accounting.BADPUT_CATEGORIES},
            "overlapped_seconds": {category: None for category in
                                   accounting.OVERLAPPED_CATEGORIES},
        }
        cmd = (f"{sys.executable} -m batch_shipyard_tpu.cli.main "
               f"goodput pool --raw")
        if self.dry:
            self.record("goodput", "dry_run", command=cmd,
                        metrics=skeleton)
            return
        try:
            from batch_shipyard_tpu.state.memory import (
                MemoryStateStore)
            store_path = os.environ.get("SHIPYARD_BENCH_STORE")
            if store_path:
                from batch_shipyard_tpu.state.localfs import (
                    LocalFSStateStore)
                store = LocalFSStateStore(store_path)
            else:
                # No orchestrated pool in this bench run: nothing to
                # account — record the honest empty decomposition.
                store = MemoryStateStore()
            report = accounting.fleet_report(store)
            with open(self.out / "GOODPUT_REPORT.json", "w",
                      encoding="utf-8") as fh:
                json.dump(report, fh, indent=2)
            self.record(
                "goodput",
                "ok" if report["wall_seconds"] > 0 else "no_events",
                goodput_ratio=report["goodput_ratio"],
                badput_seconds=report["badput_seconds"])
        except Exception as exc:  # noqa: BLE001 - report, don't die
            self.record("goodput", "failed", error=str(exc))

    def chaos_drill(self) -> None:
        """Self-healing proof (chaos/): replay a seeded fault
        schedule over a fakepod pool and assert the recovery
        invariants. Pure CPU — real NodeAgent threads, no
        accelerator — so the same drill that gates CI also runs on
        the pod to prove recovery under real substrate timing. The
        dry-run skeleton names every invariant benchgen binds to."""
        details_path = self.out / "CHAOS_DRILL_DETAILS.json"
        cmd = [sys.executable, "tools/chaos_drill.py",
               "--seeds", "7",
               "--report-out", str(details_path)]
        invariant_keys = ("tasks", "orphaned_gang_rows",
                          "queue_depth", "retries",
                          "backoff_seconds")
        if self.dry:
            self.record("chaos_drill", "dry_run",
                        command=" ".join(cmd),
                        metrics={"determinism": None,
                                 "injections_applied": None,
                                 "invariants": {k: None for k in
                                                invariant_keys}})
            return
        rc, out = _run(cmd, BENCH_QUICK_TIMEOUT)
        try:
            with open(details_path, encoding="utf-8") as fh:
                det = json.load(fh)
        except (OSError, ValueError):
            det = {}
        scenarios = det.get("scenarios") or [{}]
        first = scenarios[0]
        summary = {
            "determinism": first.get("determinism"),
            "injections_applied": first.get("injections_applied"),
            "invariants": {k: first.get("invariants", {}).get(k)
                           for k in invariant_keys},
        }
        if first.get("error"):
            summary["error"] = first["error"]
        ok = rc == 0 and det.get("ok") is True
        self.record("chaos_drill", "ok" if ok else "failed", rc=rc,
                    metrics=summary, output_tail=out[-800:])

    # -- driver ----------------------------------------------------
    def run(self) -> int:
        started = time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime())
        self.kernel_checks()
        self.ring_collectives()
        winner = self.tuning_ab()
        self.final_bench(winner)
        self.serving_speculative()
        self.checkpoint_overhead()
        self.goodput()
        self.compile_warm()
        self.chaos_drill()
        report = {
            "started_at": started,
            "finished_at": time.strftime("%Y-%m-%dT%H:%M:%SZ",
                                         time.gmtime()),
            "dry_run": self.dry,
            "phases": self.phases,
        }
        with open(self.out / "SILICON_PROOF.json", "w",
                  encoding="utf-8") as fh:
            json.dump(report, fh, indent=2)
        bad = [p for p in self.phases
               if p["status"] in ("failed", "partial")]
        print(f"[silicon-proof] report: "
              f"{self.out / 'SILICON_PROOF.json'} "
              f"({len(self.phases)} phases, {len(bad)} not ok)")
        return 1 if bad else 0


def main(argv: list[str] | None = None) -> int:
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--dry-run", action="store_true",
                        help="write the full report skeleton without "
                        "touching an accelerator (CI path)")
    parser.add_argument("--out-dir", default=str(REPO_ROOT),
                        help="where reports land (default: repo "
                        "root)")
    parser.add_argument("--skip-tuning", action="store_true",
                        help="skip the profile A/B (bench under the "
                        "default profile only)")
    args = parser.parse_args(argv)
    out_dir = pathlib.Path(args.out_dir)
    out_dir.mkdir(parents=True, exist_ok=True)
    return Pipeline(out_dir, args.dry_run, args.skip_tuning).run()


if __name__ == "__main__":
    sys.exit(main())
