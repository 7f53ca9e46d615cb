"""Load generator for the serving front end.

Measures what continuous-batching engines are judged by: TTFT and
TPOT percentiles under concurrent load, plus aggregate tokens/sec —
the serving benchmark the reference's recipes-as-acceptance strategy
(SURVEY.md section 4) implies but never had an ML engine to apply to.
stdlib-only: urllib for transport, threads for in-flight requests,
random.Random(seed) for reproducible arrivals.

Two arrival processes: steady Poisson (``arrival="poisson"``) and a
diurnal replay (``arrival="diurnal"``) that reuses the fleet
simulator's sinusoidal thinning construction
(sim/traces.diurnal_arrivals) — the same day/night curve, scaled to
real seconds, deterministic per seed. Workloads can share prompt
prefixes across request groups (``shared_prefix_groups``) to exercise
the engine's cross-request prefix cache and the router's
prefix-affinity routing, and tag requests with SLO classes to report
per-class attainment alongside the percentile tables.
"""

from __future__ import annotations

import hashlib
import json
import math
import random
import threading
import time
import urllib.error
import urllib.request
from typing import Optional, Sequence, Union

from batch_shipyard_tpu.sim import traces as sim_traces

from batch_shipyard_tpu.trace.histogram import LatencyHistogram
from batch_shipyard_tpu.utils import util

logger = util.get_logger(__name__)


def _exact_percentile(values: list, q: float) -> float:
    """Nearest-rank percentile over the raw values (no binning)."""
    if not values:
        return 0.0
    ordered = sorted(values)
    k = max(0, math.ceil(q / 100.0 * len(ordered)) - 1)
    return ordered[k]


def post_generate(base_url: str, payload: dict,
                  timeout: float = 300.0) -> dict:
    """One blocking POST /v1/generate; returns the decoded reply."""
    req = urllib.request.Request(
        f"{base_url}/v1/generate",
        data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json"}, method="POST")
    with urllib.request.urlopen(req, timeout=timeout) as resp:
        return json.loads(resp.read())


def run_load(base_url: Union[str, Sequence[str]],
             num_requests: int,
             rate_hz: float = 8.0,
             prompt_len: tuple[int, int] = (4, 32),
             max_new_tokens: tuple[int, int] = (8, 32),
             vocab_size: int = 97,
             seed: int = 0,
             eos_id: Optional[int] = None,
             request_timeout: float = 300.0,
             arrival: str = "poisson",
             day_seconds: float = 60.0,
             trough_rate_hz: Optional[float] = None,
             shared_prefix_groups: int = 0,
             shared_prefix_len: int = 0,
             slo_classes: Optional[dict] = None) -> dict:
    """Fire ``num_requests`` and return the latency report:
    TTFT/TPOT/latency p50/p90/p99 computed from MERGED per-replica
    fixed-log-bucket histograms (trace/histogram.py — the same
    aggregation rule the router and heimdall use, so bench numbers
    and fleet dashboards agree), tokens/sec, and the raw mergeable
    histograms.

    ``arrival="poisson"`` spaces requests at ``rate_hz``;
    ``arrival="diurnal"`` replays the fleet simulator's sinusoidal
    curve (peak ``rate_hz``, trough ``trough_rate_hz`` or rate_hz/4,
    one virtual day = ``day_seconds``). With ``shared_prefix_groups``
    > 0, each request prepends one of that many fixed
    ``shared_prefix_len``-token prefixes (chosen per-request by the
    seeded rng) and carries a matching ``prefix_key`` — the shape the
    prefix cache and affinity routing exist for. ``slo_classes`` maps
    class name -> {"ttft_ms", "tpot_ms"} targets (None = untargeted);
    requests then cycle through the classes and the report adds
    per-class attainment. 503-shed requests are counted separately
    from transport failures.

    ``base_url`` may be a single URL or a list of replica URLs (a
    serving fleet — one server task per pool node); requests then
    round-robin across replicas and the report adds a per-replica
    completion breakdown."""
    urls = ([base_url] if isinstance(base_url, str)
            else list(base_url))
    rng = random.Random(seed)
    prefixes = [[rng.randrange(vocab_size)
                 for _ in range(shared_prefix_len)]
                for _ in range(shared_prefix_groups)]
    class_names = sorted(slo_classes) if slo_classes else []
    if arrival == "diurnal":
        trough = (trough_rate_hz if trough_rate_hz is not None
                  else rate_hz / 4.0)
        times = sim_traces.diurnal_arrivals(
            seed, num_requests, day_seconds, rate_hz, trough)
        gaps = [times[k + 1] - times[k]
                for k in range(num_requests - 1)]
    elif arrival == "poisson":
        gaps = [rng.expovariate(rate_hz)
                for _ in range(num_requests - 1)]
    else:
        raise ValueError(f"unknown arrival process: {arrival!r}")
    results: list[Optional[dict]] = [None] * num_requests
    errors: list[Optional[str]] = [None] * num_requests
    sheds: list[Optional[str]] = [None] * num_requests
    threads = []

    def _one(k: int, url: str, payload: dict) -> None:
        try:
            result = post_generate(url, payload, request_timeout)
            result["_replica"] = url
            results[k] = result
        except urllib.error.HTTPError as exc:
            try:
                body = json.loads(exc.read())
            except Exception:
                body = {}
            if exc.code == 503 and body.get("shed"):
                sheds[k] = payload.get("slo_class", "standard")
            else:
                errors[k] = f"HTTP {exc.code}: " \
                            f"{body.get('error', '')}"
        except (urllib.error.URLError, OSError, TimeoutError) as exc:
            errors[k] = str(exc)

    started = time.perf_counter()
    for k in range(num_requests):
        plen = rng.randint(*prompt_len)
        prompt = [rng.randrange(vocab_size) for _ in range(plen)]
        payload = {
            "request_id": f"load-{seed}-{k}",
            "max_new_tokens": rng.randint(*max_new_tokens),
        }
        if prefixes:
            g = rng.randrange(len(prefixes))
            prompt = prefixes[g] + prompt
            payload["prefix_key"] = f"load-{seed}-g{g}"
        payload["prompt"] = prompt
        if class_names:
            payload["slo_class"] = class_names[k % len(class_names)]
        if eos_id is not None:
            payload["eos_id"] = eos_id
        thread = threading.Thread(
            target=_one, args=(k, urls[k % len(urls)], payload),
            daemon=True)
        thread.start()
        threads.append(thread)
        if k < num_requests - 1:
            time.sleep(gaps[k])
    for thread in threads:
        thread.join(request_timeout)
    elapsed = time.perf_counter() - started
    done = [r for r in results if r is not None]
    failed = [e for e in errors if e is not None]
    shed = [s for s in sheds if s is not None]
    tokens = sum(r["num_tokens"] for r in done)
    # One histogram per (metric, replica), merged for the report:
    # this is the exact aggregation a fleet of independent replicas
    # supports (percentiles of pooled bucket counts), as opposed to
    # averaging per-replica percentiles or reporting means.
    per_replica: dict[str, dict[str, LatencyHistogram]] = {
        metric: {url: LatencyHistogram() for url in urls}
        for metric in ("ttft_ms", "tpot_ms", "latency_ms")}
    for r in done:
        for metric in ("ttft_ms", "tpot_ms", "latency_ms"):
            per_replica[metric][r["_replica"]].observe(r[metric])
    merged = {metric: LatencyHistogram.merged(hists.values())
              for metric, hists in per_replica.items()}
    report = {
        "num_requests": num_requests,
        "completed": len(done),
        "failed": len(failed),
        "shed": len(shed),
        "arrival": arrival,
        "offered_rate_hz": rate_hz,
        "elapsed_seconds": elapsed,
        "requests_per_second": len(done) / elapsed if elapsed else 0.0,
        "tokens_per_second": tokens / elapsed if elapsed else 0.0,
        "generated_tokens": tokens,
        "ttft_ms": merged["ttft_ms"].percentiles((50, 90, 99)),
        "tpot_ms": merged["tpot_ms"].percentiles((50, 90, 99)),
        # Exact mean/percentiles from the raw observations (the
        # log-bucket histograms quantize to bucket edges; an A/B
        # delta, prefix cache on against off, needs unbinned values so
        # a real improvement can't vanish into a shared bucket).
        "ttft_mean_ms": (sum(r["ttft_ms"] for r in done) / len(done)
                         if done else 0.0),
        "tpot_mean_ms": (sum(r["tpot_ms"] for r in done) / len(done)
                         if done else 0.0),
        "ttft_exact_ms": {
            f"p{q}": _exact_percentile(
                [r["ttft_ms"] for r in done], q)
            for q in (50, 99)},
        "tpot_exact_ms": {
            f"p{q}": _exact_percentile(
                [r["tpot_ms"] for r in done], q)
            for q in (50, 99)},
        "latency_ms": merged["latency_ms"].percentiles((50, 90, 99)),
        "ttft_hist": merged["ttft_ms"].to_dict(),
        "tpot_hist": merged["tpot_ms"].to_dict(),
    }
    if slo_classes:
        # Per-class SLO attainment: of the completed requests in each
        # class, the fraction whose TTFT/TPOT landed inside the
        # class's target (a None target always attains). Sheds are
        # charged to the class that lost them.
        per_class: dict[str, dict] = {
            name: {"requests": 0, "completed": 0, "shed": 0,
                   "ttft_ok": 0, "tpot_ok": 0}
            for name in class_names}
        for s in shed:
            if s in per_class:
                per_class[s]["requests"] += 1
                per_class[s]["shed"] += 1
        for r in done:
            name = r.get("slo_class", "standard")
            row = per_class.setdefault(
                name, {"requests": 0, "completed": 0, "shed": 0,
                       "ttft_ok": 0, "tpot_ok": 0})
            row["requests"] += 1
            row["completed"] += 1
            targets = slo_classes.get(name) or {}
            for metric, key in (("ttft_ms", "ttft_ok"),
                                ("tpot_ms", "tpot_ok")):
                target = targets.get(metric)
                if target is None or r[metric] <= target:
                    row[key] += 1
        for name, row in per_class.items():
            n = row["completed"]
            targets = slo_classes.get(name) or {}
            row["ttft_target_ms"] = targets.get("ttft_ms")
            row["tpot_target_ms"] = targets.get("tpot_ms")
            row["ttft_attainment"] = row["ttft_ok"] / n if n else None
            row["tpot_attainment"] = row["tpot_ok"] / n if n else None
        report["slo_attainment"] = per_class
    if prefixes:
        report["shared_prefix_groups"] = shared_prefix_groups
        report["shared_prefix_len"] = shared_prefix_len
    # Digest of every completed request's exact token ids: two runs
    # at the same seed against byte-identical engines must agree —
    # the bench's prefix-cache-on-vs-off equivalence check.
    digest = hashlib.sha256()
    for r in sorted(done, key=lambda r: r["request_id"]):
        digest.update(f"{r['request_id']}:{r['tokens']};".encode())
    report["outputs_sha256"] = digest.hexdigest()
    if len(urls) > 1:
        by_replica: dict[str, int] = {}
        for r in done:
            by_replica[r["_replica"]] = by_replica.get(
                r["_replica"], 0) + 1
        report["replicas"] = len(urls)
        report["completed_by_replica"] = by_replica
    if failed:
        report["errors"] = failed[:8]
    return report


def main() -> int:
    """Standalone benchmark CLI against running server(s):

        python -m batch_shipyard_tpu.models.loadgen \\
            http://node0:8900 http://node1:8900 \\
            --num 128 --rate 32 --report fleet_report.json
    """
    import argparse

    parser = argparse.ArgumentParser()
    parser.add_argument("urls", nargs="+",
                        help="Serving front end base URL(s)")
    parser.add_argument("--num", type=int, default=64)
    parser.add_argument("--rate", type=float, default=8.0)
    parser.add_argument("--prompt-len", type=int, nargs=2,
                        default=(4, 32), metavar=("MIN", "MAX"))
    parser.add_argument("--gen-tokens", type=int, nargs=2,
                        default=(8, 32), metavar=("MIN", "MAX"))
    parser.add_argument("--vocab", type=int, default=32000)
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--arrival", choices=("poisson", "diurnal"),
                        default="poisson",
                        help="Arrival process; diurnal replays the "
                             "fleet simulator's day/night curve")
    parser.add_argument("--day-seconds", type=float, default=60.0,
                        help="Virtual-day length for --arrival "
                             "diurnal")
    parser.add_argument("--trough-rate", type=float, default=None,
                        help="Diurnal trough rate (default rate/4)")
    parser.add_argument("--shared-prefix-groups", type=int, default=0,
                        help="Number of shared prompt-prefix groups "
                             "(0 = fully random prompts)")
    parser.add_argument("--shared-prefix-len", type=int, default=0,
                        help="Tokens in each shared prefix")
    parser.add_argument("--slo", default=None,
                        help="JSON: class name -> "
                             '{"ttft_ms": .., "tpot_ms": ..}')
    parser.add_argument("--report", default=None,
                        help="Also write the JSON report here")
    args = parser.parse_args()
    report = run_load(
        args.urls, args.num, rate_hz=args.rate,
        prompt_len=tuple(args.prompt_len),
        max_new_tokens=tuple(args.gen_tokens),
        vocab_size=args.vocab, seed=args.seed,
        arrival=args.arrival, day_seconds=args.day_seconds,
        trough_rate_hz=args.trough_rate,
        shared_prefix_groups=args.shared_prefix_groups,
        shared_prefix_len=args.shared_prefix_len,
        slo_classes=json.loads(args.slo) if args.slo else None)
    if args.report:
        with open(args.report, "w", encoding="utf-8") as fh:
            json.dump(report, fh, indent=2)
    print(json.dumps(report))
    return 1 if report["failed"] else 0


if __name__ == "__main__":
    import sys

    sys.exit(main())
