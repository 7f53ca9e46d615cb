"""The one general traffic generator: (traffic file, seed, seconds,
vocabulary) -> the requests of a run. Standard library only, so the
load generator's process imports it without touching JAX.

Every seed is given the SAME multiset of sizes and arrival gaps (the
quantile grid of the file's distributions), so seeds never differ in
how much work a run holds. Their ORDER comes from the file's
``path_seed``: one fixed arrival path for every run, so the seed
changes the token ids alone (and with them every hash of the prefix
cache and every logit). With some hundred requests a window, WHICH
sizes arrive together decides whether the page pool fills, and a
fresh order each seed swung the p95 of first-token time 8x (PERF.md,
Findings, PR 23). A traffic mix is a data file; this module is not
edited to add one.

File keys (see benchmark/traffic/*.json):
  kind                  serve-open | serve-closed (picks the driver,
                        drivers/<kind>.py; a kind that begins with one
                        of the two, as serve-closed-<x>, is that loop
                        under a driver of its own)
  path_seed             fixes the order of sizes and gaps
  arrivals              {"rate_per_s": r}: exponential gaps on a
                        quantile grid (not a sampled Poisson stream) open
  clients               n                                         closed
  client_stagger_s      each caller joins this long after the last
  pool_requests         size of the closed loop's request list
  lead_in_s             traffic before the window (not measured)
  shared_prefix_tokens  leading tokens every request shares (0 = none)
  prompt_tokens         {"median", "sigma", "min", "max"} log-normal,
                        the part AFTER the shared prefix
  output_tokens         same, enforced by max_new_tokens (no EOS)
"""

from __future__ import annotations

import math
import random
import statistics

_NORMAL = statistics.NormalDist()


def lognormal_grid(dist: dict, n: int) -> list[int]:
    """The n mid-quantiles of a log-normal with the given median and
    sigma, clipped to [min, max] and rounded: a fixed multiset whose
    median is the stated one (to rounding)."""
    mu = math.log(dist["median"])
    out = []
    for i in range(n):
        z = _NORMAL.inv_cdf((i + 0.5) / n)
        value = math.exp(mu + dist["sigma"] * z)
        out.append(int(round(min(max(value, dist["min"]),
                                 dist["max"]))))
    return out


def exponential_gaps(rate_per_s: float, n: int,
                     span_s: float = math.inf) -> list[float]:
    """The n mid-quantiles of Exp(rate), rescaled so that they sum to
    exactly n / rate (a Poisson stream's gaps with the run-to-run
    variation of their total taken out), or to ``span_s`` where that
    is shorter, so that no request is due past its phase's end."""
    if n == 0:
        return []
    raw = [-math.log(1.0 - (i + 0.5) / n) for i in range(n)]
    scale = min(n / rate_per_s, span_s) / sum(raw)
    return [g * scale for g in raw]


def _rng(seed: int, tag: str) -> random.Random:
    return random.Random(f"{int(seed)}/{tag}")


def _tokens(rng: random.Random, n: int, vocab: int) -> list[int]:
    return [rng.randrange(1, vocab) for _ in range(n)]


def _sized_requests(traffic: dict, n: int, seed: int, tag: str,
                    vocab: int, prefix: list[int]) -> list[dict]:
    order = traffic["path_seed"]
    prompts = lognormal_grid(traffic["prompt_tokens"], n)
    outputs = lognormal_grid(traffic["output_tokens"], n)
    _rng(order, tag + "/prompt-order").shuffle(prompts)
    _rng(order, tag + "/output-order").shuffle(outputs)
    rng = _rng(seed, tag + "/tokens")
    return [{"prompt": prefix + _tokens(rng, p, vocab),
             "max_new_tokens": o}
            for p, o in zip(prompts, outputs)]


def generate(traffic: dict, seed: int, seconds: float,
             vocab: int) -> dict:
    """-> {"mode": "open"|"closed", "requests": [...], "clients": n,
    "lead_in_s": s}. Open-loop requests carry ``due_s`` relative to the
    window's start (lead-in requests are negative) and ``phase``."""
    kind = traffic["kind"]
    lead_in = float(traffic.get("lead_in_s", 0))
    prefix_len = int(traffic.get("shared_prefix_tokens", 0))
    prefix = _tokens(_rng(seed, "shared-prefix"), prefix_len, vocab)
    if kind.startswith("serve-open"):
        rate = float(traffic["arrivals"]["rate_per_s"])
        requests = []
        for phase, span, start in (("lead", lead_in, -lead_in),
                                   ("window", float(seconds), 0.0)):
            n = int(round(rate * span))
            gaps = exponential_gaps(rate, n, span)
            _rng(traffic["path_seed"],
                 phase + "/gap-order").shuffle(gaps)
            sized = _sized_requests(traffic, n, seed, phase, vocab,
                                    prefix)
            due = start
            for gap, request in zip(gaps, sized):
                request.update(due_s=due, phase=phase)
                requests.append(request)
                due += gap
        mode = "open"
    elif kind.startswith("serve-closed"):
        requests = _sized_requests(
            traffic, int(traffic["pool_requests"]), seed, "pool",
            vocab, prefix)
        mode = "closed"
    else:
        raise ValueError(f"traffic kind {kind!r} is not served traffic")
    for idx, request in enumerate(requests):
        request["idx"] = idx
    return {"mode": mode, "requests": requests,
            "clients": int(traffic.get("clients", 0)),
            "lead_in_s": lead_in}
