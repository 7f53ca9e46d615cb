"""The traffic generator is a pure function of (file, seed); the
arithmetic on hand-made samples."""

import json
import math
import statistics

import pytest

from benchmark import spec, stats, traffic_gen

CHAT = json.load(open(spec.ROOT / "benchmark/traffic/chat-online.json"))
BATCH = json.load(open(spec.ROOT / "benchmark/traffic/batch-offline.json"))


@pytest.mark.parametrize("traffic", [CHAT, BATCH],
                         ids=["chat-online", "batch-offline"])
def test_generator_is_a_pure_function_of_file_and_seed(traffic):
    a = traffic_gen.generate(traffic, 2**31 + 12345, 30, 64000)
    b = traffic_gen.generate(traffic, 2**31 + 12345, 30, 64000)
    c = traffic_gen.generate(traffic, 7, 30, 64000)
    assert a == b
    assert a["requests"] != c["requests"]


@pytest.mark.parametrize("traffic", [CHAT, BATCH],
                         ids=["chat-online", "batch-offline"])
def test_another_path_seed_is_the_same_sizes_in_another_order(traffic):
    a = traffic_gen.generate(dict(traffic, path_seed=1), 1, 30,
                             64000)["requests"]
    b = traffic_gen.generate(dict(traffic, path_seed=2), 1, 30,
                             64000)["requests"]
    prefix = traffic["shared_prefix_tokens"]

    def sizes(requests, key):
        return sorted(key(r) for r in requests)

    assert sizes(a, lambda r: len(r["prompt"])) == \
        sizes(b, lambda r: len(r["prompt"]))
    assert sizes(a, lambda r: r["max_new_tokens"]) == \
        sizes(b, lambda r: r["max_new_tokens"])
    assert [len(r["prompt"]) for r in a] != [len(r["prompt"]) for r in b]
    low = traffic["prompt_tokens"]["min"] + prefix
    high = traffic["prompt_tokens"]["max"] + prefix
    assert all(low <= len(r["prompt"]) <= high for r in a)
    assert all(1 <= t < 64000 for r in a[:5] for t in r["prompt"])


@pytest.mark.parametrize("traffic", [CHAT, BATCH],
                         ids=["chat-online", "batch-offline"])
def test_the_path_seed_fixes_the_order_and_the_seed_the_tokens(traffic):
    with pytest.raises(KeyError):       # a file has to fix its path
        traffic_gen.generate(
            {k: v for k, v in traffic.items() if k != "path_seed"},
            1, 30, 64000)
    a = traffic_gen.generate(traffic, 1, 30, 64000)["requests"]
    b = traffic_gen.generate(traffic, 2, 30, 64000)["requests"]
    assert [(len(r["prompt"]), r["max_new_tokens"], r.get("due_s"))
            for r in a] == \
        [(len(r["prompt"]), r["max_new_tokens"], r.get("due_s"))
         for r in b]
    assert all(x["prompt"] != y["prompt"] for x, y in zip(a, b))


@pytest.mark.parametrize("dist", [CHAT["prompt_tokens"],
                                  CHAT["output_tokens"],
                                  BATCH["prompt_tokens"],
                                  BATCH["output_tokens"]])
def test_clipped_lognormal_has_the_stated_median(dist):
    grid = traffic_gen.lognormal_grid(dist, 1001)
    assert abs(statistics.median(grid) - dist["median"]) <= 1
    assert min(grid) >= dist["min"] and max(grid) <= dist["max"]


def test_open_loop_arrivals_fill_the_window_at_the_fixed_rate():
    rate = CHAT["arrivals"]["rate_per_s"]
    plan = traffic_gen.generate(CHAT, 3, 30, 64000)
    window = [r for r in plan["requests"] if r["phase"] == "window"]
    lead = [r for r in plan["requests"] if r["phase"] == "lead"]
    assert len(window) == round(rate * 30)
    assert len(lead) == round(rate * CHAT["lead_in_s"])
    assert all(0 <= r["due_s"] < 30 for r in window)
    assert all(-CHAT["lead_in_s"] <= r["due_s"] < 0 for r in lead)
    dues = [r["due_s"] for r in window]
    assert dues == sorted(dues)
    gaps = traffic_gen.exponential_gaps(rate, 210)
    assert math.isclose(sum(gaps), 210 / rate)
    # 2.4/s over a 4 s lead-in rounds to 10 requests, 4.17 s of gaps:
    # they are fitted to the phase, so none is due inside the window
    assert math.isclose(sum(traffic_gen.exponential_gaps(2.4, 10, 4.0)),
                        4.0)
    shared = plan["requests"][0]["prompt"][:CHAT["shared_prefix_tokens"]]
    assert all(r["prompt"][:len(shared)] == shared
               for r in plan["requests"])


def test_closed_loop_has_clients_and_no_shared_prefix():
    plan = traffic_gen.generate(BATCH, 3, 30, 64000)
    assert plan["mode"] == "closed" and plan["clients"] == 96
    assert len(plan["requests"]) == BATCH["pool_requests"]
    assert plan["requests"][0]["prompt"][:8] != \
        plan["requests"][1]["prompt"][:8]


def test_percentiles_on_hand_made_samples():
    assert stats.percentile([1, 2, 3, 4, 5], 50) == 3
    assert stats.percentile([10, 20], 95) == pytest.approx(19.5)
    assert stats.percentile([7], 95) == 7
    assert stats.percentile([], 95) is None
    values = list(range(1, 101))
    assert stats.percentile(values, 95) == pytest.approx(95.05)


def test_a_failed_request_misses_every_limit():
    ok = [100.0] * 19
    assert stats.tail_percentile(ok + [None], 95, 1e9) > 100.0
    assert stats.tail_percentile(ok + [None], 100, 1e9) == 1e9
    assert stats.tail_percentile(ok + [None] * 3, 95, 1e9) == 1e9
    assert stats.tail_percentile(ok, 95, 1e9) == 100.0


def test_tokens_per_second_counts_arrivals_inside_the_window():
    times = [0.5, 1.0, 1.5, 2.0, 2.5, 3.0]
    assert stats.rate_in_window(times, 1.0, 3.0) == 2.0   # 1,1.5,2,2.5
    with pytest.raises(ValueError):
        stats.rate_in_window(times, 2.0, 2.0)


def test_quartile_spread_is_the_drivers_definition():
    values = [100, 101, 102, 103, 104, 105]
    q1, _, q3 = statistics.quantiles(values, n=4)
    assert stats.quartile_spread(values) == (q3 - q1) / 102.5
