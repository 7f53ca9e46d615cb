"""The serve driver reads the engine through its public surface alone
(warmup_buckets, occupancy, pending, active_request_ids, cancel,
prefix_cache_clear, and the optional take_decisions): shown on a
stand-in engine that HAS nothing else, so a read of a private name
would raise here. The warm-up lengths are worked out by hand for the
two traffic files."""

import numpy as np
import pytest

from benchmark import spec
from benchmark.drivers import serve


class PublicEngine:
    """Only what the engine documents as public, with made-up state."""

    def __init__(self, max_decode_len=2048, page_size=64,
                 prefix_cache=True, queued=0):
        self.max_decode_len = max_decode_len
        self.page_size = page_size
        self.prefix_cache = prefix_cache
        self.num_slots = 4
        self.params = None
        self.active = ["a", "b"]
        self.queued = queued
        self.cancelled = []
        self.cleared = 0
        self.stepped = 0
        self.taken = []

    def warmup_buckets(self):
        buckets = [16]
        while buckets[-1] < self.max_decode_len:
            buckets.append(min(2 * buckets[-1], self.max_decode_len))
        return buckets

    def occupancy(self):
        return {"slots_active": len(self.active), "slots_total": 4,
                "queued": self.queued, "live_tokens": 700,
                "kv_pages_in_use": 11 + self.stepped,
                "kv_pages_total": 192}

    def pending(self):
        return self.queued + len(self.active)

    def active_request_ids(self):
        return list(self.active)

    def cancel(self, request_id):
        self.active.remove(request_id)
        self.cancelled.append(request_id)
        return True

    def prefix_cache_clear(self):
        self.cleared += 1
        return 0

    def step(self):
        self.stepped += 1
        return []

    def take_decisions(self, request_id):
        """The one method a routed model's engine adds: the record of
        a finished request's choices, handed over once."""
        self.taken.append(request_id)
        if request_id == "bench-2":
            return None
        return {"first": 0, "layers": {
            "layer_0": np.array([[0, 1], [2, 1], [1, 3]])}}


@pytest.mark.parametrize("cell,cold,shared", [
    # 128 shared + 32..1024: cold 160..1152; after the prefix's two
    # whole pages a later request prefills 32..1024
    ("baichuan7b.chat-online", [256, 512, 1024, 1152],
     [32, 64, 128, 256, 512, 1024]),
    # no shared prefix: 64..1024 cold, nothing shared
    ("baichuan7b.batch-offline", [64, 128, 256, 512, 1024], []),
])
def test_warm_up_lengths_by_hand(cell, cold, shared):
    traffic = spec.load_cell(cell).traffic
    assert serve.reachable_buckets(PublicEngine(), traffic) == \
        (cold, shared)


def test_warm_up_lengths_stop_at_the_engines_cap():
    """The last bucket is the engine's cap, not a power of two."""
    traffic = {"shared_prefix_tokens": 0,
               "prompt_tokens": {"min": 10, "max": 150}}
    engine = PublicEngine(max_decode_len=160, prefix_cache=False)
    assert engine.warmup_buckets() == [16, 32, 64, 128, 160]
    assert serve.reachable_buckets(engine, traffic) == \
        ([16, 32, 64, 128, 150], [])


def test_the_step_recorder_reads_occupancy_as_the_step_starts():
    engine = PublicEngine(queued=3)
    recorder = serve.StepRecorder(engine)
    engine.step()
    engine.step()
    assert engine.stepped == 2
    (start, end, active, pages, queued, tokens), second = recorder.steps
    assert start <= end
    assert (active, pages, queued, tokens) == (2, 11, 3, 700)
    assert second[3] == 12      # read BEFORE the step it belongs to


def _session(engine):
    session = serve.Session.__new__(serve.Session)
    session.ctx = type("Ctx", (), {"seed": 0})()
    session.engine = engine
    session.params = None
    session.leaves = [(("w",), (2, 2), "served", ("normal", 2))]
    return session


def test_reseed_cancels_what_is_active_and_clears_the_prefix_cache():
    engine = PublicEngine()
    session = _session(engine)
    session.reseed(2**31 + 7)
    assert engine.cancelled == ["a", "b"] and engine.cleared == 1
    assert session.ctx.seed == 2**31 + 7
    assert engine.params is session.params
    assert session.params["w"].shape == (2, 2)


def test_reseed_refuses_an_engine_that_has_not_drained():
    engine = PublicEngine(queued=2)
    with pytest.raises(RuntimeError, match="still queued"):
        _session(engine).reseed(5)
    assert engine.cleared == 0


LAYERS = [("layer_0", 2, 4)]


def _finished():
    return {f"bench-{i}": {"idx": i} for i in (0, 2, 5)}


def test_decisions_are_asked_once_per_finished_request_by_its_id():
    """... through the public method alone, and only for a model that
    declares decisions; what the engine does not give stays None."""
    engine, rows = PublicEngine(), _finished()
    serve.take_decisions(engine, [], rows)
    assert engine.taken == [] and "decisions" not in rows["bench-0"]
    serve.take_decisions(engine, LAYERS, rows)
    assert engine.taken == ["bench-0", "bench-2", "bench-5"]
    assert rows["bench-2"]["decisions"] is None
    assert rows["bench-5"]["decisions"]["layers"]["layer_0"].shape \
        == (3, 2)


def test_an_engine_without_the_method_gives_no_record():
    class Silent(PublicEngine):
        take_decisions = None

    rows = _finished()
    serve.take_decisions(Silent(), LAYERS, rows)
    assert [row["decisions"] for row in rows.values()] == [None] * 3


def test_the_records_control_reroutes_what_was_handed_over():
    rows, sound = _finished(), _finished()
    serve.take_decisions(PublicEngine(), LAYERS, sound)
    serve.take_decisions(PublicEngine(), LAYERS, rows,
                         {"reroute_share": 1.0}, seed=3)
    was = sound["bench-0"]["decisions"]["layers"]["layer_0"]
    now = rows["bench-0"]["decisions"]["layers"]["layer_0"]
    assert (now[:, 0] == was[:, 0]).all()      # the last index alone
    assert (now[:, 1] != was[:, 1]).all()
    assert ((0 <= now) & (now < 4)).all()
    assert rows["bench-2"]["decisions"] is None
