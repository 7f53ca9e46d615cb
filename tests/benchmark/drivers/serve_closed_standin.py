"""Kind ``serve-closed-standin`` (tests/benchmark only): the closed
loop of drivers/serve.py around an engine that serves the ROUTED
STAND-IN, because the program serves no routed architecture yet. The
whole of a run is the serve driver's (front end, load generator,
window, the taking of decisions, the check); only the engine is
another: the stand-in's bfloat16 program, one position a step for
every slot, with the engine's public surface and ``take_decisions``.
A routed configuration that the program serves needs neither this file
nor a traffic kind of its own: its cell uses ``serve-closed``."""

from __future__ import annotations

import collections

import jax
import numpy as np

from benchmark import spec
from benchmark.drivers import serve


class StandinEngine:
    """The engine's public surface (as the front end and the serve
    driver use it) over models/routed_standin_program.py::step."""

    paged = prefix_cache = overcommit = draining = False
    preemptions = 0
    page_size = 16
    cache = None            # the driver drops it before the check

    def __init__(self, program, options: dict, params,
                 num_slots: int, max_decode_len: int) -> None:
        self.params = params
        self.num_slots, self.max_decode_len = num_slots, max_decode_len
        self.on_token = self.on_admit = self.on_shed = None
        self.traced_steps = 0
        self._step = program.step
        self._options = options
        self._layers = [f"layer_{i}" for i in range(options["n_layers"])]
        self._state = {
            name: np.array(rows) for name, rows in program.empty_state(
                params, options["n_layers"], num_slots).items()}
        self._queue: collections.deque = collections.deque()
        self._slots: list = [None] * num_slots
        self._records: dict = {}
        self._steps = 0

    # ---- what the front end and the driver call
    def submit(self, request, resumed: bool = False) -> None:
        if len(request.prompt) + request.max_new_tokens > \
                self.max_decode_len:
            raise ValueError(f"request {request.request_id} is longer "
                             f"than {self.max_decode_len}")
        self._queue.append(request)

    def pending(self) -> int:
        return len(self._queue) + len(self.active_request_ids())

    def active_request_ids(self) -> list:
        return [slot["request"].request_id for slot in self._slots
                if slot is not None]

    def cancel(self, request_id: str) -> bool:
        for i, slot in enumerate(self._slots):
            if slot and slot["request"].request_id == request_id:
                self._slots[i] = None
                return True
        return False

    def drain(self) -> list:
        return []

    def cache_lost(self) -> bool:
        return False

    def warmup_buckets(self) -> list:
        return [self.max_decode_len]

    def prefix_cache_clear(self) -> int:
        return 0

    def prefix_stats(self) -> dict:
        return {}

    def slo_stats(self) -> dict:
        return {}

    def occupancy(self) -> dict:
        active = [slot for slot in self._slots if slot is not None]
        return {"slots_active": len(active),
                "slots_total": self.num_slots,
                "queued": len(self._queue),
                "live_tokens": sum(slot["at"] for slot in active),
                "kv_pages_in_use": 0, "kv_pages_total": 1}

    def take_decisions(self, request_id: str):
        """The choices the steps themselves made for a finished
        request, every position of it; handed over once."""
        return self._records.pop(request_id, None)

    def step(self) -> list:
        for i, slot in enumerate(self._slots):
            if slot is None and self._queue:
                request = self._queue.popleft()
                self._slots[i] = {"request": request, "at": 0,
                                  "out": [], "chosen": []}
                for rows in self._state.values():
                    rows[i] = 0
                if self.on_admit:
                    self.on_admit(request.request_id)
        tokens = np.zeros((self.num_slots,), np.int32)
        for i, slot in enumerate(self._slots):
            if slot is not None:
                prompt = slot["request"].prompt
                tokens[i] = (prompt[slot["at"]]
                             if slot["at"] < len(prompt)
                             else slot["out"][-1])
        self._steps += 1
        out, state, chosen = self._step(
            self.params, tokens, self._state,
            jax.random.PRNGKey(self._steps), **self._options)
        self._state = {name: np.array(rows)
                       for name, rows in state.items()}
        out, chosen = np.asarray(out), np.asarray(chosen)
        finished = []
        for i, slot in enumerate(self._slots):
            if slot is None:
                continue
            request = slot["request"]
            slot["chosen"].append(chosen[:, i])
            slot["at"] += 1
            if slot["at"] < len(request.prompt):
                continue
            slot["out"].append(int(out[i]))
            if self.on_token:
                self.on_token(request.request_id, slot["out"][-1],
                              len(slot["out"]) - 1)
            if len(slot["out"]) == request.max_new_tokens:
                rows = np.stack(slot["chosen"])     # [positions, L, k]
                self._records[request.request_id] = {
                    "first": 0, "layers": {
                        name: rows[:, j]
                        for j, name in enumerate(self._layers)}}
                finished.append((request.request_id, slot["out"]))
                self._slots[i] = None
        return finished


def build_engine(model_module, model: dict, params, **overrides):
    program = spec.load_module(spec.ROOT, spec.load_benchmark(),
                               "models/routed_standin_program.py")
    engine_cfg = model["engine"]
    options = model_module.program_model(
        model, model_module.dims(model), engine_cfg, **overrides)
    return StandinEngine(program, options, params,
                         engine_cfg["num_slots"],
                         engine_cfg["max_decode_len"])


def run(ctx) -> dict:
    return serve.run(ctx, build=build_engine)
