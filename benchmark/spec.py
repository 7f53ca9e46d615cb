"""Reading BENCHMARK.json and the data files it names.

Everything that belongs to one configuration, one traffic mix or one
per-layer metric is a file of its own, found by the NAME in
BENCHMARK.json: ``configs`` entries carry their ``file``; a cell's
``traffic`` is ``<path>/traffic/<traffic>.json``; a per-layer metric is
``<path>/layer_metrics/<name>.json``. A configuration's MODEL is found
the same way: its file names a module under ``model_module``, which is
``<path>/models/<model_module>.py`` (``load_model``), and that module
is all the harness knows about an architecture. A later PR adds files
and entries and edits none of this code."""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import pathlib
import re
from typing import Optional

ROOT = pathlib.Path(__file__).resolve().parent.parent
NAME_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT_RE = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
# what a model module has: the sizes from the file's published keys;
# the parameter tree as a list of leaves with their rules; the
# program's model object; the float32 reference's logits
MODEL_FUNCTIONS = ("dims", "param_leaves", "program_model",
                   "teacher_forced_logits")
# ... and what it MAY have: the layers that make a discrete choice per
# position (top-k routed experts), which the check then takes from the
# timed path and has the reference judge (benchmark/check.py)
DECISIONS = "decision_layers"


class SpecError(ValueError):
    pass


def load_benchmark(root: pathlib.Path = ROOT) -> dict:
    with open(root / "BENCHMARK.json", encoding="utf-8") as fh:
        return json.load(fh)


def _find(root: pathlib.Path, bench: dict, relative: str
          ) -> pathlib.Path:
    for path in bench["paths"]:
        candidate = root / path / relative
        if candidate.is_file():
            return candidate
    raise SpecError(
        f"{relative} not found under any of paths={bench['paths']}")


def load_module(root: pathlib.Path, bench: dict, relative: str):
    """The Python file ``relative`` found under ``paths`` (a driver, a
    reader, a kernel's work function, a model), loaded by path: such
    modules are found by name from data, never imported by name."""
    source = _find(root, bench, relative)
    module_spec = importlib.util.spec_from_file_location(
        "benchmark_" + re.sub(r"\W", "_", relative[:-3]), source)
    module = importlib.util.module_from_spec(module_spec)
    module_spec.loader.exec_module(module)
    return module


def load_model(config: dict, root: pathlib.Path = ROOT,
               bench: Optional[dict] = None):
    """The model module a configuration file names under
    ``model_module``: ``<path>/models/<model_module>.py``, with every
    one of MODEL_FUNCTIONS. There is no default: a file without the
    key, a module that is not there and a module that lacks a function
    are each a SpecError."""
    bench = bench or load_benchmark(root)
    name = config.get("model_module")
    if not isinstance(name, str) or not NAME_RE.match(name):
        raise SpecError(
            f"the configuration file has no model_module (a name under "
            f"models/): {name!r}")
    module = load_module(root, bench, f"models/{name}.py")
    missing = [fn for fn in MODEL_FUNCTIONS
               if not callable(getattr(module, fn, None))]
    if missing:
        raise SpecError(f"models/{name}.py lacks {missing}")
    return module


def decision_layers(module, config: dict, dims: dict) -> list:
    """[(layer name, k, n)]: the layers of a configuration's model
    that choose k of n per position, as its module declares them with
    ``decision_layers(config, dims)``; [] for a module that has no
    such function. A declaration of another shape is a SpecError."""
    declare = getattr(module, DECISIONS, None)
    if declare is None:
        return []
    declared = declare(config, dims)
    layers = []
    try:
        for name, k, n in declared:
            if not (isinstance(name, str) and NAME_RE.match(name)
                    and isinstance(k, int) and isinstance(n, int)
                    and 1 <= k < n):
                raise ValueError
            layers.append((name, k, n))
    except (TypeError, ValueError):
        raise SpecError(
            f"{DECISIONS} has to give [(layer name, k, n)] with "
            f"1 <= k < n, not {declared!r}") from None
    if not layers or len({name for name, _k, _n in layers}) != \
            len(layers):
        raise SpecError(f"{DECISIONS} gives no layer, or one twice: "
                        f"{declared!r}")
    return layers


def _check_of_decisions(config: dict) -> list:
    """What a configuration whose module declares decisions has to
    state under ``check``: from where a slack counts as rejected, and
    the limit on the share that is."""
    section = config.get("check", {})
    slack_from = section.get("slack_from")
    problems = []
    if isinstance(slack_from, bool) or not isinstance(
            slack_from, (int, float)) or slack_from < 0:
        problems.append(f"check.slack_from is not a number >= 0: "
                        f"{slack_from!r}")
    if "routing_rejected_share" not in section.get("limits", {}):
        problems.append("check.limits has no routing_rejected_share")
    return problems


def _load_json(path: pathlib.Path) -> dict:
    with open(path, encoding="utf-8") as fh:
        return json.load(fh)


@dataclasses.dataclass
class Cell:
    """One entry of ``workloads`` with its files resolved."""
    name: str
    chips: int
    why: str
    config_name: str
    config: dict            # the configuration file
    traffic_name: str
    traffic: dict           # the traffic / job file
    end_to_end: list        # metric entries this cell reports
    per_layer: list         # per-layer entries read in this cell

    @property
    def kind(self) -> str:
        return self.traffic["kind"]


def metric_applies(metric: dict, cell_name: str,
                   cell_end_to_end: Optional[set] = None) -> bool:
    """An entry with a ``workloads`` key applies to the cells it lists;
    one without applies to every cell (end-to-end), or to every cell
    that reports the metric it ``moves`` (per-layer)."""
    if "workloads" in metric:
        return cell_name in metric["workloads"]
    if "moves" in metric and cell_end_to_end is not None:
        return metric["moves"] in cell_end_to_end
    return True


def load_config(name: str, root: pathlib.Path = ROOT,
                bench: Optional[dict] = None) -> dict:
    """The configuration file of the ``configs`` entry ``name``."""
    bench = bench or load_benchmark(root)
    entries = [c for c in bench["configs"] if c["name"] == name]
    if not entries:
        raise SpecError(f"configuration {name!r} is not in "
                        f"BENCHMARK.json")
    return _load_json(root / entries[0]["file"])


def load_cell(name: str, root: pathlib.Path = ROOT,
              bench: Optional[dict] = None) -> Cell:
    bench = bench or load_benchmark(root)
    entries = [w for w in bench["workloads"] if w["name"] == name]
    if not entries:
        raise SpecError(
            f"workload {name!r} is not in BENCHMARK.json (has: "
            f"{[w['name'] for w in bench['workloads']]})")
    entry = entries[0]
    config = load_config(entry["config"], root, bench)
    traffic = _load_json(
        _find(root, bench, f"traffic/{entry['traffic']}.json"))
    end_to_end = [m for m in bench["end_to_end"]
                  if metric_applies(m, name)]
    reported = {m["name"] for m in end_to_end}
    per_layer = [m for m in bench["per_layer"]
                 if metric_applies(m, name, reported)]
    return Cell(name=name, chips=int(entry["chips"]), why=entry["why"],
                config_name=entry["config"], config=config,
                traffic_name=entry["traffic"], traffic=traffic,
                end_to_end=end_to_end, per_layer=per_layer)


def layer_metric_file(name: str, root: pathlib.Path = ROOT,
                      bench: Optional[dict] = None) -> dict:
    bench = bench or load_benchmark(root)
    return _load_json(_find(root, bench, f"layer_metrics/{name}.json"))


def validate(root: pathlib.Path = ROOT) -> list[str]:
    """Every rule of the contract a file check can hold BENCHMARK.json
    to, as a list of complaints (empty = sound)."""
    bench = load_benchmark(root)
    problems: list[str] = []
    want = {"command", "paths", "run_seconds", "configs", "workloads",
            "end_to_end", "per_layer"}
    if set(bench) != want:
        problems.append(f"top-level keys {sorted(bench)} != "
                        f"{sorted(want)}")
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [e["name"] for e in bench[group]]
        if len(names) != len(set(names)):
            problems.append(f"duplicate name in {group}")
        for entry_name in names:
            if not NAME_RE.match(entry_name):
                problems.append(f"bad name {entry_name!r}")
    metric_names = [m["name"] for m in
                    bench["end_to_end"] + bench["per_layer"]]
    if len(metric_names) != len(set(metric_names)):
        problems.append("a metric name is used twice")
    for entry in bench["workloads"] + bench["configs"]:
        if not 1 <= len(entry["why"]) <= 200 or "\n" in entry["why"]:
            problems.append(f"{entry['name']}: why is not 1 to 200 "
                            f"characters on one line")
    for metric in bench["per_layer"]:
        if not 1 <= len(metric["layer"]) <= 200:
            problems.append(f"{metric['name']}: layer")
    for metric in bench["end_to_end"] + bench["per_layer"]:
        if not UNIT_RE.match(metric["unit"]):
            problems.append(f"bad unit {metric['unit']!r}")
        if metric["better"] not in ("lower", "higher"):
            problems.append(f"{metric['name']}: better")
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    if "setup_s" not in e2e:
        problems.append("no setup_s")
    for metric in bench["end_to_end"]:
        if metric["source"] not in ("host_clock", "device_trace"):
            problems.append(f"{metric['name']}: source")
        if not 0 < metric["bound"] <= 0.1:
            problems.append(f"{metric['name']}: bound")
    config_names = {c["name"] for c in bench["configs"]}
    for config in bench["configs"]:
        if not (root / config["file"]).is_file():
            problems.append(f"missing {config['file']}")
            continue
        try:
            data = load_config(config["name"], root, bench)
            module = load_model(data, root, bench)
            if decision_layers(module, data, module.dims(data)):
                problems.extend(f"{config['name']}: {problem}" for
                                problem in _check_of_decisions(data))
        except SpecError as exc:
            problems.append(f"{config['name']}: {exc}")
    cells = {}
    for workload in bench["workloads"]:
        if workload["config"] not in config_names:
            problems.append(f"{workload['name']}: unknown config")
            continue
        try:
            cells[workload["name"]] = load_cell(
                workload["name"], root, bench)
        except (SpecError, OSError) as exc:
            problems.append(str(exc))
    used = {w["config"] for w in bench["workloads"]}
    for unused in config_names - used:
        problems.append(f"config {unused} is used by no cell")
    for name, cell in cells.items():
        reported = {m["name"] for m in cell.end_to_end}
        if "setup_s" not in reported or len(reported) < 2:
            problems.append(f"{name}: needs setup_s and one more")
        if not cell.per_layer:
            problems.append(f"{name}: no per-layer metric")
    for metric in bench["per_layer"]:
        if metric["moves"] not in e2e:
            problems.append(f"{metric['name']}: moves an unknown "
                            f"metric {metric['moves']!r}")
            continue
        for cell_name in metric.get("workloads", []):
            if cell_name not in cells:
                problems.append(f"{metric['name']}: unknown cell "
                                f"{cell_name}")
            elif metric["moves"] not in {
                    m["name"] for m in cells[cell_name].end_to_end}:
                problems.append(
                    f"{metric['name']}: {cell_name} does not report "
                    f"{metric['moves']}")
        try:
            layer_metric_file(metric["name"], root, bench)
        except SpecError as exc:
            problems.append(str(exc))
    return problems
