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
            load_model(load_config(config["name"], root, bench), root,
                       bench)
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
