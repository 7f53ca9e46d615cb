"""The table of hardware peaks, keyed by jax's device_kind. A device
that is not in the table is an error, never a default."""

from __future__ import annotations

import json
import pathlib

_TABLE = pathlib.Path(__file__).resolve().parent / "peaks.json"


class UnknownDevice(RuntimeError):
    pass


def load() -> dict:
    with open(_TABLE, encoding="utf-8") as fh:
        return json.load(fh)["devices"]


def for_device_kind(kind: str) -> dict:
    table = load()
    if kind not in table:
        raise UnknownDevice(
            f"device_kind {kind!r} is not in {_TABLE.name} "
            f"(known: {sorted(table)}); add its published peaks with "
            f"their source before benchmarking on it")
    return dict(table[kind])
