"""Published peaks of each device the benchmark runs on, keyed by the
``device_kind`` that JAX reports.  A device missing from the table is
an error: a utilisation against a guessed peak means nothing."""

from __future__ import annotations

import json
import pathlib

TABLE = pathlib.Path(__file__).resolve().parent / "peaks.json"


class UnknownDevice(KeyError):
    """The device kind has no entry in ``peaks.json``."""


def peaks(device_kind: str, table: pathlib.Path = TABLE) -> dict:
    known = json.loads(table.read_text())
    if device_kind not in known:
        raise UnknownDevice(
            f"no published peaks for device kind {device_kind!r}; known: "
            f"{sorted(known)} (add it to {table.name} with its source)")
    return known[device_kind]
