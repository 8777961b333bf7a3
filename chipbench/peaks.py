"""The table of peaks, keyed by JAX's ``device_kind``."""
from __future__ import annotations

import json
import pathlib

PATH = pathlib.Path(__file__).resolve().parent / "peaks.json"


class UnknownDevice(KeyError):
    pass


def for_kind(kind: str) -> dict:
    """Peaks of one chip of ``kind``; an unknown kind is an error, never a
    default."""
    table = json.loads(PATH.read_text())["devices"]
    if kind not in table:
        raise UnknownDevice(f"no peaks for device kind {kind!r}; known: "
                            f"{sorted(table)}")
    return table[kind]
