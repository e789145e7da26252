"""The chip's published peaks, keyed by JAX's ``device_kind``
(``bench/peaks.json``, each entry with its source).  A device that is not
in the table is an error, never a default."""
from __future__ import annotations

import json
from pathlib import Path

TABLE = Path(__file__).with_name("peaks.json")


class UnknownDevice(KeyError):
    pass


def peaks(device_kind: str) -> dict:
    table = json.loads(TABLE.read_text())
    if device_kind not in table:
        raise UnknownDevice(f"no peaks for device kind {device_kind!r} in "
                            f"{TABLE.name} (have {sorted(table)})")
    return table[device_kind]
