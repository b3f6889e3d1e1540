"""The chip's published peaks, keyed by ``device_kind`` (``peaks.json``).
A kind that is not in the table is an error, never a default."""

from __future__ import annotations

import json
from pathlib import Path
from typing import Dict

TABLE = Path(__file__).resolve().parent / "peaks.json"


def lookup(device_kind: str) -> Dict[str, float]:
    table = json.loads(TABLE.read_text())
    if device_kind not in table:
        raise KeyError(f"no published peaks for device kind {device_kind!r} "
                       f"in {TABLE.name}; known: {sorted(table)}")
    return table[device_kind]
