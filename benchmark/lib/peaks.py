"""The table of peaks (benchmark/peaks.json), keyed by device_kind."""

from __future__ import annotations

import json
from pathlib import Path


def peaks_of(device_kind: str) -> dict:
    table = json.loads((Path(__file__).resolve().parent.parent
                        / "peaks.json").read_text())["peaks"]
    if device_kind not in table:
        raise KeyError(f"no peaks recorded for device kind {device_kind!r}; "
                       f"known: {sorted(table)}")
    return table[device_kind]
