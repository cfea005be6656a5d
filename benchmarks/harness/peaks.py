"""Published peaks, keyed by ``device_kind`` (``benchmarks/peaks.json``)."""

from __future__ import annotations

import json
import os

_PATH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "peaks.json")


def peaks_for(device_kind: str) -> dict:
    with open(_PATH) as f:
        table = json.load(f)
    row = table.get(device_kind)
    if not isinstance(row, dict):
        raise KeyError(
            f"no peaks for device_kind {device_kind!r} in {_PATH}: add a row "
            f"with its source; a device outside the table is an error")
    return row
