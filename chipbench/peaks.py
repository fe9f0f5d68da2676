"""The table of peaks. A device that is not in it is an error, never a
default: every share of a peak divides by a number read here."""

from __future__ import annotations

import json
import os

_PATH = os.path.join(os.path.dirname(os.path.abspath(__file__)), "peaks.json")


def peaks_for(device_kind: str) -> dict:
    with open(_PATH) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(f"no published peaks for device kind {device_kind!r} "
                       f"in {_PATH}; known: {sorted(table)}")
    return table[device_kind]
