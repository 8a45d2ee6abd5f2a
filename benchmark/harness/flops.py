"""The table of peaks.  The operations and bytes a model's mathematics
needs are its family's to count (families/<family>/counts.py)."""

from __future__ import annotations

import json
import os


def peak(device_kind: str) -> dict:
    with open(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "peaks.json")) as f:
        table = json.load(f)["peaks"]
    if device_kind not in table:
        raise KeyError(f"no peak for device kind {device_kind!r}: a device "
                       f"that is not in peaks.json is an error, not a "
                       f"default (has: {sorted(table)})")
    return table[device_kind]
