"""The table of peaks that operations and bytes are divided by, keyed by
the device kind JAX reports.  What a model's work needs is counted by its
family (``families/<family>.py``)."""
from __future__ import annotations

import json
import os

PEAKS_FILE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "peaks.json")


def peaks(device_kind: str, path: str = PEAKS_FILE) -> dict:
    """The peak row for ``device_kind``; an unknown kind raises."""
    with open(path) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in {path}; "
                       f"known: {sorted(table)}")
    return table[device_kind]
