"""The published peaks of a chip, by the ``device_kind`` JAX reports."""
from __future__ import annotations

import json
import os

PATH = os.path.join(os.path.dirname(os.path.abspath(__file__)), "peaks.json")


def peaks_for(kind: str, path: str = PATH) -> dict:
    """The peaks of ``kind``; a kind the table lacks is an error, never
    a default."""
    with open(path) as f:
        table = json.load(f)["devices"]
    if kind not in table:
        raise KeyError(f"no peaks for device kind {kind!r} in {path}; "
                       f"known: {sorted(table)}")
    return table[kind]
