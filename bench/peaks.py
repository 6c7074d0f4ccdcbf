"""Per-chip peaks for roofline readers, keyed by JAX's ``device_kind``
(``bench/peaks.json``, with its source).  A kind that is not in the
table is an error, never a default."""

from __future__ import annotations

import json
import os
from typing import Dict

__all__ = ["peaks"]

_TABLE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                      "peaks.json")


def peaks(device_kind: str) -> Dict[str, float]:
    with open(_TABLE) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"bench/peaks.json (known: {sorted(table)})")
    return dict(table[device_kind])
