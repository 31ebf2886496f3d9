"""Published peaks of the chips the benchmark runs on (``peaks.json``)."""

from __future__ import annotations

import json
import os

PEAKS = os.path.join(os.path.dirname(os.path.abspath(__file__)), "peaks.json")


def peak(device_kind: str) -> dict:
    """``flops_per_s``, ``hbm_bytes_per_s`` and ``hbm_bytes`` of one chip
    of ``device_kind``; a kind the table lacks is an error."""
    with open(PEAKS) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(f"no published peaks for device kind {device_kind!r}; "
                       f"bench/peaks.json has {sorted(table)}")
    return table[device_kind]
