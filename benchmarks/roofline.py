"""The chip's peaks, and the least time a chip could take for a count of
bytes and operations.

What is counted is the family's (`benchmarks/families/<family>/roofline.py`:
the bytes and operations of the decode steps of a traced span, from the
configuration's shapes, and at which of the chip's peaks the operations
run). Here: the table of peaks and the division. The least time is a
floor: it is the larger of the bytes over the memory's rate and the
operations over their peak, as if the two overlapped wholly.
"""

from __future__ import annotations

import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))


def peaks(device_kind: str) -> dict:
    """The table's row for `device_kind`; a kind not in it is an error."""
    with open(os.path.join(HERE, "peaks.json"), encoding="utf-8") as fh:
        table = json.load(fh)
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}: "
                       f"benchmarks/peaks.json has {sorted(table)}")
    return table[device_kind]


def least_seconds(cost: dict, device_kind: str) -> dict:
    """The least time for a family's `cost` (`bytes`, `ops`, and `ops_peak`,
    the key of the peak its operations run at) on a chip of `device_kind`,
    and which bound it is. Whatever else the family put into `cost` is
    handed on."""
    pk = peaks(device_kind)
    by_bytes = cost["bytes"] / pk["hbm_bytes_per_s"]
    by_ops = cost["ops"] / pk[cost["ops_peak"]]
    return dict(cost, seconds=max(by_bytes, by_ops),
                bound="memory" if by_bytes >= by_ops else "compute")
