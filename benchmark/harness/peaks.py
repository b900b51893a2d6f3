"""The table of peaks, keyed by ``device_kind``. A device that is not in it
is an error, not a default."""

from __future__ import annotations

import json
import os

_PATH = os.path.join(os.path.dirname(os.path.abspath(__file__)), "peaks.json")


def peaks_for(device_kind: str) -> dict:
    with open(_PATH) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise SystemExit(
            f"benchmark: no peaks for device kind {device_kind!r} in "
            f"{_PATH} (it has {sorted(table)}); the benchmark measures the "
            "chip and runs on nothing else (--rehearse checks the plumbing "
            "and writes no device metric)")
    return table[device_kind]


def least_seconds(work: dict, peaks: dict) -> tuple[float, str]:
    """The least time the chip could take for ``work`` (``flops`` and
    ``bytes`` that the algorithm needs), and which bound binds."""
    by_flops = work["flops"] / peaks["flops_per_s_bf16"]
    by_bytes = work["bytes"] / peaks["hbm_bytes_per_s"]
    return (by_flops, "flops") if by_flops > by_bytes else (by_bytes, "bytes")
