"""Reduction from a profiler trace to numbers.

A trace is normalised to ``{"device": {chip: [[name, start_ns, dur_ns],
...]}}``: the events of each chip's operation lines. ``load`` reads that from
the profiler's ``.xplane.pb`` (with nothing but JAX) or from a ``.json``
file of the same shape (the hand-made trace of the tests).
"""

from __future__ import annotations

import glob
import json
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OP_LINES = ("XLA Ops",)  # the lines of a device plane whose events are operations
# control-flow operations span the operations of their bodies: they count
# towards busy time (a union) and never as an operation of their own
CONTAINERS = re.compile(r"^%?(while|conditional|call)([.\d]*)( |$)")


def _planes(path: str) -> list:
    """The planes of an ``.xplane.pb``, or of the newest one under a
    profiler log directory (none if the profiler wrote nothing)."""
    from jax.profiler import ProfileData

    if os.path.isdir(path):
        found = sorted(glob.glob(
            os.path.join(path, "plugins", "profile", "*", "*.xplane.pb")))
        if not found:
            return []
        path = found[-1]
    return list(ProfileData.from_file(path).planes)


def load(path: str) -> dict:
    if path.endswith(".json"):
        with open(path) as f:
            return json.load(f)
    device: dict = {}
    for plane in _planes(path):
        m = DEVICE_PLANE.match(plane.name)
        if not m:
            continue
        events = device.setdefault(m.group(1), [])
        for line in plane.lines:
            if line.name in OP_LINES:
                events.extend([ev.name, float(ev.start_ns), float(ev.duration_ns)]
                              for ev in line.events)
    return {"device": device}


def union_ns(intervals) -> float:
    """Total length covered by ``(start, duration)`` intervals."""
    total, end = 0.0, None
    for s, d in sorted(intervals):
        e = s + d
        if end is None or s > end:
            total += d
            end = e
        elif e > end:
            total += e - end
            end = e
    return total


def busy_seconds(trace: dict) -> float | None:
    """Seconds in which an operation ran on the device: the union of the
    operation intervals, averaged over the chips. None without a device."""
    chips = [evs for evs in trace["device"].values() if evs]
    if not chips:
        return None
    return sum(union_ns((s, d) for _, s, d in evs) for evs in chips) / len(chips) / 1e9


def kernel_seconds(trace: dict, pattern: str) -> float | None:
    """Summed device time of the events whose name matches ``pattern``,
    averaged over the chips. None where no event matches."""
    rx = re.compile(pattern)
    chips = [evs for evs in trace["device"].values() if evs]
    per = [sum(d for name, _, d in evs if rx.search(name)) for evs in chips]
    if not per or not any(per):
        return None
    return sum(per) / len(per) / 1e9


def short(name: str, most: int = 120) -> str:
    """An operation's name without the rest of its HLO text: the result's
    name, its shape and the operation, cut to ``most`` characters."""
    return name if len(name) <= most else name[: most - 3] + "..."


def _leaves(trace: dict):
    for evs in trace["device"].values():
        for name, s, d in evs:
            if not CONTAINERS.match(name):
                yield name, s, d


def device_ops(trace: dict, k: int = 10) -> list:
    """The ``k`` operations with the most device time, summed by name."""
    n = max(len(trace["device"]), 1)
    tot: dict = {}
    for name, _, d in _leaves(trace):
        tot[name] = tot.get(name, 0.0) + d / 1e9 / n
    return [[short(name), s] for name, s in sorted(tot.items(), key=lambda r: -r[1])[:k]]


def idle_gaps(trace: dict, wall_s: float, where: str, k: int = 10) -> list:
    """The ``k`` longest idle gaps of the first chip, each named by the
    benchmark's span it falls in (``where``) and the operations around it;
    what the call's wall leaves before the first and after the last
    operation comes first."""
    chips = sorted(trace["device"].items())
    if not chips or not chips[0][1]:
        return []
    evs = sorted(chips[0][1], key=lambda e: e[1])
    gaps, end, last = [], None, None
    for name, s, d in evs:
        if end is not None and s > end:
            gaps.append([f"{where}: after {short(last, 60)} before {short(name, 60)}",
                         (s - end) / 1e9])
        if end is None or s + d > end:
            end, last = s + d, name
    edge = wall_s - (end - evs[0][1]) / 1e9
    gaps.sort(key=lambda g: -g[1])
    return [[f"{where}: before the first or after the last device operation",
             max(edge, 0.0)]] + gaps[: k - 1]
