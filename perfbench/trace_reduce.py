"""From a profiler trace to the numbers the per-layer readers take.

A trace is first flattened to events ``(plane, line, name, start_ns,
dur_ns)`` (:func:`read_xplane`); everything after that is plain
arithmetic on those tuples, so it is tested on a small recorded trace
(``tests/data``) without a chip.

Device planes are the profiler's ``/device:TPU:<n>`` planes; the work on
them is the events of their ``XLA Ops`` line.  A device is busy while
one of those runs: the union of their intervals.  Host planes start with
``/host:``; their events say what the host was doing in each idle gap.
"""

from __future__ import annotations

import glob
import gzip
import json
import os
from collections import defaultdict
from typing import Dict, Iterable, List, Sequence, Tuple

Event = Tuple[str, str, str, float, float]

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
HOST_PREFIX = "/host:"
COLLECTIVES = ("collective-permute", "all-gather", "all-reduce", "all-to-all",
               "reduce-scatter")


def read_xplane(logdir: str) -> List[Event]:
    """Every event of the one ``.xplane.pb`` under ``logdir``."""
    from jax.profiler import ProfileData

    paths = glob.glob(os.path.join(logdir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if len(paths) != 1:
        raise FileNotFoundError(f"expected one xplane.pb under {logdir}, "
                                f"found {len(paths)}")
    events = []
    for plane in ProfileData.from_file(paths[0]).planes:
        for line in plane.lines:
            for ev in line.events:
                events.append((plane.name, line.name, ev.name,
                               float(ev.start_ns), float(ev.duration_ns)))
    return events


def load_events(path: str) -> List[Event]:
    """Events saved as gzipped JSON, one ``[plane, line, name, start_ns,
    dur_ns]`` list each (the tests' recorded trace)."""
    with gzip.open(path, "rt") as f:
        return [tuple(e) for e in json.load(f)]


def device_ops(events: Iterable[Event]) -> Dict[str, List[Event]]:
    """Device plane -> its op events, in start order."""
    out: Dict[str, List[Event]] = defaultdict(list)
    for e in events:
        if e[0].startswith(DEVICE_PREFIX) and e[1] == OPS_LINE:
            out[e[0]].append(e)
    for evs in out.values():
        evs.sort(key=lambda e: e[3])
    return dict(out)


def union(intervals: Iterable[Tuple[float, float]]) -> List[Tuple[float, float]]:
    """Merged ``(start, end)`` intervals, in order."""
    merged: List[List[float]] = []
    for s, t in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], t)
        else:
            merged.append([s, t])
    return [(s, t) for s, t in merged]


def busy_s(ops: Dict[str, List[Event]]) -> float:
    """Seconds in which an op ran, averaged over the devices traced."""
    if not ops:
        return 0.0
    total = 0.0
    for evs in ops.values():
        total += sum(t - s for s, t in union((e[3], e[3] + e[4]) for e in evs))
    return total / len(ops) / 1e9


def op_seconds(ops: Dict[str, List[Event]], match) -> float:
    """Summed device seconds of the ops whose name ``match`` accepts, over
    all devices."""
    return sum(e[4] for evs in ops.values() for e in evs if match(e[2])) / 1e9


def is_collective(name: str) -> bool:
    low = name.lower()
    return any(c in low for c in COLLECTIVES)


def top_ops(ops: Dict[str, List[Event]], n: int = 10) -> List[List]:
    """The ``n`` op names that took most device time: ``[name, seconds]``,
    seconds averaged over the devices."""
    per = defaultdict(float)
    for evs in ops.values():
        for e in evs:
            per[e[2]] += e[4]
    ndev = max(len(ops), 1)
    ranked = sorted(per.items(), key=lambda kv: -kv[1])[:n]
    return [[name, ns / ndev / 1e9] for name, ns in ranked]


def idle_gaps(events: Sequence[Event], ops: Dict[str, List[Event]],
              n: int = 10) -> List[List]:
    """The ``n`` longest idle gaps of the first device, each named by the
    host event that overlaps it most among those no longer than four
    times the gap (the longest-overlapping one where none is that short):
    ``[host event name, gap seconds]``."""
    if not ops:
        return []
    first = ops[sorted(ops)[0]]
    busy = union((e[3], e[3] + e[4]) for e in first)
    gaps = [(t, s2) for (_, t), (s2, _) in zip(busy, busy[1:]) if s2 > t]
    gaps.sort(key=lambda g: g[0] - g[1])
    host = [e for e in events if e[0].startswith(HOST_PREFIX) and e[4] > 0]
    out = []
    for lo, hi in gaps[:n]:
        length = hi - lo
        best, best_key = "host: nothing traced", None
        for e in host:
            ov = min(hi, e[3] + e[4]) - max(lo, e[3])
            if ov <= 0:
                continue
            key = (e[4] <= 4 * length, ov, -e[4])
            if best_key is None or key > best_key:
                best, best_key = e[2], key
        out.append([best, length / 1e9])
    return out


def reduce(events: Sequence[Event]) -> dict:
    """The trace numbers every reader may use."""
    ops = device_ops(events)
    return {
        "devices": len(ops),
        "ops": ops,
        "busy_s": busy_s(ops),
        "collective_s": op_seconds(ops, is_collective) / max(len(ops), 1),
        "top_ops": top_ops(ops),
        "idle_gaps": idle_gaps(events, ops),
    }
