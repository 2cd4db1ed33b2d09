"""The program's ``pixie.*`` spans in a traced window, and the numbers
the span readers take from them.

The program records a ``jax.profiler.TraceAnnotation`` span at each step
of a served flush (``pixie.flush``, ``pixie.canvas_wait``,
``pixie.embed``, ``pixie.ship``, ``pixie.execute``, ...).  They are host
events of the profiler's own trace, on the clock the device's ops are
recorded on, so a span can be laid against the device's work.  A program
without them (an older checkout) gives no spans, and every reader then
returns None.

Like :mod:`trace_reduce`, everything here is arithmetic on flattened
``(plane, line, name, start_ns, dur_ns)`` events, tested on hand-built
lists without a chip.
"""

from __future__ import annotations

import sys
from typing import List, Optional, Sequence, Tuple

import numpy as np

import trace_reduce

PREFIX = "pixie."
#: How the device trace names a Pallas kernel: the op's HLO text holds
#: ``custom_call_target="tpu_custom_call"`` whatever the kernel is called.
KERNEL = 'custom_call_target="tpu_custom_call"'

#: ``(name, thread line, start_ns, dur_ns)``, the name without the
#: ``#key=value,...#`` arguments a span may carry.
Span = Tuple[str, str, float, float]


def strip_args(name: str) -> str:
    return name.split("#", 1)[0]


def spans(events: Sequence[trace_reduce.Event]) -> List[Span]:
    """The host events whose name starts with ``pixie.``, in start
    order."""
    out = [(strip_args(e[2]), e[1], e[3], e[4]) for e in events
           if e[0].startswith(trace_reduce.HOST_PREFIX)
           and e[2].startswith(PREFIX)]
    out.sort(key=lambda s: s[2])
    return out


def window_events(ctx) -> Optional[Sequence[trace_reduce.Event]]:
    """The traced window's events, or None where the run was not traced.

    ``run.py`` hands its readers the trace's reduction (``ctx.trace``),
    which keeps no host events; the flattened events stay alive in
    ``run_cell``'s frame while its readers run, so they are read from
    there."""
    if ctx.trace is None:
        return None
    frame = sys._getframe(1)
    while frame is not None:
        if frame.f_code.co_name == "run_cell":
            return frame.f_locals.get("events")
        frame = frame.f_back
    return None


def window_spans(ctx) -> List[Span]:
    events = window_events(ctx)
    return spans(events) if events is not None else []


def per_execute_ms(found: Sequence[Span], name: str) -> Optional[float]:
    """Milliseconds in spans ``name``, summed, over the number of
    ``pixie.execute`` spans (one per dispatch)."""
    n = sum(1 for s in found if s[0] == "pixie.execute")
    if not n:
        return None
    return sum(s[3] for s in found if s[0] == name) / n / 1e6


def first_device_ops(ops: dict) -> List[trace_reduce.Event]:
    return ops[sorted(ops)[0]] if ops else []


def device_queue_ms(found: Sequence[Span],
                    ops: dict) -> Optional[List[float]]:
    """Per dispatch, its kernel's start on the device minus the end of its
    ``pixie.execute`` span, in ms.  Spans and kernels are paired in order
    from the end of the trace: the trace stops only after every answer
    was read back, so the last span owns the last kernel, and kernels
    enqueued before tracing began stay unpaired.  None where there are
    fewer kernels than spans, or no spans."""
    execs = [s for s in found if s[0] == "pixie.execute"]
    kernels = sorted(e[3] for e in first_device_ops(ops) if KERNEL in e[2])
    if not execs or len(kernels) < len(execs):
        return None
    pairs = zip(reversed(execs), reversed(kernels))
    return [(k - (s[2] + s[3])) / 1e6 for s, k in pairs][::-1]


def idle_inside(found: Sequence[Span], ops: dict,
                name: str = "pixie.flush") -> Optional[float]:
    """Of the first device's idle time between its ops, the share (0-1)
    that falls inside a ``name`` span.  None without such spans or
    without idle time."""
    within = trace_reduce.union((s[2], s[2] + s[3]) for s in found
                                if s[0] == name)
    busy = trace_reduce.union((e[3], e[3] + e[4])
                              for e in first_device_ops(ops))
    gaps = [(t, s2) for (_, t), (s2, _) in zip(busy, busy[1:]) if s2 > t]
    idle = sum(hi - lo for lo, hi in gaps)
    if not within or idle <= 0:
        return None
    inside = 0.0
    for lo, hi in gaps:
        for s, t in within:
            inside += max(0.0, min(hi, t) - max(lo, s))
    return inside / idle


def p95(values: Optional[Sequence[float]]) -> Optional[float]:
    return float(np.percentile(values, 95)) if values else None
