"""The reduction from a profiler trace to the per-layer numbers: on
hand-made events, and on a 0.45 s slice of a trace recorded on one TPU
v5e while the sobel45 cell served 1080p frames (device op events, and
host events of 0.2 ms or longer)."""

from pathlib import Path

import pytest

import trace_reduce as tr

DATA = Path(__file__).resolve().parent / "data" / "sobel45_trace_slice.json.gz"
DEV0, DEV1 = "/device:TPU:0", "/device:TPU:1"
KERNEL = 'custom_call_target="tpu_custom_call"'


def ev(plane, name, start_ms, dur_ms, line=tr.OPS_LINE):
    return (plane, line, name, start_ms * 1e6, dur_ms * 1e6)


HAND = [
    ev(DEV0, "%fn.1 = custom-call(...), " + KERNEL, 0, 10),
    ev(DEV0, "%copy.1 = copy(...)", 5, 10),           # overlaps the kernel
    ev(DEV0, "%fn.1 = custom-call(...), " + KERNEL, 30, 10),
    ev(DEV0, "%collective-permute-start.2 = ...", 45, 5),
    ev(DEV0, "jit_fn(1)", 0, 50, line="XLA Modules"),  # another line: ignored
    ev(DEV1, "%fn.1 = custom-call(...), " + KERNEL, 0, 20),
    ev(DEV1, "%all-gather.3 = ...", 20, 3),
    ev("/host:CPU", "TransferToDevice", 16, 12, line="worker"),
    ev("/host:CPU", "Thread run", 0, 100, line="worker"),
    ev("/host:CPU", "short", 41, 2, line="worker"),
]


def test_union_merges_overlaps_and_keeps_gaps():
    assert tr.union([(5, 15), (0, 10), (30, 40), (40, 45)]) == [
        (0, 15), (30, 45)]


def test_busy_is_the_union_of_op_intervals_averaged_over_devices():
    ops = tr.device_ops(HAND)
    assert sorted(ops) == [DEV0, DEV1]
    # TPU:0 busy [0,15) + [30,40) + [45,50) = 30 ms; TPU:1 [0,23) = 23 ms.
    assert tr.busy_s(ops) == pytest.approx((30 + 23) / 2 / 1e3)


def test_kernel_and_collective_seconds():
    ops = tr.device_ops(HAND)
    assert tr.op_seconds(ops, lambda n: KERNEL in n) == pytest.approx(0.040)
    r = tr.reduce(HAND)
    assert r["collective_s"] == pytest.approx((5 + 3) / 2 / 1e3)
    assert r["top_ops"][0] == [HAND[0][2], pytest.approx(0.020)]


def test_idle_gaps_are_named_by_the_host_event_that_overlaps_them():
    gaps = tr.idle_gaps(HAND, tr.device_ops(HAND))
    # TPU:0 idle [15,30) and [40,45).  The transfer covers 12 of the first
    # gap's 15 ms; the thread-long event is far longer than the gap.
    assert gaps == [["TransferToDevice", pytest.approx(0.015)],
                    ["short", pytest.approx(0.005)]]


def test_recorded_trace_slice():
    events = tr.load_events(str(DATA))
    r = tr.reduce(events)
    assert r["devices"] == 1
    kernel = [e for e in r["ops"][DEV0] if KERNEL in e[2]]
    assert len(kernel) == 2
    kernel_s = tr.op_seconds(r["ops"], lambda n: KERNEL in n)
    assert kernel_s == pytest.approx(0.210981608)
    lo = min(e[3] for e in r["ops"][DEV0])
    hi = max(e[3] + e[4] for e in r["ops"][DEV0])
    assert kernel_s <= r["busy_s"] <= (hi - lo) / 1e9
    assert r["top_ops"][0][0] == kernel[0][2]
    gaps = r["idle_gaps"]
    assert gaps and all(g[1] > 0 for g in gaps)
    assert gaps == sorted(gaps, key=lambda g: -g[1])
    assert gaps[0][0] == "$fleet.py:647 _canvas"
