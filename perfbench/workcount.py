"""The useful work of a request, counted on the algorithm, not on an
implementation, so that a roofline share reads the same whatever kernel
serves it.

Ops: the operations of the app's dataflow graph per pixel, times the
frame's real pixels.  A 3x3 correlation is 9 multiplies and an adder tree
of 8 adds, plus one division where the app normalises (gauss3: 18); a
threshold is one comparison.  A chain sums its stages.  Bytes: the frame
read once and the output written once, at the grid's item size; a chain's
intermediates never need to leave the chip, so they count nothing.

``roofline_s`` is the least time a chip could take for the work: the
larger of ops over the peak integer vector rate and bytes over the peak
HBM bandwidth (``peaks.json``, keyed by JAX's ``device_kind``).
"""

from __future__ import annotations

import json
from pathlib import Path
from typing import Sequence, Tuple, Union

from reference import STENCILS

PEAKS_FILE = Path(__file__).resolve().parent / "peaks.json"


def ops_per_pixel(app: str) -> int:
    if app == "threshold":
        return 1
    _, divisor = STENCILS[app]
    return 9 + 8 + (divisor is not None)


def request_work(work: Union[str, Sequence[str]], hw: Tuple[int, int],
                 itemsize: int) -> Tuple[int, int]:
    """``(ops, bytes)`` of one request on an ``hw`` frame."""
    stages = [work] if isinstance(work, str) else list(work)
    pixels = hw[0] * hw[1]
    return (sum(ops_per_pixel(a) for a in stages) * pixels,
            2 * pixels * itemsize)


def peaks(device_kind: str) -> dict:
    """The chip's peaks; a device missing from the table is an error."""
    table = json.loads(PEAKS_FILE.read_text())["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{PEAKS_FILE.name}; known: {sorted(table)}")
    return table[device_kind]


def roofline_s(ops: float, nbytes: float, peak: dict) -> float:
    return max(ops / peak["int32_vector_ops_per_s"],
               nbytes / peak["hbm_bytes_per_s"])
