"""Resource analysis: the XLA-native analogue of the paper's Table I.

The paper reports LUT/TCON/wire-length/channel-width deltas between the
conventional and the parameterized implementation of each VCGRA component.
Those are FPGA place-and-route artefacts; the resources XLA has are HLO
ops, FLOPs and bytes.  We therefore compile both executor variants and
census the optimized HLO:

  routing ops   (gather/dynamic-slice/...)  <->  VC connection muxes / TCONs
  mux/select ops (select/clamp/compare-for-mux) <-> generic-PE output muxes
  arith ops     (add/mul/div/...)           <->  PE functional-unit LUTs
  flops/bytes   (cost_analysis)             <->  overall datapath cost

Reduction percentages between the two variants are the direct analogue of
the paper's 82 % (VC) / 24 % (FP PE) / 6 % (grid) resource cuts.
"""

from __future__ import annotations

import re
from typing import Callable, Dict, Iterable, Tuple

import jax

_OP_RE = re.compile(r"=\s*(?:\([^)]*\)|\S+)\s+([a-z][a-z0-9_\-]*)\(")

ROUTING_OPS = {
    "gather", "dynamic-slice", "dynamic-update-slice", "scatter",
    "concatenate", "slice", "pad", "reverse",
}
MUX_OPS = {"select", "clamp"}
ARITH_OPS = {
    "add", "subtract", "multiply", "divide", "compare", "maximum", "minimum",
    "abs", "negate", "sign", "floor", "power", "remainder", "and", "or",
    "xor", "not",
}
MOVE_OPS = {
    "copy", "transpose", "reshape", "broadcast", "bitcast", "convert",
    "iota", "tuple", "get-tuple-element",
}


def hlo_op_census(hlo_text: str) -> Dict[str, int]:
    """Count optimized-HLO ops by category (fusion bodies included: they
    appear as separate computations in the module text)."""
    counts: Dict[str, int] = {}
    for line in hlo_text.splitlines():
        m = _OP_RE.search(line)
        if not m:
            continue
        counts[m.group(1)] = counts.get(m.group(1), 0) + 1
    total = sum(counts.values())
    summary = {
        "total_ops": total,
        "routing_ops": sum(v for k, v in counts.items() if k in ROUTING_OPS),
        "mux_ops": sum(v for k, v in counts.items() if k in MUX_OPS),
        "arith_ops": sum(v for k, v in counts.items() if k in ARITH_OPS),
        "move_ops": sum(v for k, v in counts.items() if k in MOVE_OPS),
    }
    summary["other_ops"] = total - sum(
        summary[k] for k in ("routing_ops", "mux_ops", "arith_ops", "move_ops")
    )
    return summary


def compile_and_census(fn: Callable, *args) -> Dict[str, float]:
    """Lower+compile `fn(*args)` and return the resource census."""
    jitted = fn if isinstance(fn, jax.stages.Wrapped) else jax.jit(fn)
    lowered = jitted.lower(*args)
    compiled = lowered.compile()
    census = hlo_op_census(compiled.as_text())
    cost = compiled.cost_analysis() or {}
    census["flops"] = float(cost.get("flops", 0.0))
    census["bytes"] = float(cost.get("bytes accessed", 0.0))
    return census


def reduction_row(
    name: str, conventional: Dict[str, float], parameterized: Dict[str, float]
) -> Dict[str, object]:
    """One Table-I row: conventional vs parameterized + reduction %."""
    row: Dict[str, object] = {"component": name}
    for key in ("total_ops", "routing_ops", "mux_ops", "arith_ops", "flops", "bytes"):
        c, p = float(conventional.get(key, 0)), float(parameterized.get(key, 0))
        row[f"{key}_conv"] = c
        row[f"{key}_param"] = p
        row[f"{key}_reduction_pct"] = (100.0 * (c - p) / c) if c else 0.0
    return row


def format_table(rows: Iterable[Dict[str, object]], keys=None) -> str:
    rows = list(rows)
    if not rows:
        return "(empty)"
    keys = keys or list(rows[0].keys())
    widths = {k: max(len(str(k)), *(len(_fmt(r.get(k))) for r in rows)) for k in keys}
    head = " | ".join(str(k).ljust(widths[k]) for k in keys)
    sep = "-+-".join("-" * widths[k] for k in keys)
    body = "\n".join(
        " | ".join(_fmt(r.get(k)).ljust(widths[k]) for k in keys) for r in rows
    )
    return f"{head}\n{sep}\n{body}"


def _fmt(v) -> str:
    if isinstance(v, float):
        return f"{v:,.1f}"
    return str(v)
