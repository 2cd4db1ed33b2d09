"""Processing-Element opcodes and semantics for the Pixie VCGRA.

The paper's PE is a small FSM (AWAIT_DATA -> PROCESS_DATA -> VALID_DATA)
that applies one configured operation to its two (equal-bitwidth) inputs:
arithmetic (Add, Sub, Mul, Div), comparison (Gt, Eq), plus a BUF mode
(copy input to output, used to carry values across pipeline levels because
level bypassing is unsupported) and a NONE/idle mode (PE produces nothing).

On TPU the valid/start handshake discipline of the FSM is subsumed by data
dependence (JAX is a synchronous dataflow IR); what remains is the opcode
semantics, implemented here in two forms:

* ``apply_op``      -- *specialized* form: the opcode is a Python constant,
                       only that functional unit is emitted (the analogue of
                       the paper's parameterized configuration / constant
                       propagation through TLUTs).
* ``apply_generic`` -- *conventional* form of the XLA interpreter: the
                       opcode is a traced per-lane array, every functional
                       unit is computed and the result is selected by a
                       mux chain (the analogue of the generic
                       settings-register-driven PE).  The Pallas kernel
                       does not use it: its opcode is an SMEM scalar, so
                       it branches on it and runs ``apply_op`` of the one
                       configured unit.

Extension opcodes beyond the paper's set (MAX, MIN, ABS) follow the paper's
note that "the functionality of the processing elements is extendable"; the
MAC mode is modelled like the paper treats it: the PE semantics exist but
the mapper does not schedule it ("we do not support graph mapping for that
operation yet").
"""

from __future__ import annotations

import enum

import jax.numpy as jnp


class Op(enum.IntEnum):
    """PE opcodes. Values are the settings-register encoding."""

    NONE = 0   # idle: PE produces no output, does not raise valid
    ADD = 1
    SUB = 2
    MUL = 3
    DIV = 4
    GT = 5     # a > b  -> 1/0 in the data type
    EQ = 6     # a == b -> 1/0 in the data type
    BUF = 7    # copy: both inputs carry the same value (paper Sec III-A)
    MAX = 8    # extension op
    MIN = 9    # extension op
    ABS = 10   # extension op (unary; port b ignored)
    MAC = 11   # experimental, not schedulable by the mapper (paper Sec III-A)


#: Opcodes the place-and-route flow may schedule onto the grid.
SCHEDULABLE_OPS = frozenset(
    {Op.ADD, Op.SUB, Op.MUL, Op.DIV, Op.GT, Op.EQ, Op.BUF, Op.MAX, Op.MIN, Op.ABS}
)

#: Opcodes whose second input port is ignored.
UNARY_OPS = frozenset({Op.ABS, Op.BUF, Op.NONE})


def _safe_div(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """Division with a guarded divisor (hardware would saturate; we define 0).

    Integer ("fixed point") grids use floor division, float grids true
    division; both return 0 where the divisor is 0 so that NONE/unused PE
    lanes can never poison the array with NaN/Inf in the conventional path.
    """
    if jnp.issubdtype(a.dtype, jnp.integer):
        denom = jnp.where(b == 0, jnp.ones_like(b), b)
        return jnp.where(b == 0, jnp.zeros_like(a), a // denom)
    denom = jnp.where(b == 0, jnp.ones_like(b), b)
    return jnp.where(b == 0, jnp.zeros_like(a), a / denom)


def apply_op(op: Op, a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """Specialized PE: ``op`` is a Python constant; emit only its unit."""
    op = Op(op)
    if op == Op.ADD:
        return a + b
    if op == Op.SUB:
        return a - b
    if op == Op.MUL:
        return a * b
    if op == Op.DIV:
        return _safe_div(a, b)
    if op == Op.GT:
        return (a > b).astype(a.dtype)
    if op == Op.EQ:
        return (a == b).astype(a.dtype)
    if op == Op.BUF:
        return a
    if op == Op.MAX:
        return jnp.maximum(a, b)
    if op == Op.MIN:
        return jnp.minimum(a, b)
    if op == Op.ABS:
        return jnp.abs(a)
    if op == Op.NONE:
        return jnp.zeros_like(a)
    raise ValueError(f"opcode {op!r} has no combinational semantics")


def apply_generic(opcode: jnp.ndarray, a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """Conventional PE of the XLA interpreter: every functional unit
    computed, mux selects output.

    ``opcode`` has shape ``a.shape[:1]`` (one opcode per PE lane) or is a
    scalar; it broadcasts against ``a``/``b`` of shape ``[n_pes, batch]``.
    It mirrors the generic hardware PE, all units live because the
    settings register is runtime data (paper Table I).  The Pallas
    conventional kernel holds each opcode as an SMEM scalar instead and
    branches on it, computing only the configured unit with the same
    results (``vcgra_kernel._pe_unit``).
    """
    if opcode.ndim == a.ndim - 1:
        opcode = opcode[..., None]
    # Plain-int comparisons: enum members would become captured scalar
    # constants inside pallas kernel bodies, which pallas_call rejects.
    out = jnp.zeros_like(a)
    out = jnp.where(opcode == int(Op.ADD), a + b, out)
    out = jnp.where(opcode == int(Op.SUB), a - b, out)
    out = jnp.where(opcode == int(Op.MUL), a * b, out)
    out = jnp.where(opcode == int(Op.DIV), _safe_div(a, b), out)
    out = jnp.where(opcode == int(Op.GT), (a > b).astype(a.dtype), out)
    out = jnp.where(opcode == int(Op.EQ), (a == b).astype(a.dtype), out)
    out = jnp.where(opcode == int(Op.BUF), a, out)
    out = jnp.where(opcode == int(Op.MAX), jnp.maximum(a, b), out)
    out = jnp.where(opcode == int(Op.MIN), jnp.minimum(a, b), out)
    out = jnp.where(opcode == int(Op.ABS), jnp.abs(a), out)
    return out


def op_name(op: int) -> str:
    return Op(op).name
