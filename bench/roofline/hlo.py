"""Operand and result shapes of one Pallas call, read from the text a
TPU trace gives a device operation (its HLO instruction):

    %_zeta_jit.510 = s32[256,256]{1,0:T(8,128)S(1)} custom-call(...),
        custom_call_target="tpu_custom_call",
        operand_layout_constraints={s32[256,256]{1,0}}, ...

The roofline functions of ``bench/roofline`` count bytes from them."""
from __future__ import annotations

import re

_CALL = re.compile(r"^\s*(?:ROOT\s+)?%?(?P<name>[\w.\-]+)\s*=\s*"
                   r"(?P<result>\w+\[[\d,]*\])\S*\s+custom-call\(")
# the braces hold one layout in braces per operand: {s32[256,256]{1,0}, ...}
_OPERANDS = re.compile(
    r"operand_layout_constraints=\{(?P<ops>(?:[^{}]|\{[^{}]*\})*)\}")
_SHAPE = re.compile(r"(\w+)\[([\d,]*)\]")

BYTES = {"s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8,
         "s16": 2, "bf16": 2, "f16": 2, "s8": 1, "u8": 1, "pred": 1}


def shape(text: str) -> tuple:
    dtype, dims = _SHAPE.match(text).groups()
    return dtype, tuple(int(d) for d in dims.split(",") if d)


def nbytes(dtype_shape: tuple) -> int:
    dtype, dims = dtype_shape
    size = BYTES[dtype]
    for d in dims:
        size *= d
    return size


def parse_call(text: str) -> "dict | None":
    """``{"name", "result", "operands"}`` of a ``tpu_custom_call`` (a
    Pallas kernel), or None for any other instruction."""
    if 'custom_call_target="tpu_custom_call"' not in text:
        return None
    m = _CALL.match(text)
    if m is None:
        return None
    ops = _OPERANDS.search(text)
    return {"name": m["name"], "result": shape(m["result"]),
            "operands": [shape(s.group(0)) for s in
                         _SHAPE.finditer(ops["ops"])] if ops else []}
