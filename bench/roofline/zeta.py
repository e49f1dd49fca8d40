"""Bytes the subset zeta / Moebius transform needs (``_local_kernel`` and
``_pair_kernel`` of the program's ``kernels/zeta_pallas.py``, both called
through its ``_zeta_jit``, whose name the instructions carry).

One transform reads its (rows, 256) table once and writes it once.  The
program runs it as one local pass (one operand) and then one pair pass
per high bit (two operands): the local pass is charged the whole
transform's bytes and each pair pass none, so a kernel that does the
same transform in fewer passes reads higher on this yardstick."""
from bench.roofline.hlo import nbytes

PREFIX = "_zeta_jit"


def matches(call: dict) -> bool:
    return call["name"].split(".")[0] == PREFIX


def bytes_needed(call: dict) -> int:
    if len(call["operands"]) == 1:           # the local pass
        return nbytes(call["operands"][0]) + nbytes(call["result"])
    return 0                                 # a pair pass of the same
