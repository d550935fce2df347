"""The MITHRIL kernels by the names they carry in a device trace.

A Pallas kernel's operation is matched by its kernel name as a whole
word, in the operation's name or in its text stats (the HLO op's long
name carries the ``kernel_name`` of a ``tpu_custom_call``).
"""

from __future__ import annotations

import re
from typing import List

KERNELS = {
    "record": ("mithril_record",),
    "mine": ("mithril_mine_batched", "mithril_mine"),
}


def kernel_ops(summary, kernel: str) -> List[str]:
    """Operation names in the trace that are launches of ``kernel``."""
    pat = re.compile(r"\b(%s)\b" % "|".join(KERNELS[kernel]))
    return [op for op, label in summary.labels().items()
            if pat.search(label)]


def required_kernel_ops(summary, kernel: str) -> List[str]:
    """:func:`kernel_ops`, which must find some: a metric that names a
    kernel is listed only for cells whose traced window launches it, so
    finding none means the names no longer match the trace."""
    ops = kernel_ops(summary, kernel)
    if not ops:
        raise LookupError(
            f"no {kernel} kernel ({'/'.join(KERNELS[kernel])}) among the "
            f"traced window's {len(summary.labels())} device operations")
    return ops


def all_kernel_ops(summary) -> set:
    return {op for k in KERNELS for op in kernel_ops(summary, k)}


def kernel_seconds(summary, kernel: str) -> float:
    ops = summary.op_seconds()
    return sum(ops[op] for op in required_kernel_ops(summary, kernel))


def kernel_launches(summary, kernel: str) -> float:
    return sum(summary.launches(op)
               for op in required_kernel_ops(summary, kernel))
