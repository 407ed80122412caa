"""Share of its roofline that the queue kernel reached: the fused
total-queue + queue-linearizability program (``checkers/fused.py``, jit
module ``_combined_batch``), over its summed device time in the trace.

The work is counted from the histories the window checked, by their
shapes (rows, value space), not from the padded tensors the program
builds, so that a change of padding or layout is measured against the
same work.  Per row the kernel must read at least an op code (``f`` and
``type``, four bits each: 1 byte) and the value (``value_bytes``), and
update each of its 8 per-value statistics once (attempts, acks, definite
fails, reads, first invoke, first read, and the two counts they are
reconciled from); per history it must write its 8 per-value verdict
classes as bits.
"""

import math

from peaks import roofline

#: the pipeline dispatches the kernel inside its donating wrapper
#: (``parallel/pipeline.py::donated``, ``jax.jit`` of a lambda), so on the
#: chip its module is ``jit__lambda``; called bare it is ``jit__combined_batch``
MODULE = r"^jit__lambda$|^jit__combined_batch$"
CLASSES = 8
OPS_PER_ROW = 8


def value_bytes(space: int) -> int:
    return max(1, math.ceil(math.log2(max(space, 2)) / 8))


def work(shapes) -> tuple[int, int]:
    """``(bytes, int ops)`` of checking these histories once."""
    b = sum(h["rows"] * (1 + value_bytes(h["space"]))
            + math.ceil(CLASSES * h["space"] / 8) for h in shapes)
    return b, sum(OPS_PER_ROW * h["rows"] for h in shapes)


def read(ctx):
    t = ctx.trace
    secs = t.module_time(MODULE) if t.devices else 0.0
    if not secs:
        return None
    n = ctx.counts["passes"]
    b, ops = work(ctx.campaign)
    share, bound = roofline(n * b, n * ops, secs, ctx.device_kind)
    return share, (f"{bound}-bound: {n * b} bytes, {n * ops} int ops in "
                   f"{secs} s of {MODULE}")
