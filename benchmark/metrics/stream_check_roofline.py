"""Share of its roofline that the stream kernel reached: the stream
linearizability program (``checkers/stream_lin.py``, jit module
``_stream_lin_batch``), over its summed device time in the trace.

The work is counted from the histories the window checked, by their
shapes (rows, space of values and offsets), not from the padded tensors
the program builds.  Per row the kernel must read at least an op code
(1 byte) and a value or offset (``value_bytes``), and make 10 updates
(attempt, ack and fail counts, first invoke and first ack, offsets per
value, values per offset, the two real-time bounds, batch order); per
history it must write its 6 verdict sets (divergent, duplicate, phantom,
recovered, reorder, lost) as bits.
"""

import math

from peaks import roofline

#: the pipeline dispatches the kernel inside its donating wrapper
#: (``parallel/pipeline.py::donated``, ``jax.jit`` of a lambda), so on the
#: chip its module is ``jit__lambda``; called bare it is ``jit__stream_lin_batch``
MODULE = r"^jit__lambda$|^jit__stream_lin_batch$"
CLASSES = 6
OPS_PER_ROW = 10


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
