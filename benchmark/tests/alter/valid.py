"""Faults on outputs whose verdict is a ``valid`` field (the queue and
stream kernels): the verdict is a dataclass with one, or a tuple of such
sub-verdicts, the history valid where all are; a packed batch has a
per-history ``mask`` over its rows."""

import dataclasses

import numpy as np


def answer(out):
    """The first history's verdict is turned over where the kernel
    produces it."""
    if isinstance(out, tuple):
        # the others read valid, the last the opposite of the whole
        *rest, last = out
        both = last.valid[0]
        for r in rest:
            both = both & r.valid[0]
        return (*(dataclasses.replace(r, valid=r.valid.at[0].set(True))
                  for r in rest),
                dataclasses.replace(last, valid=last.valid.at[0].set(~both)))
    return dataclasses.replace(out, valid=out.valid.at[0].set(~out.valid[0]))


def half(packed):
    """Every other history of the batch is left out (its rows masked
    off)."""
    m = np.array(packed.mask)
    m[1::2] = False
    return dataclasses.replace(packed, mask=m)
