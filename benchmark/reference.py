"""The plain reference: the same semantics as the program's checkers,
written straight from their definitions, importing nothing of the program.

It reads a history from its JSONL bytes (the very bytes the timed path
parsed) and returns the verdict map the program returns for it, as the
configuration's family file defines it (``check(ops, opts)`` in
``benchmark/families/<family>.py``, found through ``gen.family``).

``same`` compares two verdict maps exactly: every key, every anomaly
set, every count.  A map that is missing, or an ``unknown`` verdict,
is a mismatch.
"""

from __future__ import annotations

import json
from typing import Any

import gen


def parse(text: str) -> list[dict]:
    return [json.loads(line) for line in text.splitlines() if line.strip()]


def check(family: str, ops: list[dict], opts: dict) -> dict:
    """The verdict map of one history under the configuration's
    guarantees (``opts``, the configuration's ``checker`` options)."""
    return gen.family(family).check(ops, opts)


def valid(result: dict) -> bool:
    """A history's verdict: every sub-verdict valid."""
    return all(r.get("valid?") is True for r in result.values())


def norm(x: Any) -> Any:
    """JSON-plain form: sets sorted, numpy scalars unwrapped."""
    if isinstance(x, dict):
        return {str(k): norm(v) for k, v in x.items()}
    if isinstance(x, (set, frozenset)):
        return sorted(norm(v) for v in x)
    if isinstance(x, (list, tuple)):
        return [norm(v) for v in x]
    if hasattr(x, "item"):
        return x.item()
    return x


def same(got: Any, want: dict, labels: frozenset = frozenset()) -> bool:
    """``got`` (the program's answer) says what ``want`` (the reference's
    normalized map) says: every sub-verdict, key for key.  A top-level
    ``valid?`` beside the sub-verdicts (the sidecar's) must be their
    conjunction.  ``labels`` (the family's ``LABELS``) are keys that echo
    the options a check was asked for: an answer may leave them out (the
    sidecar's ``check`` op does), but may not contradict them."""
    if not isinstance(got, dict):
        return False
    got = norm(got)
    extra = set(got) - set(want)
    if extra - {"valid?"}:
        return False
    if "valid?" in extra and got["valid?"] is not valid(want):
        return False
    for k, w in want.items():
        g = got.get(k)
        if not isinstance(g, dict):
            return False
        for key in set(g) | set(w):
            if key in labels and key not in g:
                continue
            if key not in g or key not in w or g[key] != w[key]:
                return False
    return True


def run(job: tuple) -> Any:
    """A pool worker's job: ``(family, opts, path, reduce)`` -> the
    normalized verdict map of the history in that JSONL file, or with
    ``reduce`` only its validity."""
    family, opts, path, only_valid = job
    with open(path) as fh:
        result = check(family, parse(fh.read()), opts)
    return valid(result) if only_valid else norm(result)
