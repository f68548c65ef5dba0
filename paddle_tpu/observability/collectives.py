"""What a compiled program waits for: its all-reduces, read from the
scheduled HLO's text (``compiled.as_text()``).  The train step counts them
into ``train.collectives`` / ``train.collectives_async`` once its program is
built (``models/pretrain.py::PretrainStep.count_collectives``); the compile
test for the described chip reads where each stands and what it sums
(``tests/test_chip_compile.py``)."""

from __future__ import annotations

import re

__all__ = ["find_all_reduces"]

_CALLS = re.compile(r"calls=%([\w.\-]+)")
_COMPUTATION_HEAD = re.compile(r"(ENTRY )?%([\w.\-]+) ")
_ASYNC_FUSION = re.compile(r"\s*%async-collective-start[.\d]* = \((\w+\[[\d,]*\])")
_ALL_REDUCE = re.compile(r" = (.*?) all-reduce(-start)?\(")
_LAYOUT = re.compile(r"\{[^{}]*\}")


def find_all_reduces(hlo_text: str) -> list:
    """The all-reduces in the text of a COMPILED program
    (``compiled.as_text()``: scheduled HLO), each as ``(in the entry
    computation, asynchronous, result type without layouts)``.
    Synchronous is an ``all-reduce(`` that stands in a computation of its
    own right (the entry, a loop's body); asynchronous an
    ``all-reduce-start`` or, on a TPU, an ``async-collective-start`` fusion
    whose computation holds an all-reduce (its type here: the operand's):
    the all-reduces INSIDE fused computations are the parts of such a pair
    (start, the matmul it runs under, done), not counted again."""
    fused = set(_CALLS.findall(hlo_text))
    holding, found, pairs = set(), [], []
    inside, entry = None, False
    for line in hlo_text.splitlines():
        if line and not line[0].isspace() and line.rstrip().endswith("{"):
            head = _COMPUTATION_HEAD.match(line)
            entry, inside = (bool(head.group(1)), head.group(2)) \
                if head else (False, None)
            continue
        pair = _ASYNC_FUSION.match(line)
        if pair:
            pairs.append((entry, pair.group(1),
                          _CALLS.search(line).group(1)))
            continue
        op = _ALL_REDUCE.search(line)
        if op:
            holding.add(inside)
            if inside not in fused:
                found.append((entry, bool(op.group(2)),
                              _LAYOUT.sub("", op.group(1))))
    return found + [(entry, True, typ) for entry, typ, computation in pairs
                    if computation in holding]
