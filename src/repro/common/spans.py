"""Host spans and counters of the round loop, kept in memory.

``span(name, **attrs)`` marks a stretch of host work; ``count(name, n)``
adds to a counter.  Both do nothing unless a ``recording()`` block is open:
``span`` then returns one shared null context, no list grows and no clock
is read.  Inside ``recording()`` each span keeps its name, its start and
end from ``time.time_ns()`` (the wall clock a profiler trace gives its
start time on, so the spans can be placed on the device's timeline), the
index of its parent span and the round it belongs to: a span's ``round``
attribute, else its parent's, so every span inside one ``fl.round`` shares
that round's index.  A counter is keyed by ``(name, round)``.

The recorder never touches a device array: it reads the host clock and
adds host integers, so recording adds no host-device transfer.
"""
from __future__ import annotations

import contextlib
import dataclasses
import time
from typing import Dict, Iterator, List, Optional, Tuple

_NULL = contextlib.nullcontext()


@dataclasses.dataclass
class Span:
    name: str
    start_ns: int
    end_ns: int = 0
    parent: Optional[int] = None      # index into ``Recorder.spans``
    round: Optional[int] = None
    attrs: dict = dataclasses.field(default_factory=dict)


@dataclasses.dataclass
class Recorder:
    spans: List[Span] = dataclasses.field(default_factory=list)
    counters: Dict[Tuple[str, Optional[int]], int] = dataclasses.field(
        default_factory=dict)
    open: List[int] = dataclasses.field(default_factory=list)

    def current_round(self) -> Optional[int]:
        return self.spans[self.open[-1]].round if self.open else None

    @contextlib.contextmanager
    def span(self, name: str, attrs: dict) -> Iterator[Span]:
        parent = self.open[-1] if self.open else None
        s = Span(name, time.time_ns(), parent=parent,
                 round=attrs.get("round", self.current_round()), attrs=attrs)
        self.open.append(len(self.spans))
        self.spans.append(s)
        try:
            yield s
        finally:
            self.open.pop()
            s.end_ns = time.time_ns()


_active: Optional[Recorder] = None


def span(name: str, **attrs):
    """A context manager around one stretch of host work; it yields the
    ``Span`` (add to its ``attrs`` inside), or ``None`` when not
    recording."""
    if _active is None:
        return _NULL
    return _active.span(name, attrs)


def count(name: str, n: int) -> None:
    """Add ``n`` to the counter ``name`` of the current round."""
    if _active is None:
        return
    key = (name, _active.current_round())
    _active.counters[key] = _active.counters.get(key, 0) + int(n)


@contextlib.contextmanager
def recording() -> Iterator[Recorder]:
    """Record spans and counters while the block runs; the recorder keeps
    them after it ends, and recording is off again."""
    global _active
    if _active is not None:
        raise RuntimeError("spans are already being recorded")
    _active = Recorder()
    try:
        yield _active
    finally:
        _active = None
