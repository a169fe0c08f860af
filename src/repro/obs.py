"""Host spans of the serving path.

``span(name, rid=None, **fields)`` marks one stretch of host work at a
layer boundary (``moa.runtime.*``, ``moa.scheduler.*``, ``moa.engine.*``;
a name ending in ``.read`` is a stretch in which the host waits on the
device).

Off, the default, ``span`` returns one shared no-op context after a single
flag check: it allocates nothing, records nothing and makes no profiler
annotation. On (``enable()``), each span enters a
``jax.profiler.TraceAnnotation`` carrying the rid and the fields, so a
running profiler puts it on the device trace's clock, and is kept in
memory as a :class:`Span` on ``time.monotonic()``. ``take()`` hands the
kept spans over and clears them. Kept spans grow by one per span closed
(a fused block makes three), so a long run with spans on calls ``take()``
as it goes; an array field is kept as its own small copy, never as a
view holding a larger array alive.

A span never sits inside a per-token or per-slot loop.
"""
from __future__ import annotations

import contextlib
import threading
import time
from typing import Any, Dict, List, NamedTuple, Optional

import jax
import numpy as np


class Span(NamedTuple):
    name: str
    t0: float  # time.monotonic() at entry
    t1: float  # ... and at exit
    parent: Optional[str]  # name of the span open around it on its thread
    rid: Optional[int]
    fields: Dict[str, Any]


_NOOP = contextlib.nullcontext()
_on = False
_spans: List[Span] = []
_local = threading.local()


class _Open:
    __slots__ = ("name", "rid", "fields", "parent", "ann", "t0")

    def __init__(self, name: str, rid: Optional[int], fields: dict):
        self.name, self.rid, self.fields = name, rid, fields

    def __enter__(self):
        stack = getattr(_local, "stack", None)
        if stack is None:
            stack = _local.stack = []
        self.parent = stack[-1] if stack else None
        stack.append(self.name)
        meta = self.fields if self.rid is None else dict(self.fields,
                                                         rid=self.rid)
        self.ann = jax.profiler.TraceAnnotation(self.name, **meta)
        self.ann.__enter__()
        self.t0 = time.monotonic()
        return self

    def __exit__(self, *exc):
        t1 = time.monotonic()
        self.ann.__exit__(*exc)
        _local.stack.pop()
        fields = {k: v.copy() if isinstance(v, np.ndarray)
                  and v.base is not None else v
                  for k, v in self.fields.items()}
        _spans.append(Span(self.name, self.t0, t1, self.parent, self.rid,
                           fields))
        return False


def span(name: str, rid: Optional[int] = None, **fields):
    if not _on:
        return _NOOP
    return _Open(name, rid, fields)


def enable() -> None:
    global _on
    _on = True


def enabled() -> bool:
    """Whether spans are on: a field worth computing only for a kept span
    is computed under this check."""
    return _on


def disable() -> None:
    global _on
    _on = False


def take() -> List[Span]:
    """The spans closed since the last call, in closing order; clears
    them."""
    global _spans
    out, _spans = _spans, []
    return out
