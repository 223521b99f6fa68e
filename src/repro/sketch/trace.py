"""In-program spans and counters on the served path, off by default.

``span(name)`` times one host step; ``count(name, n)`` adds to a counter
where the work happens. Off, both return after one flag check: ``span``
hands back a shared no-op context, reads no clock and calls no profiler.
On (``enable()``), each span is also a profiler trace annotation
``lsketch:<name>``, so it sits on the trace's clock beside the device
planes, and is kept in memory as ``(name, start, end, parent)``:
``time.perf_counter`` seconds, ``parent`` the index of the innermost span
open on the same thread (-1 for none). Nothing is written out; the caller
reads ``snapshot()`` when it is done.

Counters stay in memory the same way. ``PLANES_BUILD_COUNTS`` (the plane
cache's builds, folds and evictions, ``repro.sketch.query``) is always on
and lives where it is; ``snapshot()`` carries a copy of it.
"""

from __future__ import annotations

import contextlib
import threading
import time

import jax

PREFIX = "lsketch:"

_NULL = contextlib.nullcontext()
_ON = False
_LOCK = threading.Lock()
_LOCAL = threading.local()
_SPANS: list = []  # [name, start, end, parent], in start order
_COUNTS: dict = {}


class _Span:
    __slots__ = ("rec", "ann")

    def __init__(self, name: str):
        self.ann = jax.profiler.TraceAnnotation(PREFIX + name)
        self.rec = [name, 0.0, None, -1]

    def __enter__(self):
        self.ann.__enter__()
        stack = _LOCAL.__dict__.setdefault("stack", [])
        self.rec[3] = stack[-1] if stack else -1
        self.rec[1] = time.perf_counter()
        with _LOCK:
            stack.append(len(_SPANS))
            _SPANS.append(self.rec)
        return self

    def __exit__(self, *exc):
        self.rec[2] = time.perf_counter()
        _LOCAL.stack.pop()
        self.ann.__exit__(*exc)
        return False


def span(name: str):
    """A context that times one step while the facility is on."""
    if not _ON:
        return _NULL
    return _Span(name)


def count(name: str, n: int = 1) -> None:
    """Add ``n`` to counter ``name`` while the facility is on."""
    if not _ON:
        return
    with _LOCK:
        _COUNTS[name] = _COUNTS.get(name, 0) + int(n)


def enabled() -> bool:
    """Whether spans and counters record (callers skip work that only
    feeds a counter when they do not)."""
    return _ON


def enable() -> None:
    global _ON
    _ON = True


def disable() -> None:
    global _ON
    _ON = False


def reset() -> None:
    """Drop every span and counter recorded so far (call it with no span
    open: the parents of spans still open would point into the old
    list)."""
    with _LOCK:
        _SPANS.clear()
        _COUNTS.clear()


def snapshot() -> dict:
    """``{"spans": [(name, start, end, parent), ...], "counts": {...},
    "planes": PLANES_BUILD_COUNTS}``, copies; a span still open has
    ``end`` None."""
    from .query import PLANES_BUILD_COUNTS
    with _LOCK:
        return {"spans": [tuple(r) for r in _SPANS],
                "counts": dict(_COUNTS),
                "planes": dict(PLANES_BUILD_COUNTS)}
