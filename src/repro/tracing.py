"""Host spans of the program, on the device trace's clock.

Tracing is off by default.  Off, :func:`span` checks one module-level
flag and returns one shared no-op context manager: no span object, no
clock read.  On (:func:`enable`), each span records::

    {"name", "id", "parent", "t0", "t1", **attrs}

``id`` is an integer unique in the process, ``parent`` the id of the
innermost span open when it started (the hot path is single-threaded),
``t0``/``t1`` come from :func:`time.perf_counter`.  Each span also enters
``jax.profiler.TraceAnnotation(name)``, so under a profiler session it
lies on the ``/host:CPU`` plane on the same clock as the device's
operations.  Spans are kept in memory, in the order they end, until
:func:`drain`.

Spans of one request or job share an identifier attr (``rid=`` for an
engine request, ``job=`` for an LIDC job); counts known only inside the
span are written into the record ``with span(...) as rec`` yields
(``None`` when tracing is off).  A span never waits for the device:
where work is asynchronous it ends at dispatch, and a ``*.sync`` child
holds the blocking read of the result.

    from repro import tracing
    tracing.enable()
    ...                       # run jobs
    tracing.disable()
    spans = tracing.drain()
"""

from __future__ import annotations

import itertools
import time
from typing import Any, Dict, List, Optional

__all__ = ["enable", "disable", "enabled", "span", "now", "drain"]

_enabled = False
_annotation = None                 # jax.profiler.TraceAnnotation, once enabled
_ids = itertools.count(1)
_open: List[int] = []              # ids of the open spans, innermost last
_done: List[Dict[str, Any]] = []


class _NoSpan:
    """What :func:`span` returns while tracing is off."""

    __slots__ = ()

    def __enter__(self) -> None:
        return None

    def __exit__(self, *exc) -> bool:
        return False


_NO_SPAN = _NoSpan()


class _Span:
    __slots__ = ("rec", "ann")

    def __init__(self, name: str, attrs: Dict[str, Any]):
        self.rec = {"name": name, "id": next(_ids),
                    "parent": _open[-1] if _open else None, **attrs}
        self.ann = _annotation(name)

    def __enter__(self) -> Dict[str, Any]:
        self.ann.__enter__()
        _open.append(self.rec["id"])
        self.rec["t0"] = time.perf_counter()
        return self.rec

    def __exit__(self, *exc) -> bool:
        self.rec["t1"] = time.perf_counter()
        _open.pop()
        self.ann.__exit__(*exc)
        _done.append(self.rec)
        return False


def enable() -> None:
    """Record spans from now on."""
    global _enabled, _annotation
    from jax.profiler import TraceAnnotation
    _annotation = TraceAnnotation
    _enabled = True


def disable() -> None:
    """Stop recording; spans still open are recorded when they end."""
    global _enabled
    _enabled = False


def enabled() -> bool:
    return _enabled


def span(name: str, **attrs: Any):
    """A context manager timing ``name``; see the module docstring."""
    if not _enabled:
        return _NO_SPAN
    return _Span(name, attrs)


def now() -> Optional[float]:
    """The spans' clock while tracing is on, else ``None`` (no clock
    read): for stamping a time a later span measures from."""
    return time.perf_counter() if _enabled else None


def drain() -> List[Dict[str, Any]]:
    """The spans ended since the last drain, oldest end first."""
    out = _done[:]
    del _done[:]
    return out
