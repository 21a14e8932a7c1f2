"""In-program telemetry: named host spans and event counts on the round path.

``span(name)`` marks a piece of host work (``with telemetry.span("plan"):``)
and ``count(name)`` marks an event (``telemetry.count("host_sync")`` before
each blocking device-to-host read).  Both are off by default: a span then
costs one flag check and hands back a shared no-op context, and a count
returns at once.

After ``enable()``:

* a span opens ``jax.profiler.TraceAnnotation("repro.<name>")``, so that a
  profiler trace taken meanwhile shows it on the host timeline, on the same
  clock as the device's programs and idle time, and appends
  ``Event("span", name, start, end, 1)`` to an in-memory list when it closes;
* a count appends ``Event("count", name, t, t, n)``.

Times are ``time.perf_counter()`` seconds.  ``events()`` returns the list and
``reset()`` empties it.  The switch and the list are process-wide, as the
profiler's own session is: one flag is what keeps the off path free.
"""
from __future__ import annotations

import contextlib
import time
from typing import Iterator, List, NamedTuple

import jax

PREFIX = "repro."


class Event(NamedTuple):
    kind: str       # "span" | "count"
    name: str
    start: float    # perf_counter seconds
    end: float      # == start for a count
    n: int


_on = False
_events: List[Event] = []
_OFF = contextlib.nullcontext()


def enable() -> None:
    global _on
    _on = True


def disable() -> None:
    global _on
    _on = False


def events() -> List[Event]:
    """Closed spans and counts since the last ``reset``, in the order they
    ended (an inner span before the span around it)."""
    return list(_events)


def reset() -> None:
    _events.clear()


@contextlib.contextmanager
def _traced(name: str) -> Iterator[None]:
    t0 = time.perf_counter()
    try:
        with jax.profiler.TraceAnnotation(PREFIX + name):
            yield
    finally:
        _events.append(Event("span", name, t0, time.perf_counter(), 1))


def span(name: str):
    """A context manager around one piece of host work."""
    if not _on:
        return _OFF
    return _traced(name)


def count(name: str, n: int = 1) -> None:
    if _on:
        t = time.perf_counter()
        _events.append(Event("count", name, t, t, n))
