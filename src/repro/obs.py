"""Program spans and counters on the profiler's own clock.

    with obs.span("repro.plan", bucket=3, chunk=0) as sp:
        ...
        if sp.on:
            sp.stat(rows=64, lanes=23040)

A span is a ``jax.profiler.TraceAnnotation``: it lands in the same profile
as the device's operations, on the same clock, so a device-idle gap can be
put beside the span open at the time.  Its keyword arguments and
:meth:`span.stat` values are the trace event's stats, read back through
``jax.profiler.ProfileData``; the profiler keeps them in memory until its
session stops.  Ids given to a span (``bucket``, ``chunk``) are inherited
by every span opened inside it.

Outside a profiler session a span costs one ``TraceMe`` construction and
``on`` is False: counters are computed only under ``if sp.on``.  No span
reads a device value or waits for the device.
"""
from __future__ import annotations

import contextvars
import itertools

from jax.profiler import TraceAnnotation

_ids = contextvars.ContextVar("repro_obs_ids", default={})
_tally = contextvars.ContextVar("repro_obs_tally", default=None)
_serial = itertools.count()


def enabled() -> bool:
    """Whether a profiler session is recording spans right now."""
    return TraceAnnotation.is_enabled()


def next_bucket() -> int:
    """A fresh bucket serial number (one per planned bucket run)."""
    return next(_serial)


class span:
    """Context manager: a named profiler span carrying inherited ids."""

    __slots__ = ("name", "ids", "on", "_ann", "_token")

    def __init__(self, name: str, **ids):
        self.name = name
        self.ids = {**_ids.get(), **ids} if ids else _ids.get()
        self.on = False

    def __enter__(self):
        self._token = _ids.set(self.ids)
        self._ann = TraceAnnotation(self.name, **self.ids)
        self._ann.__enter__()
        self.on = enabled()
        return self

    def stat(self, **stats):
        """Attach counters known only now (call only when ``on``)."""
        self._ann.set_metadata(**stats)

    def __exit__(self, *exc):
        self._ann.__exit__(*exc)
        _ids.reset(self._token)
        return False


class upload(span):
    """A span whose stats count the host arrays that cross to the device
    inside it: ``bytes`` and ``arrays`` (see :func:`crossed`)."""

    __slots__ = ("_tally_token",)

    def __enter__(self):
        super().__enter__()
        self._tally_token = _tally.set([0, 0] if self.on else None)
        return self

    def __exit__(self, *exc):
        tally = _tally.get()
        _tally.reset(self._tally_token)
        if tally is not None:
            self.stat(bytes=tally[0], arrays=tally[1])
        return super().__exit__(*exc)


def crossed(array) -> None:
    """Count one array just copied from the host into the open
    :class:`upload` span (nothing is counted outside a profiler
    session)."""
    tally = _tally.get()
    if tally is not None:
        tally[0] += int(array.nbytes)
        tally[1] += 1
