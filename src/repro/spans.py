"""Host spans at the layer boundaries of the chip path.

Each span is a ``jax.profiler.TraceAnnotation``, so it lands on the
profiler's host plane on the same clock as the device trace, and an idle
stretch of the chip can be put down to the span the host was in.  The
profiler decides whether anything is recorded; spans have no switch of
their own.  To capture them, wrap a serving window in
``jax.profiler.start_trace(dir)`` / ``stop_trace()`` and read the
``.xplane.pb`` it writes (``jax.profiler.ProfileData``).

Spans are named ``fate.<layer>[.<part>]``; identifiers such as the
workflow and stage ids are annotation keywords, not part of the name.
"""
from __future__ import annotations

import time
from typing import Optional

from jax.profiler import TraceAnnotation


class span:
    """Context manager for one span ``name`` carrying the keyword ``ids``.

    With ``times`` given, the span's host time in milliseconds is also
    added to ``times[key]`` when the block ends without raising; the
    caller may set ``key`` inside the block, once it knows which phase
    the span was.
    """

    __slots__ = ("_annotation", "_times", "key", "_t0")

    def __init__(self, name: str, times: Optional[dict] = None,
                 key: Optional[str] = None, **ids):
        self._annotation = TraceAnnotation(name, **ids)
        self._times = times
        self.key = key

    def __enter__(self) -> "span":
        self._annotation.__enter__()
        if self._times is not None:
            self._t0 = time.perf_counter()
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        if self._times is not None and exc_type is None:
            self._times[self.key] += (time.perf_counter() - self._t0) * 1e3
        self._annotation.__exit__(exc_type, exc, tb)
