"""Named phase spans at the engine's and trainer's layer boundaries.

``with Span("engine.decode", stats, "decode_time"):`` does two things:

* it enters ``jax.profiler.TraceAnnotation(name)``, so the span lands on
  the profiler's host timeline, which shares a clock with the device ops
  of the trace: an idle gap of the device is then named by the innermost
  program span that covers it;
* it adds the span's ``time.perf_counter()`` duration to ``stats[key]``
  (when both are given), so every step carries its phase seconds with the
  profiler off.

There is no switch. With no profiler session active the annotation is not
made (``TraceAnnotation.is_enabled`` is false), and a span costs its two
clock reads and one dict update, about a microsecond of Python.

A span that covers dispatched device work must close after that work's
sync point (its ``device_get`` or ``block_until_ready``), or it times the
enqueue; the ``JAX104`` lint holds ``with Span(...)`` blocks to that rule.
"""
from __future__ import annotations

import time
from typing import Optional

from jax.profiler import TraceAnnotation

_tracing = TraceAnnotation.is_enabled


class Span:
    __slots__ = ("name", "stats", "key", "_ann", "_t0")

    def __init__(self, name: str, stats: Optional[dict] = None,
                 key: Optional[str] = None):
        self.name = name
        self.stats = stats
        self.key = key

    def __enter__(self):
        self._ann = TraceAnnotation(self.name) if _tracing() else None
        if self._ann is not None:
            self._ann.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        dt = time.perf_counter() - self._t0
        if self._ann is not None:
            self._ann.__exit__(*exc)
        if self.key is not None:
            self.stats[self.key] = self.stats.get(self.key, 0.0) + dt
        return False
