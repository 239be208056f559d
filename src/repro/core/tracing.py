"""Spans on the profiler's clock: the one way the program times a phase.

    with span("ckpt.drain", into=req.timings, key="drain_ms", step=step):
        ...

Each span takes ``time.perf_counter`` at entry and exit and wraps the block
in a ``jax.profiler.TraceAnnotation`` carrying ``args`` (small ints or
strings: ``step``, ``restore``, ``rank``, ``batch``, ``leaf``, ``bytes``;
``None`` values are left out), so one clock fills both the program's
timing dicts and a profiler trace.  With ``into`` the block's duration in
ms, rounded to 3 places, lands in ``into[key]`` (summed there with
``add=True``) when the block completes without raising.  An annotation
costs about a microsecond when no profiler runs, so spans are always on.
"""
from __future__ import annotations

import threading
import time

from jax.profiler import TraceAnnotation

_add_lock = threading.Lock()


class span:
    """Context manager timing one phase; ``t0``/``t1`` (perf_counter
    seconds) and ``ms`` stay readable after the block."""

    __slots__ = ("name", "into", "key", "add", "args", "t0", "t1", "_ann")

    def __init__(self, name: str, *, into: dict | None = None,
                 key: str | None = None, add: bool = False, **args):
        self.name, self.into, self.key, self.add = name, into, key, add
        self.args = {k: v for k, v in args.items() if v is not None}
        self.t0 = self.t1 = None

    def __enter__(self):
        self._ann = TraceAnnotation(self.name, **self.args)
        self._ann.__enter__()
        self.t0 = time.perf_counter()
        return self

    def set(self, **args) -> None:
        """Add args known only inside the block (e.g. a restore id read
        from the manifest the span times)."""
        self._ann.set_metadata(**args)

    @property
    def ms(self) -> float:
        return (self.t1 - self.t0) * 1e3

    def __exit__(self, exc_type, exc, tb):
        self.t1 = time.perf_counter()
        self._ann.__exit__(exc_type, exc, tb)
        if exc_type is None and self.into is not None:
            if self.add:
                with _add_lock:
                    self.into[self.key] = round(
                        self.into.get(self.key, 0.0) + self.ms, 3)
            else:
                self.into[self.key] = round(self.ms, 3)
        return False
