"""Spans at the layer boundaries of the checkpoint pipeline and the
serving engine.

``span(name, into=d, key=k, **args)`` times its block with two
``time.perf_counter()`` readings and always enters a
``jax.profiler.TraceAnnotation(name, **args)``, which records nothing
unless a profiler session is active: tracing is on exactly while one
is. With ``into``/``key`` the block's seconds are added to ``d[k]``, a
counter dict the caller already owns (the pipeline's ``stats``, a
``SnapshotHandle``'s or an ``Incarnation``'s ``timings``), so the
counter and the trace come from one measurement. Counts known only at
exit (bytes moved) are added to the same dict by the caller; the
span's ``seconds`` is readable after the block.

Names say the layer: ``ckpt.capture.*`` (caller thread), ``ckpt.encode``,
``ckpt.write``, ``ckpt.commit`` (pipeline threads), ``ckpt.restore.*``
and ``serve.*``. Spans of one save carry ``step=``, spans of one
admission ``rid=``.
"""
from __future__ import annotations

import threading
import time
from typing import Any, Dict, Optional

from jax.profiler import TraceAnnotation

# counters fed from several threads (the writer pool, the decode pool)
# add under this lock; a read-modify-write of a dict entry is not atomic
_ADD = threading.Lock()


class span:
    """Context manager: a profiler annotation plus a timed counter."""

    __slots__ = ("_ann", "_into", "_key", "_t0", "seconds")

    def __init__(self, name: str, *, into: Optional[Dict[str, Any]] = None,
                 key: Optional[str] = None, **args: Any) -> None:
        self._ann = TraceAnnotation(name, **args)
        self._into, self._key = into, key
        self.seconds = 0.0

    def __enter__(self) -> "span":
        self._ann.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        self.seconds = time.perf_counter() - self._t0
        self._ann.__exit__(*exc)
        if self._into is not None:
            add(self._into, self._key, self.seconds)


def add(into: Dict[str, Any], key: str, value) -> None:
    """``into[key] += value`` (from 0), safe from any thread."""
    with _ADD:
        into[key] = into.get(key, 0) + value
