"""Named host spans on the profiler's clock.

:class:`span` times a block of host code and marks the same interval as a
``jax.profiler.TraceAnnotation``, so a profiler trace (when one is active)
shows the block under its name beside the device's work. With no trace
active the annotation costs about a microsecond.

The program's spans: ``campaign.startup``, ``campaign.stage``,
``campaign.h2d``, ``campaign.wait_h2d``, ``campaign.dispatch`` and
``campaign.collect`` in ``FleetRunner.run_campaign`` (their seconds go to
``last_stats``), and ``allocator.launch`` in ``OnlineAllocator.__call__``.
Beside them ``run_campaign`` counts rows: ``last_stats["rows_dispatched"]``
(padded rows of every dispatch) and ``last_stats["rows_loaded"]`` (rows
whose scenario has a source load schedule).

The device's work carries ``jax.named_scope`` names in its op_name:
``tick`` and ``maxmin`` in the campaign's scan, ``load`` around the
evaluation of a run's source-load grid (``simulator._load_over``), and
``per_link`` and ``backfill`` inside ``allocate``.
"""
from __future__ import annotations

import time

import jax


class span:
    """``with span(name, totals, key) as s:`` — on exit, normal or by an
    exception, the block's seconds are in ``s.seconds`` and, when
    ``totals`` is given, added to ``totals[key]``.

    A span that does not fit one ``with`` block is opened with
    :meth:`__enter__` and ended with :meth:`close`; closing it again does
    nothing."""

    __slots__ = ("name", "totals", "key", "seconds", "_t0", "_ann")

    def __init__(self, name: str, totals: dict | None = None,
                 key: str | None = None):
        self.name, self.totals, self.key = name, totals, key
        self.seconds = 0.0
        self._ann = None

    def __enter__(self) -> "span":
        self._ann = jax.profiler.TraceAnnotation(self.name)
        self._ann.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> bool:
        if self._ann is None:
            return False
        self.seconds = time.perf_counter() - self._t0
        ann, self._ann = self._ann, None
        ann.__exit__(*exc)
        if self.totals is not None:
            self.totals[self.key] += self.seconds
        return False

    def close(self) -> None:
        self.__exit__(None, None, None)
