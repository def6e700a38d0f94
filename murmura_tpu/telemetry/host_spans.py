"""Host spans of the orchestrator, on the profiler's clock.

``span(name, round=...)`` brackets one piece of host work of a round
(``core/network.py``: staging the step's inputs, each dispatch, the fetch
of a round's metrics, the bookkeeping, a checkpoint).  Three things come of
one bracket:

- While a ``jax.profiler`` session is active (``tpu.profile_dir``, the
  telemetry profile window, a benchmark's ``--trace 1``: whoever started
  one) the bracket is a ``jax.profiler.TraceAnnotation`` (with
  ``step=True`` a ``StepTraceAnnotation``), so it lies in the ``/host:CPU``
  plane of the same ``.xplane.pb`` as the device's ``XLA Ops``
  (docs/OBSERVABILITY.md "Host spans and device scopes").
- Always, its count and seconds are added to a process-wide table by name.
  A span given a ``compiles`` counter (the dispatches:
  ``analysis/sanitizers.compile_count``, whose listener ``Network.train``
  installs) also goes into a second table when the counter rose while it
  was open: which dispatch paid for a compile or a load from the
  persistent cache, and how long it took.
- Only while a profiler session is active, a full record (name, start,
  end, round, the enclosing span on this thread, arguments) goes into a
  bounded ring.  The ring holds the newest session's spans: the first span
  of a new session empties it and keeps the first table as it stood then,
  so the spans since are the table's rise, and those after the session
  closed (the untraced truth of the same loop) the rise less the ring.
  With no session the ring stays empty.

Beside the spans, ``add_counters`` keeps a table of the round's counters
(``core/network.py`` adds every recorded round's ``agg_*`` means), with the
same snapshot at the start of the newest session.

There is no switch of its own: "tracing on" is "a profiler session is
active".  Off, a span costs two clock reads, one flag test and a dict
update.
"""

import collections
import itertools
import threading
import time
from typing import Any, Callable, Dict, List, Optional

from jax.profiler import StepTraceAnnotation, TraceAnnotation

RING_SPANS = 4096

_lock = threading.Lock()
_spans: Dict[str, List[float]] = {}  # name -> [count, seconds]
_first_dispatch: Dict[str, List[float]] = {}  # the same, compiling spans only
_before_session: Dict[str, List[float]] = {}  # _spans as the newest session began
_counters: Dict[str, List[float]] = {}  # name -> [rounds, sum of the values]
_counters_before_session: Dict[str, List[float]] = {}
_ring: "collections.deque[Dict[str, Any]]" = collections.deque(maxlen=RING_SPANS)
_ids = itertools.count(1)
_open = threading.local()  # .stack: ids of this thread's open recorded spans
_tracing = False  # whether the last span entered under a profiler session


def _add(table: Dict[str, List[float]], name: str, value: float) -> None:
    row = table.get(name)
    if row is None:
        row = table[name] = [0, 0.0]
    row[0] += 1
    row[1] += value


def _copy(table: Dict[str, List[float]]) -> Dict[str, List[float]]:
    return {name: list(row) for name, row in table.items()}


class span:
    """``with span("murmura.host.stage", round=r): ...``; after the block,
    ``seconds`` is its duration (the clock is read once, so a caller that
    needs the time takes it from here).  ``step=True`` marks the span as a
    step of the trace (``step_num=round``); ``compiles`` is a function that
    reads a compile counter, whose rise across the span becomes its
    ``compiled`` argument."""

    __slots__ = ("name", "round", "args", "seconds", "_step", "_compiles",
                 "_start", "_compiles_at", "_annotation", "_record")

    def __init__(self, name: str, round: Optional[int] = None, step: bool = False,
                 compiles: Optional[Callable[[], int]] = None, **args: Any):
        self.name, self.round, self.args = name, round, args
        self._step, self._compiles = step, compiles
        self.seconds = 0.0
        self._annotation = self._record = None

    def __enter__(self) -> "span":
        global _tracing, _before_session, _counters_before_session
        tracing = TraceAnnotation.is_enabled()
        if tracing and not _tracing:
            with _lock:  # a new session: the ring is this one's
                _ring.clear()
                _before_session = _copy(_spans)
                _counters_before_session = _copy(_counters)
        _tracing = tracing
        if tracing:
            if self._step:
                self._annotation = StepTraceAnnotation(
                    self.name, step_num=self.round, **self.args
                )
            else:
                named = {} if self.round is None else {"round": self.round}
                self._annotation = TraceAnnotation(self.name, **named, **self.args)
            stack = getattr(_open, "stack", None)
            if stack is None:
                stack = _open.stack = []
            self._record = {
                "id": next(_ids), "name": self.name, "round": self.round,
                "parent": stack[-1] if stack else None, "args": self.args,
            }
            stack.append(self._record["id"])
            self._annotation.__enter__()
        if self._compiles is not None:
            self._compiles_at = self._compiles()
        self._start = time.perf_counter_ns()
        return self

    def __exit__(self, *exc) -> None:
        end = time.perf_counter_ns()
        self.seconds = (end - self._start) / 1e9
        compiled = 0
        if self._compiles is not None:
            compiled = self._compiles() - self._compiles_at
        record = self._record
        if record is not None:
            if compiled:
                record["args"]["compiled"] = compiled
                self._annotation.set_metadata(compiled=compiled)
            self._annotation.__exit__(*exc)
            _open.stack.pop()
            record["start_ns"], record["end_ns"] = self._start, end
        with _lock:
            _add(_spans, self.name, self.seconds)
            if compiled:
                _add(_first_dispatch, self.name, self.seconds)
            if record is not None:
                _ring.append(record)


def add_counters(values: Dict[str, float]) -> None:
    """One recorded round's counters into the table ``counters``."""
    with _lock:
        for name, value in values.items():
            _add(_counters, name, value)


def totals() -> Dict[str, Dict[str, List[float]]]:
    """The tables since the process started: ``spans`` (every span),
    ``first_dispatch`` (the spans with a ``compiles`` counter during which a
    program was compiled or loaded), ``name -> [count, seconds]``;
    ``counters``, ``name -> [rounds, sum]`` (``add_counters``); and
    ``spans_before_session`` and ``counters_before_session``, the two
    tables as they stood when the newest profiler session's first span
    opened (empty before any session)."""
    with _lock:
        return {
            "spans": _copy(_spans),
            "first_dispatch": _copy(_first_dispatch),
            "spans_before_session": _copy(_before_session),
            "counters": _copy(_counters),
            "counters_before_session": _copy(_counters_before_session),
        }


def records() -> List[Dict[str, Any]]:
    """The ring: the newest profiler session's spans in the order they
    closed (a child before its parent).  ``start_ns``/``end_ns`` are
    ``time.perf_counter_ns``; ``parent`` is the ``id`` of the enclosing
    span on the same thread; spans of one round share ``round``; a span
    that saw compiles carries ``args["compiled"]``."""
    with _lock:
        return [dict(r, args=dict(r["args"])) for r in _ring]
