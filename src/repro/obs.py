"""The program's tracer: spans and counters at the served path's layer
boundaries.

Off by default, and turned on only by :func:`enable`.  While off,
:func:`span` is one check of a module flag that hands back a shared no-op
context manager: no clock read, no allocation, no import of
``jax.profiler``; :func:`record` and :func:`count` return at once.

While on, each :func:`span` records its name, start and end
(``time.perf_counter_ns``), the id of the span open around it (its parent)
and the current request id, and opens a ``jax.profiler.TraceAnnotation``
of the same name, so it also lands in a profiler trace on the device
trace's clock.  A span opened with ``root=True`` starts a request: its
sequence number since :func:`reset` is the request id that the spans and
records under it carry.  Per name the tracer keeps ``count``, ``total_s``
and ``self_s`` (the duration minus the part its child spans cover).

Records go into a buffer of at most :data:`CAPACITY` entries; past it
they are counted as ``dropped``, while per-name totals and counters keep
counting.  :func:`snapshot` is the only export.

The served path is single-threaded; each thread still nests its own spans,
and the shared totals are updated under a lock.
"""
from __future__ import annotations

import itertools
import threading
import time
from typing import Dict, List, Optional

#: Records kept before further ones are only counted as dropped.
CAPACITY = 1 << 16

_on = False


class _Noop:
    """The span handed out while the tracer is off."""

    __slots__ = ()
    start_ns = 0

    def __enter__(self) -> "_Noop":
        return self

    def __exit__(self, *exc) -> bool:
        return False


_NOOP = _Noop()


class _State:
    def __init__(self) -> None:
        self.lock = threading.Lock()
        self.local = threading.local()
        self.annotation = None          # jax.profiler.TraceAnnotation
        self.reset()

    def reset(self) -> None:
        #: (name, id, parent, request, start_ns, end_ns)
        self.records: List[tuple] = []
        #: name -> [count, total_ns, self_ns]
        self.totals: Dict[str, List[int]] = {}
        self.counters: Dict[str, int] = {}
        self.dropped = 0
        # next() of an itertools.count is atomic under the interpreter lock.
        self.ids = itertools.count(1)
        self.requests = itertools.count(1)

    def stack(self) -> list:
        try:
            return self.local.stack
        except AttributeError:
            stack = self.local.stack = []
            return stack

    def finish(self, name: str, span_id: Optional[int],
               parent: Optional[int], request: Optional[int], start: int,
               end: int, child_ns: int) -> None:
        with self.lock:
            tot = self.totals.get(name)
            if tot is None:
                tot = self.totals[name] = [0, 0, 0]
            tot[0] += 1
            tot[1] += end - start
            tot[2] += end - start - child_ns
            if len(self.records) < CAPACITY:
                self.records.append((name, span_id, parent, request, start,
                                     end))
            else:
                self.dropped += 1


_state = _State()


class _Span:
    __slots__ = ("name", "root", "id", "parent", "request", "start_ns",
                 "child_ns", "_annotation")

    def __init__(self, name: str, root: bool):
        self.name = name
        self.root = root

    def __enter__(self) -> "_Span":
        st = _state
        stack = st.stack()
        self.id = next(st.ids)
        if stack:
            outer = stack[-1]
            self.parent = outer.id
            self.request = outer.request
        else:
            self.parent = self.request = None
        if self.root:
            self.request = next(st.requests)
        self.child_ns = 0
        self._annotation = st.annotation(self.name)
        self._annotation.__enter__()
        stack.append(self)
        self.start_ns = time.perf_counter_ns()
        return self

    def __exit__(self, *exc) -> bool:
        end = time.perf_counter_ns()
        self._annotation.__exit__(*exc)
        st = _state
        stack = st.stack()
        stack.pop()
        if stack:
            stack[-1].child_ns += end - self.start_ns
        st.finish(self.name, self.id, self.parent, self.request,
                  self.start_ns, end, self.child_ns)
        return False


def enable() -> None:
    """Turn the tracer on (imports ``jax.profiler`` for the annotations)."""
    global _on
    from jax.profiler import TraceAnnotation
    _state.annotation = TraceAnnotation
    _on = True


def disable() -> None:
    """Turn the tracer off; what it holds stays until :func:`reset`."""
    global _on
    _on = False


def enabled() -> bool:
    return _on


def reset() -> None:
    """Drop every record, total and counter, and restart the ids."""
    with _state.lock:
        _state.reset()


def span(name: str, root: bool = False):
    """A context manager timing the code under it as span ``name``; with
    ``root=True`` it starts a new request."""
    if not _on:
        return _NOOP
    return _Span(name, root)


def record(name: str, start_ns: int, end_ns: int) -> None:
    """File a span whose start the program stamped earlier
    (``time.perf_counter_ns``), under the current request and the span
    open now.  It lies before that span, so it takes nothing from the
    span's self time, and it gets no profiler annotation."""
    if not _on:
        return
    stack = _state.stack()
    outer = stack[-1] if stack else None
    _state.finish(name, None, outer.id if outer is not None else None,
                  outer.request if outer is not None else None,
                  start_ns, end_ns, 0)


def count(name: str, n: int = 1) -> None:
    """Add ``n`` to counter ``name``."""
    if not _on:
        return
    with _state.lock:
        _state.counters[name] = _state.counters.get(name, 0) + n


def snapshot() -> dict:
    """Everything recorded since :func:`reset`, as plain data:

    * ``spans``: per name ``count``, ``total_s``, ``self_s`` and
      ``durations_s`` (of the records kept);
    * ``counters``: per name the sum;
    * ``records``: each kept record as a dict of ``name``, ``id`` (None
      for a :func:`record`), ``parent``, ``request``, ``start_ns`` and
      ``end_ns``, in the order they ended;
    * ``dropped``: records past :data:`CAPACITY`.
    """
    with _state.lock:
        records = list(_state.records)
        totals = {k: list(v) for k, v in _state.totals.items()}
        counters = dict(_state.counters)
        dropped = _state.dropped
    durations: Dict[str, List[float]] = {k: [] for k in totals}
    for name, _, _, _, start, end in records:
        durations[name].append((end - start) * 1e-9)
    keys = ("name", "id", "parent", "request", "start_ns", "end_ns")
    return {
        "spans": {k: {"count": c, "total_s": t * 1e-9, "self_s": s * 1e-9,
                      "durations_s": durations[k]}
                  for k, (c, t, s) in totals.items()},
        "counters": counters,
        "records": [dict(zip(keys, r)) for r in records],
        "dropped": dropped,
    }
