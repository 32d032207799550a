"""Nestable span tracer with Chrome trace-event JSON export.

One process-wide :class:`Tracer` (:data:`TRACER`) records *spans* — named
wall-clock intervals with key/value annotations and additive counters —
around the stack's hot boundaries: scheduler event processing, placement
search, netsim draining, backend dispatch, planner candidate pricing.
Spans nest per thread (the exporter reconstructs the hierarchy from
interval containment), and the recorded stream exports as Chrome
trace-event JSON (``"X"`` complete events), directly loadable in
Perfetto / ``chrome://tracing``.

Tracing is **globally off by default** and the disabled path is near
zero: ``TRACER.span(...)`` returns a shared no-op context manager after
one attribute check, and the hot call sites additionally guard on
``TRACER.enabled`` so no argument dict is even built.

While tracing is on and ``jax`` is already imported, every recorded span
is also emitted as a profiler ``TraceMe`` (``jax.profiler.TraceAnnotation``)
named ``layer:what`` — the span's name with its first ``.`` turned into
``:`` (``scheduler.reserve`` -> ``scheduler:reserve``).  Under
``jax.profiler.start_trace`` the spans then sit in the host plane of the
same profile as the device's operations, on the profiler's clock.  This
module never imports jax itself.  Enabling tracing
never perturbs results — spans only *measure*; the scheduler event log,
netsim makespans, and planner tables are bit-identical either way
(pinned in ``tests/test_obs.py``, overhead gated in ``BENCH_obs.json``).

>>> TRACER.enabled
False
>>> with TRACER.span("demo"):       # no-op: tracing is off
...     pass
>>> TRACER.events()
[]
"""

from __future__ import annotations

import json
import os
import sys
import threading
import time
from typing import Any, Dict, List, Optional

__all__ = ["Span", "Timer", "Tracer", "TRACER"]


class Span:
    """One live span: a named interval opened by :meth:`Tracer.span`.

    Use as a context manager; :meth:`annotate` attaches key/value pairs
    and :meth:`incr` accumulates additive counters — both land in the
    exported event's ``args``."""

    __slots__ = ("name", "args", "tid", "_tracer", "_t0", "_traceme", "duration")

    def __init__(self, tracer: "Tracer", name: str, args: Dict[str, Any]):
        self._tracer = tracer
        self.name = name
        self.args = args
        self.tid = threading.get_ident()
        self._t0 = 0
        self._traceme = None
        self.duration = 0.0  # seconds, set at exit

    def annotate(self, **kv: Any) -> "Span":
        """Attach key/value annotations to the span."""
        self.args.update(kv)
        return self

    def incr(self, key: str, n: float = 1) -> "Span":
        """Accumulate an additive counter in the span's args."""
        self.args[key] = self.args.get(key, 0) + n
        return self

    def __enter__(self) -> "Span":
        self._traceme = self._tracer._enter_profiler(self.name)
        self._t0 = time.perf_counter_ns()
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        t1 = time.perf_counter_ns()
        self.duration = (t1 - self._t0) * 1e-9
        self._tracer._record(self, self._t0, t1)
        if self._traceme is not None:
            self._traceme.__exit__(exc_type, exc, tb)
        return False


class _NoopSpan:
    """Shared disabled-path span: every method is a cheap no-op."""

    __slots__ = ()
    name = ""
    args: Dict[str, Any] = {}
    duration = 0.0

    def annotate(self, **kv: Any) -> "_NoopSpan":
        return self

    def incr(self, key: str, n: float = 1) -> "_NoopSpan":
        return self

    def __enter__(self) -> "_NoopSpan":
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        return False


_NOOP = _NoopSpan()


class Timer:
    """Always-measuring wall-clock context manager (``obs.timer``).

    Replaces ad-hoc ``time.perf_counter()`` pairs: ``elapsed`` is always
    populated (seconds), and when tracing is enabled the interval is
    *also* recorded as a span — so driver wall-clock numbers land in the
    same trace stream as the engine spans."""

    __slots__ = ("name", "args", "elapsed", "_tracer", "_t0", "_traceme")

    def __init__(self, tracer: "Tracer", name: str, args: Dict[str, Any]):
        self._tracer = tracer
        self.name = name
        self.args = args
        self.elapsed = 0.0
        self._t0 = 0
        self._traceme = None

    def annotate(self, **kv: Any) -> "Timer":
        """Attach key/value annotations (recorded when tracing is on)."""
        self.args.update(kv)
        return self

    def __enter__(self) -> "Timer":
        if self._tracer.enabled:
            self._traceme = self._tracer._enter_profiler(self.name)
        self._t0 = time.perf_counter_ns()
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        t1 = time.perf_counter_ns()
        self.elapsed = (t1 - self._t0) * 1e-9
        if self._tracer.enabled:
            span = Span(self._tracer, self.name, self.args)
            span.duration = self.elapsed
            self._tracer._record(span, self._t0, t1)
        if self._traceme is not None:
            self._traceme.__exit__(exc_type, exc, tb)
            self._traceme = None
        return False


class Tracer:
    """Thread-safe span recorder exporting Chrome trace-event JSON.

    ``enabled`` is a plain attribute — the single check the disabled
    path pays.  Finished spans append under a lock as ``"X"`` (complete)
    trace events with microsecond ``ts``/``dur`` relative to the
    tracer's epoch; per-thread ``tid`` keeps nesting reconstructible.
    """

    def __init__(self) -> None:
        self.enabled = False
        self._lock = threading.Lock()
        self._events: List[Dict[str, Any]] = []
        self._epoch = time.perf_counter_ns()
        self._annotation = None  # jax.profiler.TraceAnnotation, once jax is imported

    # -- control ------------------------------------------------------------
    def enable(self, clear: bool = False) -> None:
        """Turn tracing on (optionally clearing recorded events first)."""
        if clear:
            self.clear()
        self.enabled = True

    def disable(self) -> None:
        """Turn tracing off; recorded events are kept until :meth:`clear`."""
        self.enabled = False

    def clear(self) -> None:
        """Drop all recorded events and reset the time epoch."""
        with self._lock:
            self._events = []
            self._epoch = time.perf_counter_ns()

    # -- recording ----------------------------------------------------------
    def span(self, name: str, **args: Any):
        """Open a span (context manager).  Disabled: returns a shared
        no-op after one attribute check — the near-zero path gated by
        ``BENCH_obs.json``."""
        if not self.enabled:
            return _NOOP
        return Span(self, name, args)

    def timer(self, name: str, **args: Any) -> Timer:
        """An always-measuring :class:`Timer` (span recorded only when
        tracing is enabled)."""
        return Timer(self, name, args)

    def _enter_profiler(self, name: str):
        """Enter the profiler annotation of a span about to start (None
        until ``jax`` has been imported by someone else)."""
        annotation = self._annotation
        if annotation is None:
            if "jax" not in sys.modules:
                return None
            from jax.profiler import TraceAnnotation

            annotation = self._annotation = TraceAnnotation
        traceme = annotation(name.replace(".", ":", 1))
        traceme.__enter__()
        return traceme

    def _record(self, span: Span, t0_ns: int, t1_ns: int) -> None:
        event = {
            "name": span.name,
            "ph": "X",
            "ts": (t0_ns - self._epoch) * 1e-3,  # microseconds
            "dur": (t1_ns - t0_ns) * 1e-3,
            "pid": os.getpid(),
            "tid": span.tid,
        }
        if span.args:
            event["args"] = dict(span.args)
        with self._lock:
            self._events.append(event)

    # -- export -------------------------------------------------------------
    def events(self) -> List[Dict[str, Any]]:
        """Snapshot of the recorded trace events (copies)."""
        with self._lock:
            return [dict(e) for e in self._events]

    def chrome_trace(self) -> Dict[str, Any]:
        """The Chrome trace-event JSON object (``traceEvents`` sorted by
        start time, parents before their children)."""
        events = self.events()
        events.sort(key=lambda e: (e["tid"], e["ts"], -e["dur"]))
        return {"traceEvents": events, "displayTimeUnit": "ms"}

    def export(self, path: Optional[str] = None) -> Dict[str, Any]:
        """Return the Chrome trace object, writing it to ``path`` (JSON)
        when given."""
        trace = self.chrome_trace()
        if path is not None:
            with open(path, "w") as fh:
                json.dump(trace, fh, indent=1)
        return trace


#: The process-wide tracer every instrumented module records into.
TRACER = Tracer()
