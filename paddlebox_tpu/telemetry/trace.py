"""Span tracing: ``span("name")`` -> Chrome-trace-format JSON.

The host-side counterpart of the jax.profiler device timeline
(utils/profiler.device_trace): where the XLA trace shows per-fusion device
time, these spans show where a PASS spent its host wall clock — plan vs
feed assembly vs device step vs dump, host-plane gathers, shuffle
exchanges, checkpoint saves — with parent/child nesting.  The output is
the Chrome trace event format ("traceEvents" with complete "X" events),
which Perfetto / chrome://tracing open directly; the reference's CUPTI
timeline (platform/device_tracer.cc) served the same role for its CUDA
stack.

Tracing to FILES is off by default; the always-on flight ring
(:mod:`flight`) still receives every span, so a crash dump carries the
recent span history even in a process that never wrote a trace file.
Nesting is tracked with a per-thread span stack: children carry their
parent's name in ``args`` and Perfetto nests same-tid "X" events by time
containment.  When a distributed :mod:`context` is active (a routed
score request, a traced publish), each span also allocates a child span
ID under it, so spans recorded in DIFFERENT processes chain into one
trace for ``tools/pbox_doctor.py --trace <id>``.

Trace files carry a wall-clock anchor (``pboxWallT0``) next to the
perf-counter timestamps, so the doctor can merge spans from many
processes onto one wall-time axis.

One clock with the device: every span also enters a
``jax.profiler.TraceAnnotation("pbox.<name>")`` (:func:`annotation`), so
whenever a ``jax.profiler`` trace is running — an operator's
``TrainerConfig.trace_dir`` or a benchmark's own — the span lands on that
trace's host plane in the same nanoseconds as the device's ``XLA Ops``
line and can be laid over a device gap.  With no trace running the
annotation is a flag test.
"""

from __future__ import annotations

import contextlib
import json
import os
import sys
import threading
import time
from typing import Iterator, Optional

from paddlebox_tpu.telemetry import context as _context
from paddlebox_tpu.telemetry import flight as _flight


ANNOTATION_PREFIX = "pbox."
_trace_annotation = None  # jax.profiler.TraceAnnotation once imported


def annotation(name: str):
    """``jax.profiler.TraceAnnotation("pbox." + name)``: the span on the
    device trace's own clock.  A process that has not imported jax cannot
    be running its profiler, so it gets a ``nullcontext`` and stays
    jax-free (the router, the doctor, serving-side tooling)."""
    global _trace_annotation
    if _trace_annotation is None:
        if "jax" not in sys.modules:
            return contextlib.nullcontext()
        from jax.profiler import TraceAnnotation

        _trace_annotation = TraceAnnotation
    return _trace_annotation(ANNOTATION_PREFIX + name)


class Tracer:
    """Collects span events; ``write(path)`` emits one Chrome-trace JSON."""

    def __init__(self, process_name: str = "pbox", pid: int = 0):
        self._lock = threading.Lock()
        self._events: list = []
        self._t0 = time.perf_counter()
        # wall instant matching _t0: lets an offline reader place these
        # perf-counter timestamps on the same axis as other processes'
        self._wall_t0 = time.time()
        self._tls = threading.local()
        self.pid = int(pid)  # rank, so multi-rank traces merge cleanly
        self.process_name = process_name

    # -- recording ---------------------------------------------------------- #
    def _now_us(self) -> float:
        return (time.perf_counter() - self._t0) * 1e6

    def _stack(self) -> list:
        st = getattr(self._tls, "stack", None)
        if st is None:
            st = self._tls.stack = []
        return st

    def current(self) -> Optional[str]:
        """The innermost open span of the calling thread."""
        st = self._stack()
        return st[-1] if st else None

    def adopt(self, parent: Optional[str]) -> None:
        """Start the calling thread's span stack under ``parent``: a worker
        thread's spans then name the span that caused them (the feed
        producer under its consumer's ``pass``) instead of no parent."""
        self._tls.stack = [parent] if parent else []

    @contextlib.contextmanager
    def span(self, name: str, **meta) -> Iterator[None]:
        stack = self._stack()
        parent = stack[-1] if stack else None
        stack.append(name)
        start = self._now_us()
        try:
            yield
        finally:
            dur = self._now_us() - start
            stack.pop()
            args = {k: v for k, v in meta.items()}
            if parent is not None:
                args["parent"] = parent
            ev = {
                "name": name,
                "ph": "X",
                "ts": start,
                "dur": dur,
                "pid": self.pid,
                "tid": threading.get_ident() % 2**31,
            }
            if args:
                ev["args"] = args
            with self._lock:
                self._events.append(ev)

    def now_us(self) -> float:
        """The tracer clock (µs since tracer start) — pair with
        :meth:`add_span` for retroactive spans."""
        return self._now_us()

    def add_span(self, name: str, start_us: float, dur_us: float,
                 **meta) -> None:
        """Record a span measured externally (e.g. around a blocking wait
        instrumented with its own timer)."""
        ev = {
            "name": name, "ph": "X", "ts": start_us, "dur": dur_us,
            "pid": self.pid, "tid": threading.get_ident() % 2**31,
        }
        if meta:
            ev["args"] = dict(meta)
        with self._lock:
            self._events.append(ev)

    def instant(self, name: str, **meta) -> None:
        """A zero-duration marker (pass boundaries, aborts)."""
        ev = {
            "name": name, "ph": "i", "s": "t",
            "ts": self._now_us(), "pid": self.pid,
            "tid": threading.get_ident() % 2**31,
        }
        if meta:
            ev["args"] = dict(meta)
        with self._lock:
            self._events.append(ev)

    # -- output ------------------------------------------------------------- #
    def drain(self) -> list:
        with self._lock:
            evs, self._events = self._events, []
            return evs

    def to_dict(self, events: Optional[list] = None) -> dict:
        evs = self.drain() if events is None else events
        meta = [{
            "name": "process_name", "ph": "M", "pid": self.pid, "tid": 0,
            "args": {"name": f"{self.process_name}-r{self.pid}"},
        }]
        return {
            "traceEvents": meta + evs,
            "displayTimeUnit": "ms",
            # extra top-level keys are ignored by Perfetto/chrome://tracing
            # but give pbox_doctor the wall-clock anchor + identity it
            # needs to merge traces across processes
            "pboxWallT0": self._wall_t0,
            "pboxRank": self.pid,
            "pboxProcess": self.process_name,
        }

    def write(self, path: str) -> str:
        """Flush collected spans to ``path`` (Perfetto-loadable) and clear
        the buffer; returns the path."""
        d = os.path.dirname(path)
        if d:
            os.makedirs(d, exist_ok=True)
        with open(path, "w") as f:
            json.dump(self.to_dict(), f)
        return path


# --------------------------------------------------------------------------- #
# process-global tracer (None = tracing off; span() is then a no-op)
# --------------------------------------------------------------------------- #
_lock = threading.Lock()
_tracer: Optional[Tracer] = None


def enable_tracing(pid: int = 0, process_name: str = "pbox") -> Tracer:
    """Install (or return) the process tracer; idempotent."""
    global _tracer
    with _lock:
        if _tracer is None:
            _tracer = Tracer(process_name=process_name, pid=pid)
        return _tracer


def disable_tracing() -> None:
    global _tracer
    with _lock:
        _tracer = None


def get_tracer() -> Optional[Tracer]:
    return _tracer


@contextlib.contextmanager
def _recorded_span(t: Optional[Tracer], name: str, meta: dict):
    """One span, recorded everywhere it belongs: the tracer (when file
    tracing is on), the always-on flight ring, and — when a distributed
    trace context is active — under a freshly-allocated child span ID so
    cross-process parentage survives into the dump files."""
    ctx = _context.current()
    child = ctx.child() if ctx is not None else None
    tf: dict = {}
    if child is not None:
        tf = {"trace_id": child.trace_id, "span_id": child.span_id}
        if child.parent_span_id:
            tf["parent_span_id"] = child.parent_span_id
    start_wall = time.time()
    t0 = time.perf_counter()
    try:
        with _context.activate(child), annotation(name):
            if t is not None:
                with t.span(name, **{**meta, **tf}):
                    yield
            else:
                yield
    finally:
        flat = {
            k: v for k, v in meta.items()
            if isinstance(v, (str, int, float, bool))
        }
        _flight.record(
            "span", name, t=start_wall,
            dur_s=time.perf_counter() - t0, **flat, **tf,
        )


def span(name: str, **meta):
    """Record a span: always into the flight ring and onto any running
    ``jax.profiler`` trace (``pbox.<name>``), into the Chrome-trace tracer
    when one is enabled, and under the active distributed trace context
    when one is installed."""
    return _recorded_span(_tracer, name, meta)


def current_span() -> Optional[str]:
    """The calling thread's innermost open span (None when file tracing
    is off: the span stack is the tracer's)."""
    t = _tracer
    return t.current() if t is not None else None


def adopt_span(parent: Optional[str]) -> None:
    """Call first on a worker thread: its spans record ``parent`` (the
    spawning thread's :func:`current_span`) as the span that caused them."""
    t = _tracer
    if t is not None:
        t.adopt(parent)


def instant(name: str, **meta) -> None:
    flat = {
        k: v for k, v in meta.items()
        if isinstance(v, (str, int, float, bool))
    }
    _flight.record("instant", name, **flat)
    t = _tracer
    if t is not None:
        t.instant(name, **{**meta, **_context.trace_fields()})


def flush_trace(path: str) -> Optional[str]:
    """Write and clear the active tracer's spans (None when disabled)."""
    t = _tracer
    if t is None:
        return None
    return t.write(path)
