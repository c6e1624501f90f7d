"""Stage timing on two clocks at once, the completion watcher, and the
one-pass device trace.

TPU-native replacement for the reference's two profiling surfaces
(SURVEY.md §5.1):

  * hand-rolled hot-path timers — per-device pull/push/nccl timers printed
    by ``PrintSyncTimer`` (box_wrapper.h:375-391) and per-op wall timing in
    ``BoxPSWorker::TrainFilesWithProfiler`` (boxps_worker.cc:657-760).
    Here the jitted step is one fused program, so the meaningful split is
    host stages (batch / plan / feed / feed_wait / step / drain / readback; the
    pass boundary's census / lookup / upload / ... ), which
    :class:`StatsProfiler` observes into the registry.
  * the framework profiler / CUPTI timeline (platform/profiler.cc,
    device_tracer.cc) — ``jax.profiler`` (``device_trace`` wraps a pass in
    an XLA trace viewable in TensorBoard/Perfetto).  Every stage enters a
    ``TraceAnnotation("pbox.<family>.<stage>")`` too, so that trace holds
    the host stages on its own clock, beside the device's operations.

There is ONE profiler and it is always on: a stage costs two clock reads,
a histogram observation and an annotation that is a flag test while no
trace runs.  Profiling never changes the loop — ``TrainerConfig.profile``
and the trace dirs only decide what is reported and written after a pass
(:meth:`StatsProfiler.report` over the pass's registry delta), never how
it is fed, dispatched or synchronised.

What the device did with each dispatch is the :class:`CompletionWatcher`'s
to say: the dispatching thread never waits for the device, so a daemon
thread does, in order, and observes ``trainer.step_complete_seconds``.

The path that runs before the loop has the same primitive: ``START`` is the
``start`` family (``start.stage_seconds{stage=}`` / ``pbox.start.<stage>``:
dataset_load with read_parse and merge, table_load with store_sort,
store_split, invalidate and log_rewrite, dense_load, trainer_init), one
observation a call, a child's seconds inside its parent's.

Whether the host let the threads run is :class:`HostStall`'s to say, once a
pass: each thread's own run-queue wait, the host's CPU pressure and steal
(``host.*`` counters, read from ``/proc``).
"""

from __future__ import annotations

import contextlib
import functools
import os
import queue
import resource
import threading
import time
from typing import Iterator, Optional

from paddlebox_tpu.telemetry import metrics as _tm
from paddlebox_tpu.telemetry import trace as _trace

# host stages are sub-ms to seconds: tighter boundaries than the default
# latency ladder so per-stage quantiles don't collapse into one bucket
STAGE_BUCKETS = (
    0.0001, 0.00025, 0.0005, 0.001, 0.0025, 0.005, 0.01, 0.025, 0.05,
    0.1, 0.25, 0.5, 1.0, 2.5, 5.0, 15.0, 60.0,
)

# completion intervals: 1 ms .. 30 s, neighbours 5% apart, so a window's
# p95 and its slowest step read from bucket counts alone to within 5%
COMPLETE_BUCKETS = tuple(
    round(0.001 * 1.05 ** i, 7) for i in range(213)
)


def stage_histogram(metric: str = "trainer.stage_seconds") -> _tm.Histogram:
    return _tm.histogram(
        metric, help="host pipeline stage latency (s)", buckets=STAGE_BUCKETS
    )


class _Timed:
    """One timed region: histogram observation + ``pbox.*`` annotation on
    any running device trace (+ a Chrome-trace span while file tracing is
    on).  A plain class, not a generator: this runs several times a step."""

    __slots__ = ("_hist", "_labels", "_ann", "_span", "_t0")

    def __init__(self, hist: _tm.Histogram, ann_name: str,
                 span_name: Optional[str], labels: dict):
        self._hist = hist
        self._labels = labels
        self._ann = _trace.annotation(ann_name)
        tracer = _trace.get_tracer() if span_name else None
        self._span = tracer.span(span_name) if tracer is not None else None

    def __enter__(self):
        if self._span is not None:
            self._span.__enter__()
        self._ann.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        dt = time.perf_counter() - self._t0
        self._ann.__exit__(*exc)
        if self._span is not None:
            self._span.__exit__(*exc)
        self._hist.observe(dt, **self._labels)
        return False


def timed(metric: str, ann_name: str, help: str = ""):
    """Time a region into the unlabeled histogram ``metric`` and onto the
    device trace as ``pbox.<ann_name>`` (``data.census_seconds``)."""
    return _Timed(_tm.histogram(metric, help), ann_name, None, {})


class StatsProfiler:
    """Named stages of one family (``trainer``, ``pass``): each stage body
    is observed into ``<family>.stage_seconds{stage=<name>}``, annotated
    ``pbox.<family>.<name>`` on a running ``jax.profiler`` trace, and —
    while Chrome-trace file tracing is enabled — recorded as a ``<name>``
    span under the thread's current span.  Stages auto-create on first
    use.  Always on, in every run, and the same loop either way."""

    STAGES = ("batch", "plan", "feed", "feed_wait", "step", "dump")  # report order

    def __init__(self, metric: str = "trainer.stage_seconds"):
        self.family = metric.split(".", 1)[0]
        self._hist = stage_histogram(metric)

    def stage(self, name: str) -> _Timed:
        return _Timed(self._hist, f"{self.family}.{name}", name,
                      {"stage": name})

    def iterate(self, name: str, iterable) -> Iterator:
        """``iterable``'s items, each ``next()`` timed as the stage (the
        feed producer's batch assembly inside ``dataset.batches()``)."""
        it = iter(iterable)
        while True:
            with self.stage(name):
                try:
                    item = next(it)
                except StopIteration:
                    return
            yield item

    def wrap(self, name: str):
        """Decorator: the whole function is the stage."""
        def deco(fn):
            @functools.wraps(fn)
            def staged(*args, **kwargs):
                with self.stage(name):
                    return fn(*args, **kwargs)
            return staged
        return deco

    # -- the pass report: a registry delta, not a second set of timers ---- #
    def mark(self) -> dict:
        """{stage: (sum, count, bucket counts)} now; hand it to
        :meth:`report` after the pass."""
        out = {}
        for key, s in self._hist.series().items():
            stage = dict(key).get("stage")
            if stage is not None:
                out[stage] = (s.sum, s.count, list(s.counts))
        return out

    def report(self, mark: dict, n_steps: int,
               complete_mark: Optional[tuple] = None) -> dict:
        """Per-stage totals, counts, means (s) and quantiles since ``mark``:
        ``steps``, ``<stage>_sec``, ``<stage>_count``,
        ``<stage>_ms_per_step``, ``stage_quantiles``.  ``step`` is the
        enqueue; ``complete`` (with ``complete_mark`` from
        :meth:`CompletionWatcher.mark`) is the device's completion
        interval per dispatch."""
        now = self.mark()
        if complete_mark is not None:
            now["complete"] = CompletionWatcher.mark()
            mark = dict(mark, complete=complete_mark)
        order = [s for s in self.STAGES if s in now] + sorted(
            s for s in now if s not in self.STAGES)
        out: dict = {"steps": n_steps}
        quant: dict = {}
        for name in order:
            s1, c1, b1 = now[name]
            s0, c0, b0 = mark.get(name, (0.0, 0, [0] * len(b1)))
            if c1 == c0:
                continue
            out[f"{name}_sec"] = s1 - s0
            out[f"{name}_count"] = c1 - c0
            if n_steps:
                out[f"{name}_ms_per_step"] = 1e3 * (s1 - s0) / n_steps
            bounds = (COMPLETE_BUCKETS if name == "complete"
                      else self._hist.boundaries)
            q = _delta_quantiles(bounds, [a - b for a, b in zip(b1, b0)])
            quant[name] = {"p50_ms": round(q[0] * 1e3, 3),
                           "p99_ms": round(q[1] * 1e3, 3),
                           "count": c1 - c0}
        if quant:
            out["stage_quantiles"] = quant
        return out

    @staticmethod
    def log_line(report: dict) -> str:
        """One-line summary (the reference's log_for_profile format spirit)."""
        parts = [f"steps={report['steps']}"]
        parts += [f"{k[:-len('_ms_per_step')]}={v:.2f}ms"
                  for k, v in report.items() if k.endswith("_ms_per_step")]
        return " ".join(parts)


#: the job-start path's stages: what runs between a restart and the first
#: trained pass, outside the pass loop's ``trainer`` and ``pass`` families
START = StatsProfiler("start.stage_seconds")


def pass_seconds() -> _tm.Histogram:
    """``trainer.pass_seconds``: one observation a ``train_from_dataset``,
    the ``duration_s`` its metrics carry (no other series holds a whole
    pass: the passes before a benchmark's window are its check steps and
    its warm-up cycle)."""
    return _tm.histogram(
        "trainer.pass_seconds", "wall time of one train_from_dataset (s)",
        buckets=STAGE_BUCKETS)


def _delta_quantiles(boundaries, counts) -> tuple:
    """(p50, p99) of a window's bucket-count delta; a bucket's own edges
    stand in for the observed min and max, which a delta does not have."""
    grown = [i for i, c in enumerate(counts) if c > 0]
    top = len(boundaries) - 1
    lo = boundaries[min(grown[0], top) - 1] if grown[0] > 0 else 0.0
    hi = boundaries[min(grown[-1], top)]
    return tuple(
        _tm.quantile_from_buckets(boundaries, counts, sum(counts), lo, hi, q)
        for q in (0.5, 0.99))


class CompletionWatcher:
    """When each dispatch finished on the device, without a sync on the
    thread that dispatches.

    The trainer hands every dispatch's ``loss`` to :meth:`dispatched` (one
    queue put); a daemon thread calls ``block_until_ready()`` on them in
    order and observes ``trainer.step_complete_seconds``: completion(i) −
    max(completion(i−1), dispatch(i)), the time the device spent on
    dispatch i once it could start it.  ``trainer.dispatches`` counts
    dispatches, ``trainer.dispatches_starved`` those that found none in
    flight — the device had run dry and waited for the host."""

    _STOP = object()
    _ACCOUNT = object()

    def __init__(self):
        self._q: "queue.Queue" = queue.Queue()
        self._thread: Optional[threading.Thread] = None
        self._lock = threading.Lock()
        self._n_dispatched = 0
        self._n_completed = 0  # written by the watcher thread only
        self._last_done = 0.0
        self._hist = self._histogram()
        self._dispatches = _tm.counter(
            "trainer.dispatches", "device dispatches of the train step")
        self._starved = _tm.counter(
            "trainer.dispatches_starved",
            "dispatches that found no earlier one in flight: the device "
            "had run dry")

    @staticmethod
    def _histogram() -> _tm.Histogram:
        return _tm.histogram(
            "trainer.step_complete_seconds",
            "device completion interval per dispatch: completion(i) - "
            "max(completion(i-1), dispatch(i))",
            buckets=COMPLETE_BUCKETS)

    @classmethod
    def mark(cls) -> tuple:
        s = cls._histogram()._merged(None)
        return (s.sum, s.count, list(s.counts))

    def dispatched(self, result, t_dispatch: float) -> None:
        """``result``: a device array of the dispatch (its loss);
        ``t_dispatch``: ``time.perf_counter()`` just before the call."""
        if self._thread is None:
            with self._lock:
                if self._thread is None:
                    self._thread = threading.Thread(
                        target=self._run, name="step-complete", daemon=True)
                    self._thread.start()
        self._dispatches.inc()
        if self._n_dispatched == self._n_completed:
            self._starved.inc()
        self._n_dispatched += 1
        self._q.put((result, t_dispatch))

    def _run(self) -> None:
        while True:
            item = self._q.get()
            if item is self._STOP:
                return
            if item is self._ACCOUNT:
                HOST.thread("watch")
                continue
            result, t_dispatch = item
            try:
                result.block_until_ready()
            # pbox-lint: ignore[swallowed-exception] a failed or deleted
            # dispatch still completed; its error is the dispatching
            # thread's to raise at its own read-back
            except Exception:
                pass
            done = time.perf_counter()
            self._hist.observe(done - max(self._last_done, t_dispatch))
            self._last_done = done
            # pbox-lint: ignore[thread-shared-state] one writer (this
            # thread); dispatched() and settle() only compare it with their
            # own count, and a read one sample late is a dispatch counted
            # as "in flight" that had just finished
            self._n_completed += 1
            del item, result

    def settle(self, timeout_s: float = 1.0) -> None:
        """Let the watcher observe what the caller has already waited for
        (call after ``block_until_ready`` on the last dispatch): the pass's
        samples are then all in the registry when its report is read."""
        deadline = time.perf_counter() + timeout_s
        while (self._n_completed < self._n_dispatched
               and time.perf_counter() < deadline):
            time.sleep(0.0002)

    def account(self) -> None:
        """Have the watcher thread add its own run-queue wait to
        ``host.runqueue_wait_seconds{thread=watch}`` (once a pass, after
        ``settle``: one queue put; no thread, nothing to account for)."""
        if self._thread is not None:
            self._q.put(self._ACCOUNT)

    def close(self) -> None:
        """Retire the thread (it finishes what it holds first)."""
        with self._lock:
            thread, self._thread = self._thread, None
        if thread is not None:
            self._q.put(self._STOP)
            thread.join(timeout=5.0)


class HostStall:
    """Did the host let the pass's threads run?  When ``step_complete``,
    ``batch`` and ``feed_wait`` stretch together, four cumulative readings
    tell the causes apart: a thread was runnable and not running
    (``host.runqueue_wait_seconds{thread=}``, the second field of its own
    ``/proc/thread-self/schedstat``), the whole host lacked CPU
    (``host.cpu_pressure_seconds``, ``some ... total=`` of
    ``/proc/pressure/cpu``), the VM was not running
    (``host.steal_seconds``, the ``steal`` column of ``/proc/stat``), the
    kernel took the CPU from the process (``host.involuntary_switches``,
    ``ru_nivcsw``) -- or none of them moved and the process stood still
    for another reason.

    Each thread reads its own file, once a pass (:meth:`thread`); the
    dispatching thread reads the process's and the host's too
    (:meth:`process`).  A reading adds its growth since the same thread's
    (the process's) previous one, from zero at the first, so a series is
    cumulative from the thread's (the host's: from the first reading's)
    start.  A file the kernel does not have leaves its series absent,
    never an error."""

    def __init__(self, root: str = "/proc"):
        self._root = root
        self._tls = threading.local()
        self._last: dict = {}
        self._tick = float(os.sysconf("SC_CLK_TCK"))

    def _first_line(self, *path: str, starts: str = "") -> Optional[list]:
        try:
            with open(os.path.join(self._root, *path)) as f:
                for line in f:
                    if line.startswith(starts):
                        return line.split()
        except OSError:
            pass
        return None

    def thread(self, name: str) -> None:
        """The calling thread's run-queue wait since its previous call."""
        fields = self._first_line("thread-self", "schedstat")
        if fields is None or len(fields) < 2:
            return
        now = int(fields[1]) * 1e-9
        grew = now - getattr(self._tls, "wait", 0.0)
        self._tls.wait = now
        _tm.counter(
            "host.runqueue_wait_seconds",
            "time a thread was runnable and not running, by thread",
        ).inc(max(grew, 0.0), thread=name)

    def _grow(self, counter: _tm.Counter, now: float, first: float) -> None:
        grew = now - self._last.get(counter.name, first)
        self._last[counter.name] = now
        counter.inc(max(grew, 0.0))

    def process(self) -> None:
        """The host's CPU pressure and steal and the process's involuntary
        context switches since the previous call (the first call counts
        the switches from the process's start, the host's two from now)."""
        some = self._first_line("pressure", "cpu", starts="some")
        total = [f for f in some or () if f.startswith("total=")]
        if total:
            now = int(total[0][len("total="):]) * 1e-6
            self._grow(_tm.counter(
                "host.cpu_pressure_seconds",
                "time some runnable task of the host waited for a CPU "
                "(PSI)"), now, now)
        cpu = self._first_line("stat", starts="cpu ")
        if cpu is not None and len(cpu) > 8:
            now = int(cpu[8]) / self._tick
            self._grow(_tm.counter(
                "host.steal_seconds",
                "CPU time the hypervisor gave to others, all CPUs"),
                now, now)
        self._grow(_tm.counter(
            "host.involuntary_switches",
            "times the kernel took the CPU from the process"),
            float(resource.getrusage(resource.RUSAGE_SELF).ru_nivcsw), 0.0)

    def after_drain(self, watch: "CompletionWatcher") -> None:
        """What the dispatching thread does once a pass, after ``drain``:
        it has the watcher's thread account for itself, and reads its own
        wait, the process's and the host's."""
        watch.account()
        self.thread("dispatch")
        self.process()


#: the process's one reader of the host's stall counters
HOST = HostStall()


@contextlib.contextmanager
def device_trace(logdir: Optional[str]) -> Iterator[None]:
    """jax.profiler trace capture around a pass (None -> no-op): the
    device's operations with the program's ``pbox.*`` stages on the same
    clock.  View the dump with TensorBoard's profile plugin or Perfetto."""
    if not logdir:
        yield
        return
    import jax

    jax.profiler.start_trace(logdir)
    try:
        yield
    finally:
        jax.profiler.stop_trace()
