"""Compile-event witness: per-stage ``jit.compiles`` telemetry.

The serving fast path (PR 13) and both trainer paths are built on one
promise: after warmup, a steady-state step is a CACHED dispatch — no
trace, no XLA compile, no host sync hidden inside the call.  A silent
recompile per step (a shape-varying argument, a python scalar flipping
weak types, a fresh ``jax.jit`` wrapper built inside the loop) costs
tens of milliseconds on CPU and minutes at pod scale, and nothing in the
metrics surface showed it.  This module is the runtime half of the
``jit-retrace-hazard`` static pass (tools/pbox_analyze): the static rule
catches the shapes that retrace, and this witness proves at runtime —
and pins in tier-1 — that steady-state passes and steady-state serving
trigger ZERO retraces after warmup.

Mechanism: ``jax.monitoring`` emits one
``/jax/core/compile/backend_compile_duration`` event per XLA backend
compile, synchronously on the thread that triggered it.  The installed
listener attributes each event to the innermost active *stage* (a
thread-local scope string: ``train.step``, ``spmd.step``,
``serve.predict`` ...) and feeds two metrics:

  * ``jit.compiles`` (counter, label ``stage``) — backend compiles per
    stage; steady state means the per-stage count stops moving;
  * ``jit.compile_seconds`` (histogram, label ``stage``) — where the
    compile wall time goes (warmup cost is real and worth seeing).  Where
    the persistent compile cache served the executable (``jit.cache_hits``
    counts those), the event's duration is the retrieval: reading and
    deserializing the cached program, not a compile.

The two phases before the backend are heard the same way, with the same
attribution: ``/jax/core/compile/jaxpr_trace_duration`` into
``jit.trace_seconds`` (Python tracing a function to a jaxpr; a function
traced inside another's trace counts once, in its own observation) and
``/jax/core/compile/jaxpr_to_mlir_module_duration`` into
``jit.lower_seconds`` (the jaxpr to StableHLO).  Neither is skipped by the
persistent cache, whose key is made from the lowered module; a decoder's
``train.step`` is half a million characters of it.

``counted_jit(fn, stage=..., **jit_kwargs)`` is the adoption surface:
a drop-in ``jax.jit`` replacement whose calls run inside the stage
scope, so every compile its dispatch triggers lands on the right label.
It also tracks the wrapper's own trace-cache size, so ``retraces()``
answers "how many distinct signatures has this step seen" without
scraping counters.  Code that calls pre-compiled artifacts directly
(the predictor's ``exported.call``) uses ``stage_scope`` alone.

jax is imported lazily — this module must stay importable (and the
metric names registerable) on jax-free hosts like the analyzer's bare
checkout and the serving-side quant tooling.
"""

from __future__ import annotations

import contextlib
import threading
import time

from paddlebox_tpu.telemetry import metrics

#: the one event that fires exactly when XLA compiles something new and
#: never on a cache hit — the whole witness keys on it.
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"

#: fired (inside the compile event's window) when the persistent compile
#: cache supplied the executable, so XLA compiled nothing after all.
_CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"

#: stage attributed to compiles outside any scope (import-time warmup,
#: library internals) — visible, not silently dropped.
UNTAGGED = "untagged"

#: the phases before it: Python -> jaxpr, jaxpr -> StableHLO
_TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
_LOWER_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"

_COMPILES = metrics.counter(
    "jit.compiles",
    "XLA backend compiles by stage (zero per stage in steady state)",
)
_COMPILE_SECONDS = metrics.histogram(
    "jit.compile_seconds",
    "XLA backend compile wall time by stage (a cache hit's: the retrieval)",
)
_TRACE_SECONDS = metrics.histogram(
    "jit.trace_seconds", "time tracing functions to jaxprs, by stage",
)
_LOWER_SECONDS = metrics.histogram(
    "jit.lower_seconds", "time lowering jaxprs to StableHLO, by stage",
)
_CACHE_HITS = metrics.counter(
    "jit.cache_hits",
    "jit.compiles events served by the persistent compile cache, by stage",
)

_tls = threading.local()
_install_lock = threading.Lock()
_installed = False


def _stack() -> list:
    st = getattr(_tls, "stack", None)
    if st is None:
        st = _tls.stack = []
    return st


def current_stage() -> str:
    st = _stack()
    return st[-1] if st else UNTAGGED


class stage_scope(contextlib.ContextDecorator):
    """Attribute backend compiles on this thread to ``stage`` while the
    scope is active (a ``with`` block, or a decorated function's calls).
    Reentrant; innermost scope wins."""

    def __init__(self, stage: str):
        self.stage = stage

    def __enter__(self):
        _stack().append(self.stage)
        return self

    def __exit__(self, *exc):
        st = _stack()
        if st:
            st.pop()
        return False


def _on_event(event: str, duration_secs: float, **kwargs) -> None:
    if event == _COMPILE_EVENT:
        stage = current_stage()
        _COMPILES.inc(stage=stage)
        _COMPILE_SECONDS.observe(duration_secs, stage=stage)
        return
    if event == _TRACE_EVENT:
        _TRACE_SECONDS.observe(_outside_inner_traces(duration_secs),
                               stage=current_stage())
    elif event == _LOWER_EVENT:
        _LOWER_SECONDS.observe(duration_secs, stage=current_stage())


def _outside_inner_traces(duration_secs: float) -> float:
    """A trace's seconds without the traces that ran inside it.  The trace
    event also fires for every function traced on the way (each ``jnp``
    call of a model is a jitted function of its own), inside the outer
    one's duration and before it: the events of a thread come in
    post-order, so the inner ones are the latest that ended after this one
    began.  What is observed then adds up to the outermost traces' wall.
    The list holds the traces no later one enclosed: one entry a trace-
    cache miss at the top level."""
    done = getattr(_tls, "traced", None)
    if done is None:
        done = _tls.traced = []
    now = time.perf_counter()
    began = now - duration_secs
    inner = 0.0
    while done and done[-1][0] >= began:
        inner += done.pop()[1]
    done.append((now, duration_secs))
    return max(duration_secs - inner, 0.0)


def _on_plain_event(event: str, **kwargs) -> None:
    if event == _CACHE_HIT_EVENT:
        _CACHE_HITS.inc(stage=current_stage())


def install_compile_listener() -> bool:
    """Register the jax.monitoring listener (idempotent, thread-safe).
    Returns False when jax or the monitoring API is unavailable — the
    witness degrades to no-op counters, never an import error."""
    global _installed
    with _install_lock:
        if _installed:
            return True
        try:
            from jax import monitoring
        # pbox-lint: ignore[swallowed-exception] capability probe: a
        # jax-free or pre-monitoring build runs without the witness
        except Exception:
            return False
        register = getattr(
            monitoring, "register_event_duration_secs_listener", None)
        if register is None:
            return False
        register(_on_event)
        monitoring.register_event_listener(_on_plain_event)
        _installed = True
        return True


def compiles_by_stage() -> dict:
    """{stage: backend-compile count} — the read surface of the tier-1 pins."""
    out: dict = {}
    for key, cell in _COMPILES.series().items():
        stage = dict(key).get("stage", UNTAGGED)
        out[stage] = out.get(stage, 0) + int(cell[0])
    return out


def total_compiles() -> int:
    return sum(compiles_by_stage().values())


def compile_summary() -> dict:
    """{stage: {"compiles", "cache_hits", "trace_seconds", "lower_seconds",
    "seconds"}}: XLA backend compiles that really ran (compile events
    minus the ones the persistent cache served), those cache hits, and the
    wall time of the three phases: tracing, lowering, and the backend's
    (compiles and retrievals together).  A stage that only traced or
    lowered (a ``.lower()`` never compiled) is listed too."""
    events = compiles_by_stage()
    phases = {"trace_seconds": _TRACE_SECONDS, "lower_seconds": _LOWER_SECONDS,
              "seconds": _COMPILE_SECONDS}
    stages = {dict(key).get("stage", UNTAGGED)
              for hist in phases.values() for key in hist.series()}
    out: dict = {}
    for stage in sorted(stages):
        hits = int(_CACHE_HITS.value(stage=stage))
        out[stage] = {"compiles": events.get(stage, 0) - hits,
                      "cache_hits": hits}
        for name, hist in phases.items():
            out[stage][name] = round(
                hist.summary(stage=stage)["sum"] or 0.0, 3)
    return out


class CountedJit:
    """``jax.jit`` with a stage label: every dispatch runs inside
    ``stage_scope(stage)`` so the listener attributes its compiles, and
    the wrapper tracks its own trace-cache growth (``retraces()``).

    Forwards everything else (``lower``, ``clear_cache``, ``__name__``,
    ...) to the underlying jitted callable, so existing call sites and
    the static analyzer's jit-binding detection keep working unchanged.
    """

    def __init__(self, fn, stage: str, **jit_kwargs):
        import jax

        install_compile_listener()
        self._jitted = jax.jit(fn, **jit_kwargs)
        self.stage = stage
        self._seen_cache = 0

    def __call__(self, *args, **kwargs):
        with stage_scope(self.stage):
            out = self._jitted(*args, **kwargs)
        self._bump_cache()
        return out

    def _bump_cache(self) -> None:
        size_fn = getattr(self._jitted, "_cache_size", None)
        if size_fn is None:
            return
        try:
            n = int(size_fn())
        # pbox-lint: ignore[swallowed-exception] capability probe: the
        # private cache-size API may vanish; the listener still counts
        except Exception:
            return
        if n > self._seen_cache:
            self._seen_cache = n

    def retraces(self) -> int:
        """Distinct signatures this wrapper has traced (0 before first
        call; steady state means this stops growing)."""
        self._bump_cache()
        return self._seen_cache

    def __getattr__(self, name):
        return getattr(self._jitted, name)


def counted_jit(fn=None, *, stage: str, **jit_kwargs):
    """Drop-in ``jax.jit`` replacement with per-stage compile telemetry.

    Usable directly (``counted_jit(f, stage="train.step",
    donate_argnums=(0,))``) or as a decorator factory
    (``@counted_jit(stage="cache.gather", static_argnames=("n",))``).
    """
    if fn is None:
        return lambda f: CountedJit(f, stage=stage, **jit_kwargs)
    return CountedJit(fn, stage=stage, **jit_kwargs)
