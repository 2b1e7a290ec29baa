"""Process events the tracer cannot time from a span round our own code:
XLA compiles (JAX's own monitoring events) and CPython garbage
collections (gc.callbacks).  Both feed TRACER counters; nothing here
costs anything while nothing compiles and nothing is collected.

    jax_compile_seconds_total{stage}      stage = trace | lower |
    jax_compile_events_total{stage}               backend_compile
    jax_compiles_by_function_total{fun,span}
    jax_persistent_cache_total{result}    request | hit | miss
    gc_pause_seconds_total{generation}
    gc_collections_total{generation}
    span gc_gen2                          one per full collection

A JAX compile is synchronous on the thread that called the jitted
function, so the listener runs on that thread: the tracer's span stack
names WHERE the compile happened (the `span` label), and a per-thread
running total lets a caller bracket one call (`thread_compile_seconds`:
framework/replay.py times a cached scan's first call with it).

Both also keep their last few thousand events as (end, seconds) marks
on time.perf_counter's clock, so that a stall record can ask AFTER the
fact what fell inside a span (`compile_seconds_since`,
`gc_seconds_since`; utils/blackbox.py).
"""

from __future__ import annotations

import gc
import threading
import time
from collections import deque

from .tracing import TRACER

_STAGES = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "backend_compile",
}
_CACHE_EVENTS = {
    "/jax/compilation_cache/compile_requests_use_cache": "request",
    "/jax/compilation_cache/cache_hits": "hit",
    "/jax/compilation_cache/cache_misses": "miss",
}
MAX_FUN_LABELS = 64

_lock = threading.Lock()
_installed = False
_fun_labels: set[str] = set()
_tls = threading.local()


def thread_compile_seconds() -> float:
    """Seconds of JAX compile stages (trace + lower + backend compile)
    seen on the calling thread so far; the difference round one call is
    what that call spent compiling."""
    return getattr(_tls, "seconds", 0.0)


def _seconds_since(marks, t0: float) -> float:
    """The seconds of the marks that ended after t0 (an event that
    straddles t0 counts whole)."""
    while True:
        try:
            held = tuple(marks)
            break
        except RuntimeError:
            # mutated during iteration: the GC callback appends inside
            # any allocation, this tuple's own included
            continue
    total = 0.0
    for t_end, seconds in reversed(held):
        if t_end <= t0:
            break
        total += seconds
    return total


def compile_seconds_since(t0: float) -> float:
    """thread_compile_seconds()'s growth since perf_counter time t0."""
    return _seconds_since(getattr(_tls, "marks", ()), t0)


def gc_seconds_since(t0: float) -> float:
    """Seconds inside garbage collections, on any thread, since t0."""
    return _seconds_since(_gc_marks, t0)


def _fun_label(fun_name) -> str:
    name = str(fun_name or "unknown")[:80]
    with _lock:
        if name in _fun_labels:
            return name
        if len(_fun_labels) >= MAX_FUN_LABELS:
            return "other"
        _fun_labels.add(name)
        return name


def _on_duration(event: str, duration_secs: float, **kw) -> None:
    stage = _STAGES.get(event)
    if stage is None:
        return
    _tls.seconds = getattr(_tls, "seconds", 0.0) + duration_secs
    marks = getattr(_tls, "marks", None)
    if marks is None:
        marks = _tls.marks = deque(maxlen=4096)
    marks.append((time.perf_counter(), duration_secs))
    TRACER.inc("jax_compile_seconds_total", duration_secs, stage=stage)
    TRACER.inc("jax_compile_events_total", stage=stage)
    if stage == "backend_compile":
        TRACER.inc("jax_compiles_by_function_total",
                   fun=_fun_label(kw.get("fun_name")),
                   span=TRACER.current_span_name() or "none")


def _on_event(event: str, **kw) -> None:
    result = _CACHE_EVENTS.get(event)
    if result is not None:
        TRACER.inc("jax_persistent_cache_total", result=result)


# per generation: [pause seconds, collections] seen, and handed over.
# A collection can start inside ANY allocation — one made with the
# tracer's lock held too — so the callback takes no lock: it adds to
# these floats, and the tracer collects the difference before each
# export (Tracer.add_collector).  Full (generation 2) collections are
# also kept one by one, (start, seconds), and handed over as `gc_gen2`
# spans: on /api/v1/trace a slow cycle can be laid beside them.
_gc_seen = [[0.0, 0] for _ in range(3)]
_gc_given = [[0.0, 0] for _ in range(3)]
_gc_full: deque = deque(maxlen=1024)
_gc_marks: deque = deque(maxlen=4096)
_gc_t0 = 0.0
_gc_annotation = None


def _on_gc(phase: str, info: dict) -> None:
    # start -> stop runs on one thread under the GIL: globals carry t0
    # and, under a profile, the kss:gc TraceMe of this collection
    global _gc_t0, _gc_annotation
    if phase == "start":
        _gc_annotation = TRACER.annotate("gc")
        _gc_t0 = time.perf_counter()
        return
    seconds = time.perf_counter() - _gc_t0
    if _gc_annotation is not None:
        _gc_annotation.__exit__(None, None, None)
        _gc_annotation = None
    generation = min(info.get("generation", 0), 2)
    seen = _gc_seen[generation]
    seen[0] += seconds
    seen[1] += 1
    _gc_marks.append((_gc_t0 + seconds, seconds))
    if generation == 2:
        _gc_full.append((_gc_t0, seconds))


def _give_gc() -> None:
    for generation, (seen, given) in enumerate(zip(_gc_seen, _gc_given)):
        seconds, n = seen[0], seen[1]
        if n > given[1]:
            TRACER.inc_process("gc_pause_seconds_total", seconds - given[0],
                               generation=generation)
            TRACER.inc_process("gc_collections_total", n - given[1],
                               generation=generation)
            given[0], given[1] = seconds, n
    while _gc_full:
        t0, seconds = _gc_full.popleft()
        TRACER.record_span("gc_gen2", t0, seconds)


def install() -> None:
    """Register the JAX monitoring listeners and the GC hook, once per
    process (the server calls this at start; the scan cache on its
    first miss, so direct engine use gets real compile seconds too)."""
    global _installed
    with _lock:
        if _installed:
            return
        _installed = True
    import jax.monitoring

    jax.monitoring.register_event_duration_secs_listener(_on_duration)
    jax.monitoring.register_event_listener(_on_event)
    gc.callbacks.append(_on_gc)
    TRACER.add_collector(_give_gc)
