"""Span tracer: the one timing mechanism for sweeps and benchmarks.

Replaces the ad-hoc ``time.perf_counter`` arithmetic that used to be
copy-pasted across ``sweep()`` and ``benchmarks/*.py`` with a span-tree
API::

    from repro.telemetry import trace

    with trace.span("compile", partition=3) as sp:
        compiled = jitted.lower(args).compile()
    print(sp.duration_us)

Spans nest (a ``with`` inside a ``with`` becomes a child span) and the
whole tree exports as Chrome trace-event JSON — ``trace.export(path)``
writes a ``{"traceEvents": [...]}`` document loadable in Perfetto or
``chrome://tracing``.  The process-global tracer is what the module-level
helpers operate on; ``Tracer`` instances can be used standalone (tests).

This module is the *owner* of raw-clock access: the ``raw-timing`` analyze
rule flags ``time.perf_counter()`` call sites anywhere outside
``src/repro/telemetry/``, so new timing code must come through here.

Spans are on the profiler's clock: start times are wall-clock (epoch)
microseconds, the clock the profiler's host events use, and every span
also enters a ``jax.profiler.TraceAnnotation`` of the same name with its
attributes as stats.  Under ``jax.profiler.trace`` (or
``start_trace``/``stop_trace``) a span therefore lands in the profile's
host plane beside the device ops it dispatched; with the profiler off the
annotation is a no-op.  An xplane's event times count from its
``profile_start_time`` stat (wall-clock ns), so ``start_us * 1e3`` minus
that stat is the span's place in the profile.  A profiler records only
the annotations entered while it runs: a span that waits on the device
calls ``trace.wait(arrays)`` inside it, which re-enters the span's
annotation when a profiler starts mid-wait.  Without jax installed the
spans stay host-only.
"""
from __future__ import annotations

import functools
import json
import os
import threading
import time
from contextlib import contextmanager, nullcontext
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, Iterator, List, Optional

__all__ = [
    "Span", "Timing", "Tracer", "export", "get_tracer", "reset", "span",
    "spans", "timed_call", "to_chrome_trace", "wait",
]


@dataclass
class Span:
    """One timed interval.  ``start_us`` is wall-clock (epoch) µs;
    ``duration_us`` is valid after the ``with`` block exits; ``attrs`` may
    be extended inside the block (they export as the Chrome event's
    ``args``; the profiler sees those given at entry)."""

    name: str
    start_us: float
    duration_us: float = 0.0
    attrs: Dict[str, Any] = field(default_factory=dict)
    children: List["Span"] = field(default_factory=list)
    tid: int = 0
    profiled: bool = False  # a running profiler recorded its entry


class Timing(float):
    """A median-microseconds float that also carries the compile/run split.

    ``float(t)`` (and all arithmetic) is the median run time per call in
    microseconds, so existing ``emit(name, time_call(...), ...)`` callers
    keep working; ``t.compile_us`` is the first-call (compile-inclusive)
    wall time and ``t.run_us`` the steady-state median.
    """

    compile_us: Optional[float]
    run_us: float

    def __new__(cls, run_us: float, compile_us: Optional[float] = None):
        self = float.__new__(cls, run_us)
        self.run_us = float(run_us)
        self.compile_us = None if compile_us is None else float(compile_us)
        return self


# how often a device wait checks whether a profiler has started
_POLL_S = 0.01


class Tracer:
    """A span tree with a per-thread open-span stack."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._stacks: Dict[int, List[Span]] = {}
        self.roots: List[Span] = []

    @staticmethod
    def _now_us() -> float:
        return time.time_ns() / 1e3

    @contextmanager
    def span(self, name: str, **attrs: Any) -> Iterator[Span]:
        tid = threading.get_ident()
        with _annotation(name, attrs):
            sp = Span(name=name, start_us=self._now_us(), attrs=dict(attrs),
                      tid=tid & 0xFFFF, profiled=_profiling())
            with self._lock:
                stack = self._stacks.setdefault(tid, [])
                (stack[-1].children if stack else self.roots).append(sp)
                stack.append(sp)
            try:
                yield sp
            finally:
                sp.duration_us = self._now_us() - sp.start_us
                with self._lock:
                    self._stacks[tid].pop()

    def wait(self, tree: Any) -> None:
        """Block until every device array in ``tree`` is ready, inside this
        thread's innermost open span.

        A profiler records only the annotations entered while it runs, so
        one started during a long wait would miss the span.  Where the
        span began with no profiler running, a helper thread blocks on the
        arrays while this one checks every ``_POLL_S`` whether a profiler
        has started, and then re-enters the span's annotation for the rest
        of the wait: a profile of the wait's tail still names it.  The wait
        ends as soon as the arrays are ready."""
        import jax

        with self._lock:
            stack = self._stacks.get(threading.get_ident())
            open_span = stack[-1] if stack else None
        if open_span is None or open_span.profiled:
            jax.block_until_ready(tree)
            return
        ready, failed = threading.Event(), []

        def block():
            try:
                jax.block_until_ready(tree)
            except BaseException as e:  # re-raised by the caller below
                failed.append(e)
            finally:
                ready.set()

        waiter = threading.Thread(target=block, daemon=True)
        waiter.start()
        while not ready.wait(_POLL_S):
            if _profiling():
                with _annotation(open_span.name, open_span.attrs):
                    ready.wait()
        waiter.join()
        if failed:
            raise failed[0]

    def reset(self) -> None:
        self.__init__()

    def spans(self) -> List[Span]:
        return list(self.roots)

    def to_chrome_trace(self) -> Dict[str, Any]:
        """The span tree as a Chrome trace-event document (Perfetto-loadable):
        one ``ph="X"`` complete event per span, µs timestamps."""
        pid = os.getpid()
        events: List[Dict[str, Any]] = [{
            "name": "process_name", "ph": "M", "pid": pid, "tid": 0,
            "args": {"name": "repro"},
        }]

        def visit(sp: Span) -> None:
            events.append({
                "name": sp.name, "cat": "repro", "ph": "X",
                "ts": sp.start_us, "dur": sp.duration_us,
                "pid": pid, "tid": sp.tid,
                "args": {k: _json_safe(v) for k, v in sp.attrs.items()},
            })
            for child in sp.children:
                visit(child)

        for root in self.roots:
            visit(root)
        return {"traceEvents": events, "displayTimeUnit": "ms"}

    def export(self, path: str) -> Dict[str, Any]:
        doc = self.to_chrome_trace()
        with open(path, "w", encoding="utf-8") as f:
            json.dump(doc, f, indent=1)
        return doc


@functools.cache
def _annotation_cls():
    """``jax.profiler.TraceAnnotation``, imported on first use; None where
    jax is not installed."""
    try:
        from jax.profiler import TraceAnnotation
    except ImportError:
        return None
    return TraceAnnotation


def _annotation(name: str, attrs: Dict[str, Any]):
    """``TraceAnnotation(name, **attrs)``: recorded when a profiler runs."""
    cls = _annotation_cls()
    return nullcontext() if cls is None else cls(name, **attrs)


def _profiling() -> bool:
    cls = _annotation_cls()
    return cls is not None and cls.is_enabled()


def _json_safe(v: Any) -> Any:
    """Chrome's ``args`` values must be JSON: numbers/strings/bools pass
    through (non-finite floats stringify), everything else reprs."""
    if isinstance(v, bool) or v is None or isinstance(v, (int, str)):
        return v
    if isinstance(v, float):
        return v if v == v and abs(v) != float("inf") else repr(v)
    return repr(v)


# ---------------------------------------------------------------------------
# The process-global tracer (what sweep/benchmarks record into).
# ---------------------------------------------------------------------------

_TRACER = Tracer()


def get_tracer() -> Tracer:
    return _TRACER


def span(name: str, **attrs: Any):
    """``with trace.span("dispatch", partition=i) as sp: ...``"""
    return _TRACER.span(name, **attrs)


def wait(tree: Any) -> None:
    """Block until ``tree``'s device arrays are ready inside the open span,
    keeping the span visible to a profiler started mid-wait
    (:meth:`Tracer.wait`)."""
    _TRACER.wait(tree)


def reset() -> None:
    _TRACER.reset()


def spans() -> List[Span]:
    return _TRACER.spans()


def to_chrome_trace() -> Dict[str, Any]:
    return _TRACER.to_chrome_trace()


def export(path: str) -> Dict[str, Any]:
    """Write the global span tree as Chrome trace JSON; returns the doc."""
    return _TRACER.export(path)


def timed_call(
    fn: Callable,
    *args: Any,
    warmup: int = 1,
    iters: int = 5,
    block: Optional[Callable[[Any], Any]] = None,
    name: Optional[str] = None,
) -> Timing:
    """Median wall time per call, with the compile/run split as spans.

    The first warmup call runs inside a ``compile:<name>`` span (for jitted
    callables that is where compilation lands); the timed iterations run
    inside one ``run:<name>`` span.  ``block`` is applied to each result
    before the clock stops (pass ``jax.block_until_ready`` for jax work —
    this module deliberately does not import jax).
    """
    label = name or getattr(fn, "__name__", None) or "call"
    sink = block if block is not None else (lambda x: x)
    compile_us: Optional[float] = None
    if warmup > 0:
        with span(f"compile:{label}") as sp:
            sink(fn(*args))
        compile_us = sp.duration_us
        for _ in range(warmup - 1):
            sink(fn(*args))
    times = []
    with span(f"run:{label}", iters=iters) as sp:
        for _ in range(iters):
            t0 = time.perf_counter()
            sink(fn(*args))
            times.append(time.perf_counter() - t0)
    times.sort()
    run_us = times[len(times) // 2] * 1e6
    sp.attrs["median_us"] = run_us
    return Timing(run_us, compile_us=compile_us)
