"""Hot-path span tracing — the structural half of the metrics layer.

Reference Lighthouse instruments every crate with a ``metrics.rs``
against the global ``lighthouse_metrics`` registry scraped by
``http_metrics``; histograms alone, though, cannot say *where inside*
``JaxBlsBackend._dispatch`` a batch spent its time or died. A ``Span``
is a timed context manager: spans nest through a thread-local stack,
finished roots land in a bounded ring buffer, and every span's duration
is mirrored into registry histograms — the shared ``lhtpu_span_seconds``
family labelled by span name (unless opened with ``mirror=False``),
plus an optional caller-supplied histogram with its own labels — so
ONE instrumentation point feeds the Prometheus
scrape (``/metrics``), the Chrome-trace export (``/trace`` or
``chrome_trace()``), and the profiler: an enabled span also opens a
``jax.profiler.TraceAnnotation`` of its name, so while a JAX profiler
trace is recording, every span lands on the trace's host plane, on the
clock the device operations use.

Overhead discipline: with ``LHTPU_TRACE=0`` every ``span()`` call
returns the shared no-op span — no clock read, no allocation, no JAX
import, nothing on the measured path. Enabled is the default.

Usage::

    from lighthouse_tpu.common import tracing

    with tracing.span("bls_dispatch/pack", sets=n) as sp:
        ...
        sp.set(padded=S)

    tracing.chrome_trace()   # -> chrome://tracing / Perfetto events
"""

from __future__ import annotations

import os
import threading
import time
from collections import deque

from . import knobs
from .metrics import REGISTRY, Histogram

#: spans kept for export, children included: the oldest finished roots
#: are dropped, whole, until their spans fit (the newest root is kept)
MAX_SPANS = 16384

_enabled = bool(knobs.knob("LHTPU_TRACE"))


def enabled() -> bool:
    """Is tracing on? (LHTPU_TRACE=0 disables; read once at import,
    flip at runtime with :func:`set_enabled`)."""
    return _enabled


def set_enabled(on: bool) -> bool:
    """Enable/disable tracing at runtime; returns the previous state."""
    global _enabled
    prev = _enabled
    _enabled = bool(on)
    return prev


#: every finished span mirrors its duration here, labelled by span name
SPAN_SECONDS = REGISTRY.histogram(
    "lhtpu_span_seconds",
    "Duration of tracing spans, labelled by span name",
    ("span",),
)


class _NullSpan:
    """The shared do-nothing span handed out when tracing is off."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set(self, **attrs) -> None:
        pass


NULL_SPAN = _NullSpan()

_annotation = None  # jax.profiler.TraceAnnotation, or False without JAX


def _annotation_cls():
    """The profiler's annotation type, imported on the first enabled
    span; False where JAX cannot be imported (spans then only time)."""
    global _annotation
    if _annotation is None:
        try:
            from jax.profiler import TraceAnnotation
        except ImportError:
            TraceAnnotation = False
        _annotation = TraceAnnotation
    return _annotation


class Span:
    """One timed region. Context manager; nests via the owning tracer's
    thread-local stack. ``metric``/``labels``: an extra Histogram to
    mirror the duration into (on top of ``lhtpu_span_seconds``, unless
    ``mirror`` is false)."""

    __slots__ = (
        "name", "attrs", "start", "end", "children", "tid", "size",
        "_tracer", "_metric", "_labels", "_mirror", "_ann",
    )

    def __init__(self, tracer: "Tracer", name: str,
                 metric: Histogram | None, labels: dict | None,
                 mirror: bool, attrs: dict):
        self.name = name
        self.attrs = attrs
        self.start = 0.0
        self.end: float | None = None
        self.children: list[Span] = []
        self.tid = threading.get_ident()
        self.size = 1  # this span and every span nested in it
        self._tracer = tracer
        self._metric = metric
        self._labels = labels
        self._mirror = mirror
        self._ann = None

    @property
    def duration(self) -> float | None:
        return None if self.end is None else self.end - self.start

    def set(self, **attrs) -> None:
        self.attrs.update(attrs)

    def __enter__(self) -> "Span":
        self._tracer._stack().append(self)
        ann = _annotation_cls()
        if ann:
            self._ann = ann(self.name)
            self._ann.__enter__()
        self.start = time.perf_counter()
        return self

    def __exit__(self, exc_type, exc, tb):
        self.end = time.perf_counter()
        if self._ann is not None:
            self._ann.__exit__(exc_type, exc, tb)
            self._ann = None
        if exc_type is not None:
            # failures stay attributed even when the caller re-raises
            self.attrs.setdefault("error", exc_type.__name__)
        stack = self._tracer._stack()
        if self in stack:  # tolerate interleaved exits
            while stack and stack[-1] is not self:
                stack.pop()
            if stack:
                stack.pop()
        dur = self.end - self.start
        if self._mirror:
            SPAN_SECONDS.observe(dur, span=self.name)
        if self._metric is not None:
            self._metric.observe(dur, **(self._labels or {}))
        if stack:
            stack[-1].children.append(self)
            stack[-1].size += self.size
        else:
            self._tracer._add_root(self)
        return False


class Tracer:
    """Thread-local span stacks + a ring of finished roots bounded by
    the number of spans they hold."""

    def __init__(self, max_spans: int = MAX_SPANS):
        self._local = threading.local()
        self._lock = threading.Lock()
        self._roots: deque[Span] = deque()
        self._spans = 0  # spans held by self._roots
        self._max_spans = max_spans
        self._origin = time.perf_counter()

    def _stack(self) -> list:
        stack = getattr(self._local, "stack", None)
        if stack is None:
            stack = self._local.stack = []
        return stack

    def _add_root(self, span: Span) -> None:
        with self._lock:
            self._roots.append(span)
            self._spans += span.size
            while self._spans > self._max_spans and len(self._roots) > 1:
                self._spans -= self._roots.popleft().size

    # ---------------------------------------------------------------- API
    def span(self, name: str, metric: Histogram | None = None,
             labels: dict | None = None, mirror: bool = True, **attrs):
        """A new active span, or the shared no-op when tracing is off.
        ``mirror=False`` keeps the span out of ``lhtpu_span_seconds``:
        for spans opened once per item of a hot loop, whose observe
        would cost more than the rest of the span."""
        if not _enabled:
            return NULL_SPAN
        return Span(self, name, metric, labels, mirror, attrs)

    def current(self) -> Span | None:
        """The innermost open span on THIS thread (None outside spans)."""
        stack = self._stack()
        return stack[-1] if stack else None

    def roots(self) -> list[Span]:
        with self._lock:
            return list(self._roots)

    def clear(self) -> None:
        with self._lock:
            self._roots.clear()
            self._spans = 0

    def chrome_trace(self) -> list[dict]:
        """Finished spans as Chrome trace-event 'X' (complete) events —
        load via chrome://tracing or https://ui.perfetto.dev."""
        pid = os.getpid()
        events: list[dict] = []

        def emit(span: Span) -> None:
            events.append({
                "name": span.name,
                "ph": "X",
                "ts": (span.start - self._origin) * 1e6,
                "dur": (span.duration or 0.0) * 1e6,
                "pid": pid,
                "tid": span.tid,
                "args": dict(span.attrs),
            })
            for c in span.children:
                emit(c)

        for root in self.roots():
            emit(root)
        return events


#: the process-global tracer (pairs with metrics.REGISTRY)
TRACER = Tracer()


def span(name: str, metric: Histogram | None = None,
         labels: dict | None = None, mirror: bool = True, **attrs):
    """Module-level convenience for ``TRACER.span`` (the common call)."""
    return TRACER.span(name, metric=metric, labels=labels, mirror=mirror,
                       **attrs)


def current_span() -> Span | None:
    return TRACER.current()


def roots() -> list[Span]:
    return TRACER.roots()


def clear() -> None:
    TRACER.clear()


def chrome_trace() -> list[dict]:
    return TRACER.chrome_trace()
