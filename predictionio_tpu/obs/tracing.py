"""Request-scoped tracing: trace ids, spans, a ring buffer, JSON logs.

A trace id is minted (or accepted from the ``X-Pio-Trace-Id`` header) at
ingress and rides a :mod:`contextvars` variable through the asyncio
handlers; thread hops (the micro-batcher's dispatch/fetch workers, the
event server's storage executor) re-install it explicitly because
``run_in_executor`` does not copy the caller's context.

Every finished span is (a) appended to a bounded ring buffer served at
``/traces/recent`` and (b) emitted as one JSON line on the ``pio.trace``
logger — the structured log the acceptance trail greps for a single trace
id across ingress, batch, and storage spans. Span kinds used by the
framework: ``ingress`` (HTTP arrival), ``batch`` (micro-batch queue +
device dispatch/fetch, with wall/queue/device timings in tags),
``storage`` (DAO method via :mod:`predictionio_tpu.data.storage.traced`),
``serving`` (per-query decode/serve work).

Import-light by design (stdlib only): `pio top`, the lint CLI, and the
event server all reach this module without dragging in jax/numpy.
"""

from __future__ import annotations

import contextlib
import contextvars
import dataclasses
import json
import logging
import threading
import time
import uuid
from collections import deque
from typing import Any, Iterator

TRACE_HEADER = "X-Pio-Trace-Id"

# one trace id per logical request, carried across awaits by contextvars
_current_trace: contextvars.ContextVar[str | None] = contextvars.ContextVar(
    "pio_trace_id", default=None
)

# the span open in this context: the parent of any span started under it
_current_span: contextvars.ContextVar[str | None] = contextvars.ContextVar(
    "pio_span_id", default=None
)

_trace_logger = logging.getLogger("pio.trace")


def mint_trace_id() -> str:
    return uuid.uuid4().hex[:16]


def current_trace_id() -> str | None:
    return _current_trace.get()


def current_span_id() -> str | None:
    """The id of the span open in this context (``Tracer.span``), for work
    that leaves the context (the micro-batcher's queue) to carry along as
    its spans' parent."""
    return _current_span.get()


def set_trace_id(trace_id: str | None) -> contextvars.Token:
    """Install ``trace_id`` for the current context; pair with
    :func:`reset_trace_id` (thread hops install/reset around each unit of
    work for one request)."""
    return _current_trace.set(trace_id)


def reset_trace_id(token: contextvars.Token) -> None:
    _current_trace.reset(token)


def get_trace_logger() -> logging.Logger:
    """The structured span logger (one JSON object per line). Serving-path
    code should log through spans/this logger, not ``print`` or the root
    logger — the ``obs-unstructured-log`` lint rule enforces it."""
    return _trace_logger


@dataclasses.dataclass
class Span:
    trace_id: str
    name: str
    kind: str = "internal"
    span_id: str = dataclasses.field(default_factory=lambda: uuid.uuid4().hex[:8])
    # timing hygiene contract: start_time is a WALL-CLOCK display anchor
    # only (correlating spans with external logs); every duration in this
    # module — span durations, phase timings, queue waits — is measured
    # from time.perf_counter()/time.monotonic(), never as a wall-clock
    # delta, so an NTP step can shift where a span *appears* on a timeline
    # but can never corrupt how long anything *took*
    start_time: float = dataclasses.field(default_factory=time.time)
    duration_s: float = 0.0
    status: str = "ok"
    tags: dict[str, Any] = dataclasses.field(default_factory=dict)
    # the span that caused this one (None at a request's root), and the
    # start on the monotonic clock: what orders a request's spans
    parent_id: str | None = None
    start_mono_ns: int = dataclasses.field(default_factory=time.monotonic_ns)

    def to_json_dict(self) -> dict[str, Any]:
        return {
            "traceId": self.trace_id,
            "spanId": self.span_id,
            "parentId": self.parent_id,
            "name": self.name,
            "kind": self.kind,
            "startTime": round(self.start_time, 6),
            "startMonoNs": self.start_mono_ns,
            "durationMs": round(self.duration_s * 1000.0, 3),
            "status": self.status,
            "tags": self.tags,
        }


class Tracer:
    """Span sink: bounded ring buffer + JSON log emission.

    One process-wide default instance (:func:`get_tracer`) is shared by
    the servers and the storage wrappers, mirroring how all structured
    logs converge on one logging tree; tests may construct private
    tracers for isolation.
    """

    def __init__(self, ring_size: int = 512):
        self._ring: deque[Span] = deque(maxlen=max(1, ring_size))
        self._lock = threading.Lock()
        self.spans_recorded = 0

    @contextlib.contextmanager
    def span(
        self,
        name: str,
        kind: str = "internal",
        trace_id: str | None = None,
        **tags: Any,
    ) -> Iterator[Span]:
        """Time a block as one span. The span is yielded so callers can
        attach tags mid-flight; an escaping exception marks the status
        with the exception type and re-raises. The span open in the
        calling context is its parent, and it is the parent of spans
        started inside the block."""
        sp = Span(
            trace_id=trace_id or current_trace_id() or mint_trace_id(),
            name=name,
            kind=kind,
            tags=dict(tags),
            parent_id=_current_span.get(),
        )
        token = _current_span.set(sp.span_id)
        t0 = time.perf_counter()
        try:
            yield sp
        except BaseException as exc:
            sp.status = type(exc).__name__
            raise
        finally:
            sp.duration_s = time.perf_counter() - t0
            _current_span.reset(token)
            self.record(sp)

    def record_span(
        self,
        name: str,
        kind: str,
        duration_s: float,
        trace_id: str | None = None,
        status: str = "ok",
        parent_id: str | None = None,
        **tags: Any,
    ) -> Span:
        """Record an already-timed span that ends now (the micro-batcher
        measures queue/dispatch/fetch itself and reports per-query
        afterwards, under the ingress span it was handed as parent)."""
        sp = Span(
            trace_id=trace_id or current_trace_id() or mint_trace_id(),
            name=name,
            kind=kind,
            start_time=time.time() - duration_s,
            duration_s=duration_s,
            status=status,
            tags=dict(tags),
            parent_id=parent_id,
            start_mono_ns=time.monotonic_ns() - int(duration_s * 1e9),
        )
        self.record(sp)
        return sp

    def record(self, span: Span) -> None:
        with self._lock:
            self._ring.append(span)
            self.spans_recorded += 1
        if _trace_logger.isEnabledFor(logging.INFO):
            _trace_logger.info("%s", json.dumps(span.to_json_dict()))

    def recent(self, limit: int | None = None) -> list[dict[str, Any]]:
        """Newest-first JSON dicts for ``/traces/recent``."""
        with self._lock:
            spans = list(self._ring)
        spans.reverse()
        if limit is not None:
            spans = spans[: max(0, limit)]
        return [s.to_json_dict() for s in spans]

    def find(self, trace_id: str) -> list[dict[str, Any]]:
        """All ring-resident spans of one trace, oldest first by their
        monotonic start (the ring holds them in the order they ENDED: an
        ingress span after the batch span it caused)."""
        with self._lock:
            spans = [s for s in self._ring if s.trace_id == trace_id]
        spans.sort(key=lambda s: s.start_mono_ns)
        return [s.to_json_dict() for s in spans]

    def clear(self) -> None:
        with self._lock:
            self._ring.clear()


_default_tracer = Tracer()


def get_tracer() -> Tracer:
    """The process-wide tracer shared by servers and storage wrappers."""
    return _default_tracer
