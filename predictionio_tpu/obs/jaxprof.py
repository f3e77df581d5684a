"""JAX compile/dispatch profiling hooks.

TPU serving systems die of invisible compiles: a ragged request shape
slips past the pow2 buckets, every arrival compiles a fresh XLA program,
and the operator sees only a p99 cliff. This module makes that failure
mode a first-class signal:

- :class:`CompileWatcher` tracks the jit cache size of every compiled
  function in the package (``PjitFunction._cache_size``); growth between
  samples becomes ``pio_jit_cache_misses_total{fn=...}`` and a burst
  above ``storm_threshold`` in one sampling interval raises the
  ``pio_jit_recompile_storm`` gauge and logs a warning naming the
  functions that recompiled.
- :func:`install_jax_monitoring` taps ``jax.monitoring`` for backend
  compiles and the seconds spent tracing, lowering and compiling —
  ``pio_xla_compile_events_total`` / ``pio_xla_compile_seconds_total``.
- :func:`timed_block_until_ready` is the sanctioned way for algorithm
  code to host-sync: it accounts the stall into
  ``pio_device_stall_seconds_total`` instead of losing it.
- :func:`annotate` is the ONE way the program writes a host span onto the
  profiler's clock (``pio:<layer>.<what>``, docs/observability.md "Spans
  on the profiler's clock"); :class:`GcWatcher` counts the interpreter's
  collection pauses and puts the full ones on that clock too.

jax itself is imported lazily — constructing a watcher costs nothing on
processes (event server, ``pio top``) that never touch a device.
"""

from __future__ import annotations

import functools
import gc
import logging
import sys
import threading
import time
from typing import Any, Callable

from predictionio_tpu.obs.metrics import MetricsRegistry

logger = logging.getLogger(__name__)

# ---------------------------------------------------------------------------
# jax.monitoring tap (process-global; registered once, read by any watcher)
# ---------------------------------------------------------------------------

_mon_lock = threading.Lock()
_mon_installed = False
_mon_compile_events = 0
_mon_compile_seconds = 0.0
_mon_cache_hits = 0
_mon_cache_misses = 0


def _looks_like_compile(event: str) -> bool:
    e = event.lower()
    # the persistent cache's own bookkeeping (hits, retrieval time, the
    # compile time a hit SAVED) is not compile work; a cache hit still
    # shows as a (short) backend_compile_duration
    return "compil" in e and "/compilation_cache/" not in e


def _on_duration(event: str, duration_secs: float, *a: Any, **kw: Any) -> None:
    """jax 0.9.0 reports a compile as three durations (jaxpr trace, MLIR
    lowering, backend compile) and as no plain event: the seconds are
    the sum of all three, the count is one per backend compile (or its
    load from the persistent cache)."""
    global _mon_compile_events, _mon_compile_seconds
    event = str(event)
    if _looks_like_compile(event):
        with _mon_lock:
            _mon_compile_seconds += float(duration_secs)
            if event.endswith("/backend_compile_duration"):
                _mon_compile_events += 1


def _on_event(event: str, *a: Any, **kw: Any) -> None:
    """The persistent compile cache's own verdicts (jax 0.9.0
    ``_src/compiler.py``, ``_src/compilation_cache.py``): a hit loaded an
    executable, a miss went on to compile one. Neither fires when the
    cache is off or a program is refused admission to it."""
    global _mon_cache_hits, _mon_cache_misses
    if event == "/jax/compilation_cache/cache_hits":
        with _mon_lock:
            _mon_cache_hits += 1
    elif event == "/jax/compilation_cache/cache_misses":
        with _mon_lock:
            _mon_cache_misses += 1


def install_jax_monitoring() -> None:
    """Register the compile-duration and cache-verdict listeners with
    ``jax.monitoring``.
    Idempotent. The whole check-register-set sequence holds the lock
    (registration is a plain list append, never re-enters this module) —
    a check-then-act gap would let two concurrent watchers
    double-register and permanently double-count every compile."""
    global _mon_installed
    with _mon_lock:
        if _mon_installed:
            return
        import jax.monitoring as monitoring

        monitoring.register_event_duration_secs_listener(_on_duration)
        monitoring.register_event_listener(_on_event)
        _mon_installed = True


def monitoring_totals() -> tuple[int, float]:
    with _mon_lock:
        return _mon_compile_events, _mon_compile_seconds


def compile_cache_totals() -> tuple[int, int]:
    """``(hits, misses)`` of the persistent compile cache so far."""
    with _mon_lock:
        return _mon_cache_hits, _mon_cache_misses


# ---------------------------------------------------------------------------
# host spans on the profiler's clock
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=1)
def _trace_annotation() -> Any:
    """``jax.profiler.TraceAnnotation``, imported on first use."""
    from jax.profiler import TraceAnnotation

    return TraceAnnotation


def annotate(name: str, **stats: Any):
    """A host span on the profiler's clock: ``with annotate("pio:dispatch",
    batch=7): ...``. The span is recorded only while a profiler session is
    open (``POST /profile/capture``, ``PIO_PROFILE_DIR``, a benchmark's
    traced slice) and lands in that session's xplane file beside the
    device's ``XLA Ops``; with no session it costs under a microsecond and
    writes nothing. It wraps synchronous code on one thread, never an
    ``await``: the event loop interleaves coroutines on one thread and
    would break the nesting. A wait is a counter, not a span."""
    return _trace_annotation()(name, **stats)


class GcWatcher:
    """The interpreter's collection pauses, counted where they happen:
    a ``gc.callbacks`` hook that takes two clock readings a collection and
    adds to plain attributes (collections are serialised by the interpreter,
    and the hook runs for every one of them, the young ones included: 30 a
    second under the saturated benchmark cell, PERF.md), mirrored into
    ``pio_gc_pause_seconds_total{generation}`` and
    ``pio_gc_collections_total{generation}`` at scrape. A full collection
    (generation 2) also opens a ``pio:gc`` span on the collecting thread.
    ``install`` / ``remove`` bracket the owner's life."""

    GENERATIONS = 3

    def __init__(self, registry: MetricsRegistry):
        self.pause_s = [0.0] * self.GENERATIONS
        self.collections = [0] * self.GENERATIONS
        self._t0 = 0.0
        self._span: Any = None
        self._m_pause = registry.counter(
            "pio_gc_pause_seconds_total",
            "seconds the interpreter stood still collecting garbage, "
            "by generation",
            labelnames=("generation",),
        )
        self._m_collections = registry.counter(
            "pio_gc_collections_total",
            "garbage collections run, by generation",
            labelnames=("generation",),
        )
        self.collect()  # every generation scrapes as an explicit 0

    def install(self) -> None:
        if self._on_gc not in gc.callbacks:
            gc.callbacks.append(self._on_gc)

    def remove(self) -> None:
        if self._on_gc in gc.callbacks:
            gc.callbacks.remove(self._on_gc)

    def _on_gc(self, phase: str, info: dict[str, int]) -> None:
        if phase == "start":
            if info["generation"] == 2:
                self._span = annotate("pio:gc", generation=2)
                self._span.__enter__()
            self._t0 = time.perf_counter()
            return
        elapsed = time.perf_counter() - self._t0
        generation = info["generation"]
        self.pause_s[generation] += elapsed
        self.collections[generation] += 1
        if self._span is not None:
            self._span.__exit__(None, None, None)
            self._span = None

    def collect(self) -> None:
        """Registry collector: mirror the plain tallies at scrape."""
        for generation in range(self.GENERATIONS):
            label = str(generation)
            self._m_pause.set_total(self.pause_s[generation], generation=label)
            self._m_collections.set_total(
                self.collections[generation], generation=label
            )


# ---------------------------------------------------------------------------
# compile watcher
# ---------------------------------------------------------------------------


def _is_jitted(obj: Any) -> bool:
    # PjitFunction exposes _cache_size(); duck-typed so we never need to
    # import jax just to scan for compiled functions
    return callable(obj) and callable(getattr(obj, "_cache_size", None))


class CompileWatcher:
    """Samples jit cache sizes and turns growth into metrics.

    ``watch``/``watch_package`` snapshot each function's current cache
    size as its baseline, so compiles that already happened (deploy-time
    warmup — those are *paid for on purpose*) don't count as serving
    recompiles. ``sample()`` is cheap (one C call per watched function)
    and runs as a registry collector, i.e. exactly when someone scrapes
    ``/metrics``.
    """

    def __init__(
        self,
        registry: MetricsRegistry,
        storm_threshold: int = 4,
        package_prefix: str = "predictionio_tpu",
    ):
        self.registry = registry
        self.storm_threshold = max(1, storm_threshold)
        self.package_prefix = package_prefix
        self._lock = threading.Lock()
        self._watched: dict[str, Any] = {}
        self._last_size: dict[str, int] = {}
        self._seen_module_count = -1  # rescan trigger (see sample())
        self._misses = registry.counter(
            "pio_jit_cache_misses_total",
            "jit cache misses (recompiles) observed per engine function "
            "since warmup",
            labelnames=("fn",),
        )
        self._cache_size = registry.gauge(
            "pio_jit_cache_size",
            "current jit cache size (compiled program count) per function",
            labelnames=("fn",),
        )
        self._storm = registry.gauge(
            "pio_jit_recompile_storm",
            "recompiles seen in the most recent sampling interval; values "
            ">= the storm threshold also log a warning",
        )
        self._storm.set(0.0)
        self._xla_events = registry.counter(
            "pio_xla_compile_events_total",
            "XLA compile events reported by jax.monitoring",
        )
        self._xla_seconds = registry.counter(
            "pio_xla_compile_seconds_total",
            "cumulative seconds spent in XLA compilation (jax.monitoring)",
        )
        self._cache_hits = registry.counter(
            "pio_compile_cache_hits_total",
            "programs loaded from the persistent compile cache",
        )
        self._cache_misses = registry.counter(
            "pio_compile_cache_misses_total",
            "programs the persistent compile cache did not hold (compiled)",
        )
        install_jax_monitoring()

    # -- registration -------------------------------------------------------
    def watch(self, name: str, fn: Any) -> bool:
        """Track one compiled function; baseline = its current cache size."""
        if not _is_jitted(fn):
            return False
        try:
            size = int(fn._cache_size())
        except Exception:
            return False
        with self._lock:
            if name not in self._watched:
                self._watched[name] = fn
                self._last_size[name] = size
        return True

    def watch_package(self) -> int:
        """Scan loaded ``<package_prefix>`` modules for module-level jitted
        functions (the framework keeps its serving kernels there — e.g.
        ``ops/topk.py``'s three programs). Returns how many are watched."""
        for mod_name, module in list(sys.modules.items()):
            if module is None or not mod_name.startswith(self.package_prefix):
                continue
            for attr, value in list(vars(module).items()):
                if _is_jitted(value):
                    self.watch(f"{mod_name.removeprefix(self.package_prefix + '.')}"
                               f".{attr}", value)
        with self._lock:
            return len(self._watched)

    # -- sampling -----------------------------------------------------------
    def sample(self) -> int:
        """Refresh gauges/counters; returns recompiles since last sample.
        Registered as a registry collector so every scrape is current.
        The module scan only re-runs when sys.modules has grown (a lazy
        import may have brought new kernels); the steady-state cost per
        scrape is one ``_cache_size`` read per watched function."""
        n_modules = len(sys.modules)
        if n_modules != self._seen_module_count:
            self.watch_package()
            self._seen_module_count = n_modules
        with self._lock:
            watched = list(self._watched.items())
        new_misses = 0
        stormers: list[str] = []
        for name, fn in watched:
            try:
                size = int(fn._cache_size())
            except Exception:
                continue
            with self._lock:
                last = self._last_size.get(name, size)
                delta = size - last
                self._last_size[name] = size
            self._cache_size.set(size, fn=name)
            if delta > 0:
                self._misses.inc(delta, fn=name)
                new_misses += delta
                stormers.append(f"{name} (+{delta})")
        self._storm.set(float(new_misses))
        if new_misses >= self.storm_threshold:
            logger.warning(
                "recompile storm: %d jit cache misses since last sample: %s",
                new_misses,
                ", ".join(stormers),
            )
        events, seconds = monitoring_totals()
        self._xla_events.set_total(events)
        self._xla_seconds.set_total(seconds)
        hits, misses = compile_cache_totals()
        self._cache_hits.set_total(hits)
        self._cache_misses.set_total(misses)
        return new_misses

    def total_misses(self) -> float:
        return self._misses.total()


# ---------------------------------------------------------------------------
# stall accounting
# ---------------------------------------------------------------------------


def timed_block_until_ready(
    x: Any, registry: MetricsRegistry, where: str = "unspecified"
) -> Any:
    """``jax.block_until_ready`` that accounts its stall time.

    Algorithm code that must host-sync on the serving path should do it
    through here (and suppress the host-sync lint with a reason): the
    stall lands in ``pio_device_stall_seconds_total{where=...}`` and the
    ``pio_device_fetch_seconds`` histogram instead of disappearing into
    the request wall time. On the *training* path the same call is what
    the ``train-unaccounted-sync`` lint demands: when a train profile is
    recording (``obs.xray``), the stall is additionally attributed to the
    profile's current phase so device time can't leak out of the step
    timeline.
    """
    import jax

    t0 = time.perf_counter()
    out = jax.block_until_ready(x)
    elapsed = time.perf_counter() - t0
    from predictionio_tpu.obs import xray

    prof = xray.current_profile()
    if prof is not None:
        prof.note_device_time(elapsed, where)
    registry.counter(
        "pio_device_stall_seconds_total",
        "cumulative seconds spent blocked on device->host synchronization",
        labelnames=("where",),
    ).inc(elapsed, where=where)
    registry.histogram(
        "pio_device_fetch_seconds",
        "device->host fetch / block_until_ready stall durations",
    ).observe(elapsed)
    return out


__all__ = [
    "CompileWatcher",
    "GcWatcher",
    "annotate",
    "compile_cache_totals",
    "install_jax_monitoring",
    "monitoring_totals",
    "timed_block_until_ready",
]
