"""Engine (query) server — the deploy surface.

Reference parity: ``core/.../workflow/CreateServer.scala`` —
  POST /queries.json  (:464-616): decode query -> serving.supplement ->
                      per-algorithm predict -> serving.serve -> JSON;
                      optional async feedback POST of a `predict` event
                      (entityType ``pio_pr``, prId) to the event server
                      (:500-570); per-request latency bookkeeping (:578-585).
  GET /               engine status incl. requestCount / avgServingSec /
                      lastServingSec (:385-420).
  POST /reload        hot-swap to the latest COMPLETED engine instance
                      (MasterActor :317-343; the GET spelling is kept for
                      compat but logs a deprecation warning).
  GET /models + POST /models/{candidate,promote,rollback}
                      model registry / progressive rollout surface
                      (docs/model_registry.md): pinned stable version,
                      sticky canary or shadow candidate, metric-gated
                      auto-promote and auto-rollback.
  POST/GET /stop      graceful undeploy (used by the CLI's undeploy).
  GET /plugins.json   engine-server plugin inventory.

TPU notes: models are re-laid-out on device once at (re)load via
``Engine.prepare_deploy``; the predict path calls resident jitted functions
(e.g. the ALS top-k) so a request does one small host->device transfer and
one device->host top-k readback. Serving latency histogram kept in-process
(the measurement machinery BASELINE.md requires).
"""

from __future__ import annotations

import asyncio
import collections
import dataclasses
import datetime as _dt
import functools
import json
import logging
import os
import sys
import threading
import time
from typing import Any

from aiohttp import web

from predictionio_tpu.ann import lifecycle as ann_lifecycle
from predictionio_tpu.ann.metrics import AnnInstruments
from predictionio_tpu.bandit import (
    ARM_CANDIDATE,
    ARM_STABLE,
    DECIDE_PROMOTE,
    DECIDE_RETIRE,
    BanditCriteria,
    BanditInstruments,
    BanditLoop,
    RewardTailer,
)
from predictionio_tpu.controller.engine import Engine, EngineParams
from predictionio_tpu.data.storage.base import EngineInstance
from predictionio_tpu.data.storage.registry import Storage
from predictionio_tpu.obs import xray
from predictionio_tpu.obs.jaxprof import CompileWatcher, GcWatcher, annotate
from predictionio_tpu.obs.metrics import MetricsRegistry
from predictionio_tpu.obs.profiler import (
    ProfileBusyError,
    ProfileSession,
    ProfileStore,
)
from predictionio_tpu.obs.sampler import HostSampler
from predictionio_tpu.obs.tracing import (
    TRACE_HEADER,
    Tracer,
    current_span_id,
    current_trace_id,
    get_tracer,
    mint_trace_id,
    reset_trace_id,
    set_trace_id,
)
from predictionio_tpu.obs.slo import (
    SLOEngine,
    counter_ratio_source,
    histogram_threshold_source,
    paired_counter_source,
)
from predictionio_tpu.obs.waterfall import (
    PHASE_BATCH_ASSEMBLY,
    PHASE_CACHE,
    PHASE_DEVICE_COMPUTE,
    PHASE_DISPATCH,
    PHASE_FETCH,
    PHASE_INGRESS_PARSE,
    PHASE_QUEUE_WAIT,
    PHASE_RESPOND,
    PHASE_SERVE,
    PhaseWaterfall,
    phase_tags_ms,
)
from predictionio_tpu.obs.web import (
    BreakerInstruments,
    metrics_response,
    slo_response,
    traces_response,
)
from predictionio_tpu.registry.controller import (
    VERDICT_PROMOTE,
    VERDICT_ROLLBACK,
    PromotionCriteria,
    RolloutController,
)
from predictionio_tpu.registry.router import (
    LANE_CANDIDATE,
    LANE_SHADOW,
    LANE_STABLE,
    PLAN_OFF,
    Lane,
    RolloutInstruments,
    RolloutPlan,
    choose_lane,
    routing_key,
)
from predictionio_tpu.registry.result_cache import ResultCache
from predictionio_tpu.registry.store import (
    MODE_CANARY,
    MODE_SHADOW,
    ArtifactStore,
)
from predictionio_tpu.resilience import (
    OPEN,
    CircuitBreaker,
    CircuitOpenError,
    Deadline,
    DeadlineExceeded,
)
from predictionio_tpu.workflow import model_io
from predictionio_tpu.workflow.context import WorkflowContext
from predictionio_tpu.workflow.core_workflow import load_models_for_instance
from predictionio_tpu.workflow.engine_loader import EngineManifest, load_engine

logger = logging.getLogger(__name__)
UTC = _dt.timezone.utc


class LoadShedError(RuntimeError):
    """Admission control rejected the request (queue over high water).

    Not transient in-process: the server is telling the *client* to back
    off (`Retry-After`), not asking itself to retry into the same queue.
    """

    transient = False

    def __init__(self, message: str, retry_after_s: float):
        super().__init__(message)
        self.retry_after_s = retry_after_s


class ShuttingDownError(RuntimeError):
    """The server is stopping; in-flight and new requests answer 503."""

    transient = False

    def __init__(self):
        super().__init__("query server is shutting down")


@dataclasses.dataclass
class ServerConfig:
    ip: str = "0.0.0.0"
    port: int = 8000
    accesskey: str | None = None  # optional auth for /queries.json
    feedback: bool = False
    event_server_url: str | None = None  # e.g. http://localhost:7070
    feedback_access_key: str | None = None
    # TLS (ref common/SSLConfiguration.scala): PEM cert + key paths
    ssl_certfile: str | None = None
    ssl_keyfile: str | None = None
    bind_retries: int = 3  # ref MasterActor bind retry x3 (CreateServer.scala:348)
    # remote log shipping of serving errors (ref CreateServer.scala:423-434,
    # 595-611): POST log_prefix + JSON{engineInstance, message} to log_url
    log_url: str | None = None
    log_prefix: str = ""
    # serving micro-batch dispatch: concurrent /queries.json requests are
    # coalesced into one algorithm.predict_batch call (the reference predicts
    # per-request on an actor and carries a literal ``TODO: Parallelize``,
    # CreateServer.scala:488-491). max_batch_size <= 1 disables coalescing;
    # batch_window_ms > 0 adds a flush timer (rarely needed: batches form
    # adaptively while the device is busy). At most two batches are ahead of
    # the device at a time (a slot is a batch it has not answered yet: one
    # computing, one queued behind it to hide the host's launch); a batch is
    # closed when it gets its slot, so arrivals join it until then. Not a
    # setting: see _MicroBatcher.
    max_batch_size: int = 128
    batch_window_ms: float = 0.0
    # -- resilience (see docs/resilience.md) --------------------------------
    # per-request deadline: a /queries.json answer is due within this many
    # seconds or the request is failed with 503 instead of hanging; <= 0
    # disables (NOT recommended: a wedged device call then blocks forever)
    request_timeout_s: float = 10.0
    # admission control: when this many queries are already waiting in the
    # micro-batch queue, new arrivals are shed with 503 + Retry-After
    # instead of growing the queue without bound; 0 = unbounded
    queue_high_water: int = 256
    shed_retry_after_s: float = 1.0  # Retry-After hint on load-shed 503s
    # oversized request bodies are rejected with 413 before JSON decode
    max_payload_bytes: int = 1 << 20
    # background HTTP (feedback + remote log) total timeout: a stalled
    # collector must not accumulate hung tasks forever
    http_timeout_s: float = 10.0
    # dispatch circuit breaker: this many consecutive watchdog trips (device
    # calls blowing their deadline) opens the circuit and sheds all traffic
    # for breaker_recovery_s before probing again
    breaker_threshold: int = 3
    breaker_recovery_s: float = 5.0
    # -- model registry / progressive rollout (docs/model_registry.md) ------
    # artifact registry base dir; None disables the registry surface (the
    # metadata store's latest-COMPLETED instance is then the only source)
    registry_dir: str | None = None
    # sticky canary routing: the payload field identifying the user (a
    # user must see ONE model for a whole bake); missing fields fall back
    # to a deterministic hash of the payload
    sticky_key_field: str = "user"
    # consecutive candidate-lane failures that trip the candidate breaker
    # and force an INSTANT rollback (no bake-window wait)
    candidate_breaker_threshold: int = 3
    # promotion gates (see registry/controller.py PromotionCriteria)
    bake_window_s: float = 60.0
    bake_min_requests: int = 20
    max_error_ratio: float = 2.0
    max_p95_ratio: float = 1.5
    max_divergence_rate: float = 0.25
    auto_promote: bool = True
    bake_check_interval_s: float = 1.0  # controller evaluation cadence
    # shadow scoring backlog bound (batches): a candidate slower than live
    # traffic drops shadow samples (counted) instead of growing the queue
    # without limit — shadow is sampling, not accounting
    shadow_max_backlog: int = 8
    # -- SLOs (docs/observability.md): burn rates on /slo + pio_slo_* ------
    # latency objective: this fraction of /queries.json answers must land
    # at or under the threshold (default = the paper's <10ms p50 deploy
    # target; keep the threshold on a histogram bucket bound)
    slo_latency_threshold_s: float = 0.010
    slo_latency_objective: float = 0.50
    # availability objective: non-5xx fraction of /queries.json answers
    slo_availability_objective: float = 0.999
    # shed objective: fraction of arrivals NOT rejected by admission control
    slo_shed_objective: float = 0.99
    # -- version-keyed result cache (registry/result_cache.py) -------------
    # repeat queries answer from an LRU keyed (model_version, canonical
    # query bytes) BEFORE micro-batch admission — and even while the
    # dispatch breaker is open. 0 disables. Bypassed while a rollout is
    # active (bake gates need dispatched traffic; a canary answer is never
    # cached, so it can never be served from a stale lane).
    result_cache_size: int = 1024
    # staleness bound for serving components that read live state outside
    # the immutable model artifact (disabled-items files, constraint
    # entities); the model itself can't go stale under a version key
    result_cache_ttl_s: float = 10.0
    # -- fleet coordination (docs/fleet.md) --------------------------------
    # poll the registry's state_generation() on this cadence and adopt
    # stage/promote/rollback/stable-pin changes made by OTHER processes
    # (fleet replicas, the CLI, another replica's bake gate); 0 disables.
    # Requires a registry_dir.
    registry_sync_interval_s: float = 0.0
    # graceful drain (SIGTERM / supervised restart): how long to wait for
    # queued + in-flight queries to answer after the listener closes
    drain_grace_s: float = 15.0
    # -- profiling plane (docs/observability.md §Profiling plane) ----------
    # content-addressed profile bundle store (lazy-created on first
    # capture; newest-N GC) behind POST /profile/capture + `pio profile`
    profile_dir: str = "pio_obs/profiles"
    profile_max_bundles: int = 20
    # device-capture duration rails: ?ms= defaults/clamps here (the trace
    # buffers device events in memory — unbounded capture is a self-DoS)
    profile_default_ms: int = 500
    profile_max_ms: int = 10_000
    # always-on host stack sampler (GET /profile/stacks, pio top
    # --hotspots); <= 0 disables sampling (instruments still registered)
    sampler_period_s: float = 0.05
    # profile-on-alert: SLO-alert transitions and candidate-breaker trips
    # capture a rate-limited host-stack bundle; alert_trace_ms > 0 adds a
    # short device trace to it (off by default: a wedged device is often
    # WHY the alert fired, and a trace capture would then hang too)
    profile_on_alert: bool = True
    profile_alert_min_interval_s: float = 60.0
    profile_alert_trace_ms: int = 0
    # -- bandit exploration lanes (docs/bandit.md) -------------------------
    # policy steering the candidate traffic fraction while a rollout is
    # live: "epsilon" | "thompson"; None keeps the plain PR-4 bake gate.
    # With a policy set, the bandit owns the promote/retire decision (the
    # bake gate keeps its error/latency/divergence veto) and the plan
    # fraction follows the reward posterior every bake tick.
    bandit_policy: str | None = None
    bandit_epsilon: float = 0.1  # explore share (and cold-start fraction)
    bandit_min_pulls: int = 20  # per-arm evidence floor before deciding
    bandit_promote_threshold: float = 0.95  # P(candidate better) to promote
    bandit_retire_threshold: float = 0.05  # ... to retire the candidate
    bandit_min_fraction: float = 0.05
    bandit_max_fraction: float = 0.9
    # reward source: feedback events tailed from the event store and
    # matched to impressions by the trace id echoed into properties
    bandit_app_name: str | None = None  # app whose events carry rewards
    bandit_channel_name: str | None = None
    bandit_reward_events: tuple[str, ...] = ("reward",)
    bandit_trace_property: str = "traceId"
    bandit_reward_property: str = "reward"
    bandit_impression_capacity: int = 65536
    bandit_seed: int = 0

    def ssl_context(self):
        from predictionio_tpu.utils.tls import server_ssl_context

        return server_ssl_context(self.ssl_certfile, self.ssl_keyfile)


# Precompiled encoders, split by contract (the hot respond path must not
# pay for canonicalization it doesn't need):
#  - _fast_dumps: compact, insertion-ordered — response serialization.
#    json.dumps re-parses its kwargs into a fresh encoder per call; a
#    prebuilt JSONEncoder skips that per-request setup.
#  - _CANONICAL: sort_keys — ONLY for paths that need order-independent
#    bytes (shadow divergence comparison, result-cache keys).
# No default= on _FAST: a non-JSON-serializable value in a response body
# (a numpy scalar leaking from an engine) must raise like web.json_response
# always did, not silently reach clients as a string.
_FAST = json.JSONEncoder(separators=(",", ":"))
_fast_dumps = _FAST.encode
_CANONICAL = json.JSONEncoder(
    sort_keys=True, separators=(",", ":"), default=str
)


def _canonical_json(value: Any) -> str:
    """Order-independent JSON for shadow divergence comparison."""
    try:
        return _CANONICAL.encode(value)
    except (TypeError, ValueError):
        return repr(value)


def _canonical_query_bytes(payload: Any) -> bytes:
    """The result-cache key: canonical bytes of the raw query payload, so
    ``{"user": "u1", "num": 10}`` and ``{"num": 10, "user": "u1"}`` share
    one entry."""
    return _CANONICAL.encode(payload).encode()


def _swallow_result(fut) -> None:
    """Done-callback for executor futures the watchdog may abandon: retrieve
    the late exception so the loop never logs 'exception was never
    retrieved' for a batch that was already failed and answered."""
    if not fut.cancelled():
        fut.exception()


@dataclasses.dataclass
class _QItem:
    """One queued query: its payload, the caller's future, the request
    deadline, the ingress trace id (the contextvar does NOT survive the
    hop onto the dispatch thread — it rides here instead), the enqueue
    time (queue-wait accounting), and the mutable ``phases`` channel the
    handler shares with the batcher so per-request waterfall timestamps
    (``t_collect``/``t_done``) flow back without changing ``submit``'s
    return contract."""

    payload: Any
    fut: asyncio.Future
    deadline: Deadline
    trace_id: str | None
    t_submit: float
    phases: dict[str, float] = dataclasses.field(default_factory=dict)
    # canonical query bytes when this answer is result-cacheable (miss on
    # a quiesced stable lane); the batcher inserts the encoded body under
    # (answered version, key) once the batch resolves
    cache_key: bytes | None = None
    # the ingress span's id: the parent of this query's `query.batch` span
    parent_span_id: str | None = None


class _Slot:
    """One of the batcher's places ahead of the chip, held by a dispatched
    batch until the device has answered it. It goes back exactly once,
    whichever gets there first: ``device_answered`` from the fetch thread,
    the moment ``finalize`` has the packed results on the host and before
    it serves them, or ``release`` on the event loop (a failed dispatch, and
    the end of the batch's ``_finish`` task however it ends: result,
    exception, watchdog trip, cancellation). ``device_answered`` also reads
    the fetch thread's clock and hands the reading to ``on_answer`` on the
    loop, with the release: the batcher counts from it how long the device
    owed this answer (``_MicroBatcher._answered``)."""

    __slots__ = ("_slots", "_loop", "_held")

    def __init__(self, slots: asyncio.Semaphore, loop: asyncio.AbstractEventLoop):
        self._slots = slots
        self._loop = loop
        self._held = True

    def release(self, _finished: asyncio.Task | None = None) -> None:
        if self._held:
            self._held = False
            self._slots.release()

    def _hand_back(self, on_answer, *answer) -> None:
        self.release()
        on_answer(*answer)

    def device_answered(self, on_answer, *args) -> None:
        """Runs on the fetch thread; ``on_answer(*args, the answer's time)``
        runs on the loop."""
        try:
            self._loop.call_soon_threadsafe(
                self._hand_back, on_answer, *args, time.perf_counter()
            )
        except RuntimeError:
            pass  # the loop closed under a finalize that outlived shutdown


class _MicroBatcher:
    """Coalesces concurrent /queries.json requests into batched predicts.

    Requests enqueue (payload, future) pairs; a single dispatcher hands
    batches to a dedicated dispatch thread (decode -> supplement -> launch)
    and their finalizes (block on the device -> unpack -> serve -> encode)
    to fetch threads, all off the event loop. Batching is *adaptive*: while
    the device is busy, new arrivals accumulate and become the next batch —
    a solo request dispatches immediately (no timer penalty), a concurrent
    burst converges to one device call per batch. An optional flush window
    can be configured but is 0 by default.

    A *slot* is a batch the device has not answered yet, and there are
    ``SLOTS`` = 2 of them: one batch computing and one queued behind it is
    what hides the host's decode, upload and launch of the next; one chip
    runs batches one after another, so every further batch ahead of it only
    waits in the device's queue, and every query in it waits one kernel
    longer. The dispatcher waits for the first pending query, then for a
    slot, and only then closes the batch: nothing is collected and held, so
    what arrives while it waits rides the batch that gets the slot. The
    slot goes back when ``finalize`` has the device's results on the host,
    not when the host has finished serving them (see ``_Slot``), so batch
    n's serve overlaps the dispatch of n + 1 and n + 2; ``FETCH_THREADS``
    leaves room for those overlapping serve stages.
    """

    SLOTS = 2
    FETCH_THREADS = 4

    def __init__(
        self,
        server: "QueryServer",
        max_batch: int,
        window_s: float,
        high_water: int = 0,
        shed_retry_after_s: float = 1.0,
    ):
        import concurrent.futures

        self._server = server
        self.max_batch = max(1, max_batch)
        self.window_s = max(0.0, window_s)
        self.high_water = max(0, high_water)
        self.shed_retry_after_s = shed_retry_after_s
        # pending queries, oldest first: they stay here until a batch is
        # closed, so queue_depth, shedding and close() see every one of them
        self._queue: collections.deque[_QItem] = collections.deque()
        self._arrived = asyncio.Event()
        self._task: asyncio.Task | None = None
        self._closed = False
        # dispatch runs on one thread (decode + device enqueue, fast);
        # finalizes block on the device and then serve, on their own threads
        self._dispatch_pool = concurrent.futures.ThreadPoolExecutor(
            max_workers=1, thread_name_prefix="pio-dispatch"
        )
        self._fetch_pool = concurrent.futures.ThreadPoolExecutor(
            max_workers=self.FETCH_THREADS, thread_name_prefix="pio-fetch"
        )
        self._slots = asyncio.Semaphore(self.SLOTS)
        self._finish_tasks: set[asyncio.Task] = set()
        self._cancelled_tasks: list[asyncio.Task] = []
        self.batches_dispatched = 0
        self.queries_dispatched = 0
        # running number of collected batches: the `batch` stat of a batch's
        # pio: spans and the `batch` tag of its riders' query.batch spans
        self._batch_seq = 0
        self.watchdog_trips = 0  # batches failed for blowing their deadline
        self.shed_count = 0  # requests rejected by admission control
        # queries of dispatched batches whose futures are not resolved yet:
        # up where a batch's `_finish` is scheduled, down in `_settled`
        self._inflight = 0
        # batches whose `_finish` task has not taken its first step, by batch
        # number: the task's body answers a cancelled batch, and `close()`
        # may cancel a task before its body ever runs
        self._unstarted: dict[int, list[_QItem]] = {}
        # the loop's clock at the device's newest answer (`_answered`)
        self._last_answer = 0.0

    @property
    def queue_depth(self) -> int:
        return len(self._queue)

    async def submit(
        self,
        payload: Any,
        deadline: Deadline | None = None,
        phases: dict[str, float] | None = None,
        t_submit: float | None = None,
        cache_key: bytes | None = None,
    ) -> Any:
        """Enqueue one query payload; returns the encoded result body or
        raises the per-query error. Fails fast when the server is shutting
        down (never restarts the collect loop against shut-down pools) and
        sheds with ``LoadShedError`` when the queue is over high water.
        ``phases`` (optional) is filled with waterfall timestamps
        (``t_collect``, ``t_done``) as the query moves through the
        pipeline; ``t_submit`` lets the caller anchor the queue-wait phase
        at its own last measured boundary so adjacent phases tile."""
        if self._closed:
            raise ShuttingDownError()
        if self.high_water and len(self._queue) >= self.high_water:
            self.shed_count += 1
            self._server._m_shed.inc()
            raise LoadShedError(
                f"admission queue over high water "
                f"({len(self._queue)}/{self.high_water})",
                self.shed_retry_after_s,
            )
        if deadline is None:
            deadline = Deadline.never()
        fut = asyncio.get_running_loop().create_future()
        self._queue.append(
            _QItem(
                payload,
                fut,
                deadline,
                current_trace_id(),
                t_submit if t_submit is not None else time.perf_counter(),
                phases if phases is not None else {},
                cache_key,
                current_span_id(),
            )
        )
        self._arrived.set()
        if self._task is None or self._task.done():
            self._task = asyncio.ensure_future(self._run())
        return await fut

    @staticmethod
    def _fail_batch(batch: list[_QItem], exc: BaseException) -> None:
        for item in batch:
            if not item.fut.done():
                item.fut.set_exception(exc)

    def _tick(self, state: str, since: float) -> float:
        """Add the time from ``since`` to now to one state of the loop's
        clock (``pio_batch_loop_seconds_total``); returns now, the next
        interval's start, so that consecutive intervals tile."""
        now = time.perf_counter()
        self._server._m_loop.inc(now - since, state=state)
        return now

    def _answered(self, dispatch_end: float, t_answered: float) -> None:
        """The device answered a batch at ``t_answered``: it owed this answer
        since the later of its previous answer and the end of this batch's
        dispatch, so a server with nothing dispatched adds no time. The gaps
        and their squares are summed: the quotient of the two sums' growth is
        the gap a random moment of the busy time falls into."""
        gap = max(0.0, t_answered - max(self._last_answer, dispatch_end))
        self._last_answer = max(self._last_answer, t_answered)
        self._server._m_answer_gap.inc(gap)
        self._server._m_answer_gap_squared.inc(gap * gap)

    def _settled(self, queries: int, _finished: asyncio.Task) -> None:
        """Done-callback of a batch's ``_finish`` task, however it ended:
        result, exception, watchdog trip or cancellation. It is given the
        batch's SIZE and not the batch: a callback that keeps the queries
        alive until it runs, behind their callers' wake-ups, cost the
        saturated webgraph cell its first seconds (PERF.md section 6, PR 39)."""
        self._inflight -= queries

    def _dispatch_combined(self, items: list[_QItem], batch_no: int = 0):
        """Idle fast path: dispatch AND finalize in ONE executor hop.

        The dispatch->fetch pipeline exists to overlap batch n's transport
        with batch n+1's dispatch — but a solo request on an idle server
        has nothing to overlap with, and pays two thread wakes + two
        watchdog waits for it. When the collect loop sees a batch of one
        with nothing queued and nothing in flight, the whole
        decode->dispatch->fetch->serve chain runs inside the single
        dispatch-pool call; the returned finalize is already resolved
        (``resolved`` attribute), so ``_finish`` skips the fetch executor
        entirely. Arrivals during the combined call simply form the next
        batch — exactly what adaptive batching does while a dispatch is
        busy."""
        fin = self._server._dispatch_query_batch(items, batch_no)
        results = fin()

        def resolved():
            return results

        resolved.resolved = True
        resolved.timings = getattr(fin, "timings", None)
        return resolved

    def _replace_dispatch_pool(self) -> None:
        """Abandon a dispatch thread stuck past its batch's deadline: the
        single dispatch thread is the serialization point for ALL traffic,
        so a wedged device call head-of-line-blocks every later batch
        unless we walk away from it. The old executor is shut down without
        cancelling the running call (it cannot be interrupted); its thread
        finishes (or hangs) in the background while a fresh pool serves
        new batches."""
        import concurrent.futures

        old = self._dispatch_pool
        self._dispatch_pool = concurrent.futures.ThreadPoolExecutor(
            max_workers=1, thread_name_prefix="pio-dispatch"
        )
        old.shutdown(wait=False)

    def _replace_fetch_pool(self) -> None:
        """Same walk-away for a finalize stuck on the transport. Other
        in-flight finalizes on the old pool run to completion there."""
        import concurrent.futures

        old = self._fetch_pool
        self._fetch_pool = concurrent.futures.ThreadPoolExecutor(
            max_workers=self.FETCH_THREADS, thread_name_prefix="pio-fetch"
        )
        old.shutdown(wait=False)

    async def _run(self) -> None:
        loop = asyncio.get_running_loop()
        # the loop's clock (`_tick`): every interval from here on goes to one
        # of idle, the slot wait's own counter, collect and dispatch
        mark = time.perf_counter()
        # `submit` starts this task on an arrival: its first turn is a wake
        started = True
        while True:
            # slot first, batch closed last: wait for a pending query, then
            # for a slot, and only then drain the queue into the batch. A
            # cancellation (close()) at any of these awaits holds nothing:
            # the queries are still queued and close() answers them
            woken, started = started or not self._queue, False
            if not self._queue:
                self._arrived.clear()
                await self._arrived.wait()
            if self.window_s > 0:
                await asyncio.sleep(self.window_s)
            queued_before = len(self._queue)
            # what this turn last waited on is what closes its batch: a slot
            # (whatever arrives meanwhile rides along), else the first
            # arrival on an empty queue, else the previous batch's dispatch
            after = "slot" if self._slots.locked() else "idle" if woken else "dispatch"
            wait_t0 = self._tick("idle", mark)
            await self._slots.acquire()
            slot = _Slot(self._slots, loop)
            collect_t = time.perf_counter()
            self._server._m_slot_wait.inc(collect_t - wait_t0)
            self._batch_seq += 1
            batch_no = self._batch_seq
            with annotate("pio:loop.collect", batch=batch_no):
                # requests that expired while queued are failed here, not
                # dispatched: device work for an answer nobody is waiting on
                # would only deepen an overload
                live = []
                joined = 0
                cut = len(self._queue) > self.max_batch
                inflight = self._inflight
                for arrival in range(min(len(self._queue), self.max_batch)):
                    item = self._queue.popleft()
                    if item.fut.done():  # client gone / cancelled
                        # its probe slot (if it held one) can never be recorded
                        self._server.dispatch_breaker.release_probe()
                        continue
                    if item.deadline.expired:
                        item.fut.set_exception(
                            DeadlineExceeded("query expired in admission queue")
                        )
                    else:
                        live.append(item)
                        if arrival >= queued_before:
                            joined += 1  # arrived during the wait for this slot
                        queue_wait_s = collect_t - item.t_submit
                        item.phases["t_collect"] = collect_t
                        self._server.waterfall.observe(
                            PHASE_QUEUE_WAIT, queue_wait_s, item.trace_id
                        )
                if not live:
                    slot.release()
                    mark = self._tick("collect", collect_t)
                    continue
                batch = live
                batch_deadline = Deadline.min_of([it.deadline for it in batch])
                # idle fast path: a batch of ONE with nothing queued behind
                # it and no finalize in flight has nothing to pipeline
                # against — run dispatch AND finalize in one executor hop
                # (see _dispatch_combined); the dispatch watchdog below still
                # bounds the whole combined call. Any larger batch means the
                # server is under load, where occupying the dispatch thread
                # through the fetch would serialize the pipeline it exists to
                # overlap.
                combined = (
                    len(batch) == 1
                    and not self._queue
                    and not self._finish_tasks
                )
                dispatch_t0 = self._tick("collect", collect_t)
                # the batch list itself is the handoff — the dispatch
                # thread reads payload/trace_id straight off the queued
                # items (no per-batch tuple-list materialization)
                exec_fut = loop.run_in_executor(
                    self._dispatch_pool,
                    self._dispatch_combined
                    if combined
                    else self._server._dispatch_query_batch,
                    batch,
                    batch_no,
                )
                exec_fut.add_done_callback(_swallow_result)
            # dispatch under a watchdog. NOT wait_for(): cancelling an
            # executor future whose fn is already running blocks until the
            # fn returns — the exact hang the watchdog exists to escape.
            # asyncio.wait() times out without cancelling; the stuck call
            # is then abandoned and its pool replaced.
            try:
                done, pending = await asyncio.wait(
                    [exec_fut], timeout=batch_deadline.remaining()
                )
            except asyncio.CancelledError:
                slot.release()
                # shutdown mid-dispatch: this batch's clients must get a
                # response too (close()'s drain only covers queued items)
                self._fail_batch(batch, ShuttingDownError())
                raise  # close() must actually terminate the collect loop
            if pending:
                # watchdog trip: fail THIS batch, walk away from the stuck
                # dispatch thread, keep serving everyone else
                slot.release()
                self.watchdog_trips += 1
                self._server._m_watchdog.inc()
                self._replace_dispatch_pool()
                self._server.dispatch_breaker.record_failure()
                self._fail_batch(
                    batch,
                    DeadlineExceeded("micro-batch dispatch: deadline exceeded"),
                )
                mark = self._tick("dispatch", dispatch_t0)
                continue
            dispatch_s = time.perf_counter() - dispatch_t0
            try:
                finalize = exec_fut.result()
            except BaseException as exc:
                slot.release()
                self._server.dispatch_breaker.record_failure()
                for item in batch:
                    if not item.fut.done():
                        item.fut.set_exception(exc)
                mark = self._tick("dispatch", dispatch_t0)
                continue
            if getattr(finalize, "resolved", False):
                # combined fast path: the measured dispatch window swallowed
                # device compute + serve; carve them back out so _finish's
                # device/serve observations keep the waterfall tiling
                t = getattr(finalize, "timings", None) or {}
                device_s = max(0.0, t.get("device_s", 0.0))
                dispatch_s = max(
                    0.0, dispatch_s - device_s - t.get("serve_s", 0.0)
                )
                # the device's answer came inside the one call: the dispatch
                # proper ended where its wait for the device began
                self._answered(
                    dispatch_t0 + dispatch_s, dispatch_t0 + dispatch_s + device_s
                )
            # batch-scoped waterfall phases: every rider waits out the whole
            # batch, so each query is accounted the batch's duration
            assembly_s = max(0.0, dispatch_t0 - collect_t)
            for item in batch:
                self._server.waterfall.observe(
                    PHASE_BATCH_ASSEMBLY, assembly_s, item.trace_id
                )
                self._server.waterfall.observe(
                    PHASE_DISPATCH, dispatch_s, item.trace_id
                )
            self.batches_dispatched += 1
            self.queries_dispatched += len(batch)
            self._server._m_joined_in_slot_wait.inc(joined)
            self._server._m_closed.inc(after=after)
            self._server._m_closed_queries.inc(len(batch), after=after)
            if cut:
                self._server._m_cut.inc()
            self._server._m_inflight_at_close.inc(inflight)
            # finish asynchronously: the collect loop immediately forms and
            # dispatches the next batch while this one's fetch is in flight
            task = asyncio.ensure_future(
                self._finish(
                    batch,
                    finalize,
                    batch_deadline,
                    slot.device_answered,
                    dispatch_s,
                    dispatch_t0 + dispatch_s,
                    batch_no,
                )
            )
            self._finish_tasks.add(task)
            self._inflight += len(batch)
            self._unstarted[batch_no] = batch
            task.add_done_callback(self._finish_tasks.discard)
            task.add_done_callback(slot.release)  # if finalize has not already
            task.add_done_callback(functools.partial(self._settled, len(batch)))
            mark = self._tick("dispatch", dispatch_t0)

    async def _finish(
        self,
        batch: list[_QItem],
        finalize,
        deadline: Deadline,
        device_answered,
        dispatch_s: float = 0.0,
        dispatch_end: float = 0.0,
        batch_no: int = 0,
    ) -> None:
        loop = asyncio.get_running_loop()
        # the first step: from here on a cancellation is answered below
        self._unstarted.pop(batch_no, None)
        fetch_t0 = time.perf_counter()
        if getattr(finalize, "resolved", False):
            # combined fast path (_dispatch_combined): the dispatch call
            # already ran finalize on the dispatch thread under the dispatch
            # watchdog — results are in hand, no fetch hop. The device
            # transport DID block inside that call (the finalize's device_s
            # window), so it still counts as stall time: an idle-but-serving
            # instance, where every solo request takes this path, must not
            # read as zero-stall
            results = finalize()
            fetch_s = time.perf_counter() - fetch_t0
            device_s = (getattr(finalize, "timings", None) or {}).get(
                "device_s", 0.0
            )
            if device_s > 0.0:
                self._server._m_stall.inc(device_s, where="micro-batch-fetch")
            self._server.dispatch_breaker.record_success()
        else:
            # finalize hands the slot back itself, from the fetch thread, the
            # moment the device's results are on the host: the next batch is
            # closed and dispatched while this one is still being served
            exec_fut = loop.run_in_executor(
                self._fetch_pool,
                finalize,
                functools.partial(device_answered, self._answered, dispatch_end),
            )
            exec_fut.add_done_callback(_swallow_result)
            try:
                done, pending = await asyncio.wait(
                    [exec_fut], timeout=deadline.remaining()
                )
            except asyncio.CancelledError:
                # shutdown: resolve the batch's futures (handlers awaiting
                # them would otherwise hang for aiohttp's whole shutdown
                # timeout)
                self._fail_batch(batch, ShuttingDownError())
                raise
            if pending:
                # fetch watchdog: same walk-away as dispatch (see _run);
                # other finalizes in flight on the old pool still run to
                # completion
                self.watchdog_trips += 1
                self._server._m_watchdog.inc()
                self._replace_fetch_pool()
                self._server.dispatch_breaker.record_failure()
                self._fail_batch(
                    batch,
                    DeadlineExceeded("micro-batch fetch: deadline exceeded"),
                )
                return
            fetch_s = time.perf_counter() - fetch_t0
            # the fetch phase is where the host blocks on the device
            # transport: account it as stall time (see obs/jaxprof.py)
            self._server._m_stall.inc(fetch_s, where="micro-batch-fetch")
            try:
                results = exec_fut.result()
            except BaseException as exc:
                # a finalize that raised wholesale is a dispatch-path
                # failure (per-query errors are isolated inside finalize and
                # arrive as entries in the results) — it must count against
                # the breaker exactly like a failed dispatch, not close a
                # half-open circuit
                results = [(exc, "")] * len(batch)
                self._server.dispatch_breaker.record_failure()
            else:
                self._server.dispatch_breaker.record_success()
        done_t = time.perf_counter()
        with annotate("pio:loop.finish", batch=batch_no):
            # waterfall decomposition of the dispatch-end -> results-distributed
            # window: device compute and serve are measured inside finalize (it
            # publishes them via its `timings` attribute); everything else in
            # the window — executor hop, transport readback, result unpack — is
            # the fetch residual
            timings = getattr(finalize, "timings", None) or {}
            device_s = max(0.0, timings.get("device_s", 0.0))
            serve_s = max(0.0, timings.get("serve_s", 0.0))
            window_s = (done_t - dispatch_end) if dispatch_end else fetch_s
            fetch_resid_s = max(0.0, window_s - device_s - serve_s)
            wf = self._server.waterfall
            for item, (out, version) in zip(batch, results):
                wf.observe(PHASE_DEVICE_COMPUTE, device_s, item.trace_id)
                wf.observe(PHASE_FETCH, fetch_resid_s, item.trace_id)
                wf.observe(PHASE_SERVE, serve_s, item.trace_id)
                if item.cache_key is not None and not isinstance(out, BaseException):
                    self._server._cache_store(version, item.cache_key, out)
                item.phases["t_done"] = done_t
                queue_s = max(
                    0.0, item.phases.get("t_collect", item.t_submit) - item.t_submit
                )
                # one `batch` span per query, carrying the full phase waterfall
                # AND the model version that answered — the hop between the
                # ingress span and any storage spans the engine's serving
                # components recorded
                self._server.tracer.record_span(
                    "query.batch",
                    kind="batch",
                    duration_s=done_t - item.t_submit,
                    trace_id=item.trace_id,
                    status=type(out).__name__ if isinstance(out, BaseException) else "ok",
                    parent_id=item.parent_span_id,
                    batch=batch_no,
                    batch_size=len(batch),
                    version=version,
                    queue_ms=round(queue_s * 1000, 3),
                    dispatch_ms=round(dispatch_s * 1000, 3),
                    fetch_ms=round(fetch_s * 1000, 3),
                    **phase_tags_ms(
                        device_compute=device_s,
                        serve=serve_s,
                        fetch_residual=fetch_resid_s,
                    ),
                )
                if item.fut.done():  # client gone / cancelled
                    continue
                if isinstance(out, BaseException):
                    item.fut.set_exception(out)
                else:
                    item.fut.set_result(out)

    def close(self) -> None:
        self._closed = True  # new submits fail fast from here on
        if self._task is not None:
            self._task.cancel()
            self._cancelled_tasks.append(self._task)
            self._task = None
        for task in list(self._finish_tasks):
            task.cancel()
            self._cancelled_tasks.append(task)
        # fail everything still queued: enqueued-but-never-collected items
        # have handlers awaiting their futures (collected/dispatched batches
        # are resolved by the _run/_finish cancellation paths, and a batch
        # whose _finish was cancelled before its first step right here)
        exc = ShuttingDownError()
        for batch in self._unstarted.values():
            self._fail_batch(batch, exc)
        self._unstarted.clear()
        while self._queue:
            item = self._queue.popleft()
            if not item.fut.done():
                item.fut.set_exception(exc)
        self._dispatch_pool.shutdown(wait=False, cancel_futures=True)
        self._fetch_pool.shutdown(wait=False, cancel_futures=True)

    async def wait_closed(self) -> None:
        """Drain the cancellations issued by ``close()`` so shutdown leaves
        zero pending asyncio tasks behind."""
        tasks = [t for t in self._cancelled_tasks if not t.done()]
        self._cancelled_tasks.clear()
        if tasks:
            await asyncio.gather(*tasks, return_exceptions=True)


class QueryServer:
    def __init__(
        self,
        engine: Engine,
        engine_params: EngineParams,
        models: list[Any],
        manifest: EngineManifest,
        instance_id: str,
        storage: Storage | None = None,
        config: ServerConfig | None = None,
        plugin_context=None,
        registry_store: ArtifactStore | None = None,
        model_version: str | None = None,
    ):
        from predictionio_tpu.workflow.server_plugins import (
            EngineServerPluginContext,
        )

        self.engine = engine
        self.engine_params = engine_params
        self.manifest = manifest
        self.instance_id = instance_id
        self.storage = storage or Storage.instance()
        self.config = config or ServerConfig()
        self.plugin_context = plugin_context or EngineServerPluginContext()
        self.registry_store = registry_store or (
            ArtifactStore(self.config.registry_dir)
            if self.config.registry_dir
            else None
        )
        _, _, algorithms, serving = engine.make_components(engine_params)
        # (algorithms, serving, models, version) live in ONE Lane tuple
        # swapped atomically: the dispatch thread snapshots it in a single
        # attribute read, so a concurrent /reload or promote can never pair
        # new algorithms with old models (attribute-by-attribute assignment
        # allowed exactly that interleave)
        self._active: Lane = Lane(
            algorithms,
            serving,
            models,
            model_version or instance_id,
            instance_id,
            engine_params,
        )
        # progressive rollout: an optional candidate Lane next to stable,
        # with the routing plan snapshotted separately (an in-flight batch
        # keeps whatever lanes it read — same contract as /reload)
        self._candidate: Lane | None = None
        self._plan: RolloutPlan = PLAN_OFF
        # serializes lane swaps across the event loop (promote endpoint,
        # controller tick) and dispatch threads (breaker-trip rollback)
        self._rollout_mutex = threading.Lock()
        self._rollout_task: asyncio.Task | None = None
        # fleet coordination: the registry state generation this process
        # last reconciled against (None = never; first tick reconciles,
        # which is exactly right after a crash-restart mid-bake)
        self._registry_sync_task: asyncio.Task | None = None
        self._seen_state_gen: int | None = None
        # graceful drain: listener closed, in-flight answered, then exit
        self._draining = False
        self._inflight_requests = 0
        self._drain_task: asyncio.Task | None = None
        # rollout generation: bumped on every stage/promote/rollback so
        # in-flight shadow work (queued behind a slow candidate) can tell
        # it belongs to a PREVIOUS rollout and must not feed the breaker
        # or counters of the current one
        self._rollout_gen = 0
        self._shadow_lock = threading.Lock()
        self._shadow_pending = 0
        self.start_time = _dt.datetime.now(tz=UTC)
        self.serving_devices: dict[str, Any] | None = None  # set by warmup
        self.request_count = 0
        self.avg_serving_sec = 0.0
        self.last_serving_sec = 0.0
        # -- observability (docs/observability.md) --------------------------
        self.metrics = MetricsRegistry()
        self.tracer: Tracer = get_tracer()
        m = self.metrics
        self._m_requests = m.counter(
            "pio_requests_total",
            "HTTP requests served, by route and status",
            labelnames=("endpoint", "status"),
        )
        # ONE latency histogram backs both the legacy `/` status page and
        # /metrics — two independent ladders reported different p95s for
        # the same traffic and sent operators chasing phantom regressions
        self._m_latency = m.histogram(
            "pio_request_seconds",
            "HTTP request wall time, by route",
            labelnames=("endpoint",),
        )
        self._m_slot_wait = m.counter(
            "pio_batch_slot_wait_seconds_total",
            "seconds the micro-batcher waited for a slot with at least one "
            "query pending (once a batch; the batch stays open meanwhile; "
            "inside the queue_wait phase)",
        )
        self._m_joined_in_slot_wait = m.counter(
            "pio_batch_joined_in_slot_wait_total",
            "queries of dispatched micro-batches that arrived after the "
            "batcher began waiting for that batch's slot",
        )
        # a batch's life between `submit` and the device's answer, on the
        # loop's clock, once a batch (docs/observability.md, "Reading a
        # batch's life"); every label's series stands at 0 from the start
        self._m_loop = m.counter(
            "pio_batch_loop_seconds_total",
            "seconds of the micro-batcher's loop by what it was doing: "
            "state=idle the queue was empty (the flush window's sleep too), "
            "state=collect a slot in hand to the batch's hand-off to the "
            "dispatch thread, state=dispatch awaiting that thread (and the "
            "loop's own way back to the batcher) to the scheduling of the "
            "batch's finish; with "
            "pio_batch_slot_wait_seconds_total they tile the loop's wall",
            labelnames=("state",),
        )
        self._m_closed = m.counter(
            "pio_batch_closed_total",
            "dispatched micro-batches by what the loop last waited on before "
            "it drained the queue: after=slot a slot (both were taken when it "
            "asked), after=idle the first arrival on an empty queue with a "
            "slot free, after=dispatch neither (back from the previous "
            "batch's dispatch to a queue that was not empty)",
            labelnames=("after",),
        )
        self._m_closed_queries = m.counter(
            "pio_batch_closed_queries_total",
            "queries of dispatched micro-batches, by the same label",
            labelnames=("after",),
        )
        for state in ("idle", "collect", "dispatch"):
            self._m_loop.inc(0.0, state=state)
        for after in ("slot", "idle", "dispatch"):
            self._m_closed.inc(0.0, after=after)
            self._m_closed_queries.inc(0.0, after=after)
        self._m_cut = m.counter(
            "pio_batch_cut_total",
            "dispatched micro-batches whose drain stopped at the batch limit "
            "with queries left queued: over pio_batch_closed_total, the share "
            "of batches that the limit closed and not the slot",
        )
        self._m_inflight_at_close = m.counter(
            "pio_batch_inflight_at_close_total",
            "queries of earlier batches not yet answered to their callers, "
            "summed over the moments a dispatched micro-batch was closed",
        )
        self._m_answer_gap = m.counter(
            "pio_batch_answer_gap_seconds_total",
            "seconds the device owed an answer: from the later of its "
            "previous answer and the end of the batch's dispatch to the "
            "batch's answer, summed over batches",
        )
        self._m_answer_gap_squared = m.counter(
            "pio_batch_answer_gap_squared_seconds_total",
            "the same gaps squared and summed: over the sum of the gaps, the "
            "gap a random moment of the busy time falls into",
        )
        # what power-of-two bucketing launched (ops/topk.batch_bucket keeps
        # the tallies, the engines know no server): mirrored at scrape
        self._m_serve_rows = m.counter(
            "pio_serve_rows_total",
            "rows of launched serving buckets: kind=real are queries, "
            "kind=bucket is what the device scored (the rest is padding)",
            labelnames=("kind",),
        )
        self._m_serve_batches = m.counter(
            "pio_serve_batches_total",
            "serving batches launched, by power-of-two bucket",
            labelnames=("bucket",),
        )
        self._m_serve_table_bytes = m.gauge(
            "pio_serve_table_bytes",
            "bytes of the factor tables a ServingIndex holds on the device: "
            "table=item is read whole by every batch and kept at the width "
            "the product multiplies in (ops/topk.item_table_dtype)",
            labelnames=("table",),
        )
        m.register_collector(self._collect_buckets)
        # the interpreter's collection pauses (hook installed by start(),
        # removed by stop())
        self.gc_watcher = GcWatcher(m)
        m.register_collector(self.gc_watcher.collect)
        self._m_stall = m.counter(
            "pio_device_stall_seconds_total",
            "cumulative seconds spent blocked on device->host synchronization",
            labelnames=("where",),
        )
        self._m_shed = m.counter(
            "pio_load_shed_total",
            "requests rejected by admission control (503 + Retry-After)",
        )
        self._m_deadline = m.counter(
            "pio_deadline_exceeded_total",
            "requests failed for blowing their deadline (queued or in flight)",
        )
        self._m_watchdog = m.counter(
            "pio_watchdog_trips_total",
            "batches abandoned because a device call blew its deadline",
        )
        self._m_breaker_rejected = m.counter(
            "pio_breaker_rejections_total",
            "requests shed at the door because the dispatch circuit was open",
        )
        self._breaker_instruments = BreakerInstruments(m)
        # per-request latency attribution: every query accounted into the
        # phase waterfall (pio_phase_seconds{phase=...}) with trace-id
        # exemplars — see obs/waterfall.py for the phase boundaries
        self.waterfall = PhaseWaterfall(m)
        # version-keyed result cache (registry/result_cache.py): repeat
        # queries on a quiesced stable lane answer BEFORE batch admission.
        # The pio_cache_* counters mirror the cache's own monotonic stats
        # at scrape time (same set_total pattern as the batcher counters).
        self._result_cache: ResultCache | None = (
            ResultCache(
                self.config.result_cache_size, self.config.result_cache_ttl_s
            )
            if self.config.result_cache_size > 0
            else None
        )
        self._m_cache_hits = m.counter(
            "pio_cache_hits_total",
            "queries answered from the version-keyed result cache "
            "(never entered the micro-batch queue)",
        )
        self._m_cache_misses = m.counter(
            "pio_cache_misses_total",
            "cacheable queries that missed and went through dispatch",
        )
        self._m_cache_evictions = m.counter(
            "pio_cache_evictions_total",
            "result-cache entries dropped by LRU pressure or TTL expiry",
        )
        self._m_cache_invalidations = m.counter(
            "pio_cache_invalidations_total",
            "result-cache entries flushed by model swap/promote/rollback/"
            "stage/reload",
        )
        if self._result_cache is not None:
            m.register_collector(self._collect_cache)
        # declarative SLOs evaluated as multi-window burn rates from the
        # instruments above (obs/slo.py): /slo + pio_slo_* gauges
        self.slo = SLOEngine(m)
        _queries = "/queries.json"
        self.slo.add(
            "latency",
            f"{_queries} answered within "
            f"{self.config.slo_latency_threshold_s * 1000:g} ms",
            self.config.slo_latency_objective,
            histogram_threshold_source(
                self._m_latency,
                self.config.slo_latency_threshold_s,
                endpoint=_queries,
            ),
        )
        self.slo.add(
            "availability",
            f"{_queries} answered without a 5xx",
            self.config.slo_availability_objective,
            counter_ratio_source(
                self._m_requests,
                bad=lambda l: l.get("status", "").startswith("5"),
                match=lambda l: l.get("endpoint") == _queries,
            ),
        )
        self.slo.add(
            "shed",
            f"{_queries} arrivals not rejected by admission control",
            self.config.slo_shed_objective,
            paired_counter_source(
                counter_ratio_source(
                    self._m_requests,
                    bad=lambda l: False,
                    match=lambda l: l.get("endpoint") == _queries,
                ),
                self._m_shed,
            ),
        )
        # the pio_ann_* family (docs/ann.md): registered eagerly so the
        # family exists from process start; lanes loaded from the registry
        # bind their attached AnnServing to it in _warmup_components. The
        # collector reconciles the version-labeled index gauges against
        # the LIVE lanes each scrape — a reload must retire the old
        # version's series, not leave it rendering as pinned forever
        self.ann_instruments = AnnInstruments(m)
        m.register_collector(self._collect_ann_indexes)
        # jit cache misses / XLA compile events become first-class metrics;
        # sampled at scrape time via the registry collector hook
        self.compile_watcher = CompileWatcher(m)
        m.register_collector(self.compile_watcher.sample)
        m.register_collector(self._breaker_instruments.collect)
        m.register_collector(self.slo.collect)
        # registry lease-mutex counters (registry/lease.py): every server
        # that can stage/promote through the shared-storage registry
        # exports its acquire/steal/fencing-loss tallies
        from predictionio_tpu.registry.lease import register_lease_metrics

        register_lease_metrics(m)
        self._runner: web.AppRunner | None = None
        self._stop_event = asyncio.Event()
        # strong refs to fire-and-forget tasks (the loop keeps only weak ones)
        self._bg_tasks: set[asyncio.Task] = set()
        # ONE shared session with a total timeout for all background HTTP
        # (feedback + remote log): per-call bare ClientSessions with no
        # timeout accumulated hung tasks forever against a stalled collector
        self._http_session = None
        # consecutive watchdog trips (device calls blowing their deadline)
        # open this breaker; while open /queries.json sheds instantly with
        # 503 + Retry-After instead of feeding more work to a wedged device
        self.dispatch_breaker = self._breaker_instruments.watch(
            CircuitBreaker(
                name="dispatch",
                failure_threshold=self.config.breaker_threshold,
                recovery_timeout_s=self.config.breaker_recovery_s,
            )
        )
        # candidate-lane breaker: consecutive candidate predict failures
        # force an instant rollback (no bake-window wait) via the chained
        # trip listener; the obs instruments see its transitions too
        self.candidate_breaker = self._breaker_instruments.watch(
            CircuitBreaker(
                name="candidate",
                failure_threshold=self.config.candidate_breaker_threshold,
                recovery_timeout_s=self.config.breaker_recovery_s,
            )
        )
        self.candidate_breaker.chain_listener(self._on_candidate_transition)
        self._rollout_instruments = RolloutInstruments(m)
        self.rollout_controller = RolloutController(
            self._rollout_instruments,
            PromotionCriteria(
                bake_window_s=self.config.bake_window_s,
                min_requests=self.config.bake_min_requests,
                max_error_ratio=self.config.max_error_ratio,
                max_p95_ratio=self.config.max_p95_ratio,
                max_divergence_rate=self.config.max_divergence_rate,
                auto_promote=self.config.auto_promote,
            ),
        )
        # -- bandit exploration lanes (docs/bandit.md): the pio_bandit_*
        # family registers eagerly (exists at zero with no policy, same
        # discipline as AnnInstruments); the loop itself only exists when
        # a policy is configured. It rides the rollout machinery: arms are
        # the stable/candidate lanes, the policy's actuator is the canary
        # fraction, and promote/retire route through the existing
        # transitions — so a losing arm retires with zero client 5xx.
        self.bandit_instruments = BanditInstruments(m)
        self.bandit: BanditLoop | None = (
            BanditLoop(
                self.config.bandit_policy,
                epsilon=self.config.bandit_epsilon,
                criteria=BanditCriteria(
                    min_pulls=float(self.config.bandit_min_pulls),
                    promote_threshold=self.config.bandit_promote_threshold,
                    retire_threshold=self.config.bandit_retire_threshold,
                    min_fraction=self.config.bandit_min_fraction,
                    max_fraction=self.config.bandit_max_fraction,
                ),
                instruments=self.bandit_instruments,
                store=self.registry_store,
                engine_id=self.manifest.engine_id,
                impression_capacity=self.config.bandit_impression_capacity,
                seed=self.config.bandit_seed,
            )
            if self.config.bandit_policy
            else None
        )
        self._reload_lock = asyncio.Lock()
        self._batcher = _MicroBatcher(
            self,
            max_batch=self.config.max_batch_size,
            window_s=self.config.batch_window_ms / 1000.0,
            high_water=self.config.queue_high_water,
            shed_retry_after_s=self.config.shed_retry_after_s,
        )
        # scrape-time gauges mirroring live batcher state (hot path pays 0)
        m.gauge(
            "pio_queue_depth", "queries waiting in the micro-batch queue"
        ).set_function(lambda: self._batcher.queue_depth)
        m.gauge(
            "pio_queue_high_water",
            "admission-control shed threshold (0 = unbounded)",
        ).set(self.config.queue_high_water)
        import concurrent.futures

        self._sniffer_pool = concurrent.futures.ThreadPoolExecutor(
            max_workers=1, thread_name_prefix="pio-sniffer"
        )
        # shadow scoring runs off the serving path entirely: the candidate
        # is scored on this thread, its answer discarded, divergence
        # recorded — a slow or crashing candidate cannot touch a response
        self._shadow_pool = concurrent.futures.ThreadPoolExecutor(
            max_workers=1, thread_name_prefix="pio-shadow"
        )
        # -- profiling plane (docs/observability.md §Profiling plane) -------
        # always-on host stack sampler + single-flight device capture. Both
        # register their pio_profile_* instruments eagerly here so the
        # family exists from process start (the metrics contract test
        # resolves every documented metric against a fresh server).
        self.sampler = HostSampler(
            period_s=self.config.sampler_period_s
            if self.config.sampler_period_s > 0
            else 0.05,
            metrics=m,
        )
        self.profiler = ProfileSession(
            ProfileStore(
                self.config.profile_dir, self.config.profile_max_bundles
            ),
            default_ms=self.config.profile_default_ms,
            max_ms=self.config.profile_max_ms,
            alert_min_interval_s=self.config.profile_alert_min_interval_s,
            alert_trace_ms=self.config.profile_alert_trace_ms,
            context_fn=self._profile_context,
            metrics=m,
        )
        # SLO alert-transition tracker for profile-on-alert (the rollout
        # heartbeat checks it): a long burn must capture ONCE per
        # transition, not once per tick
        self._slo_alerting: dict[str, bool] = {}

    def _profile_context(self) -> dict[str, Any]:
        """Manifest enrichment for every profile bundle: which engine and
        model were serving, at which registry generation — the trace
        viewer can't answer that, the manifest must."""
        generation = None
        if self.registry_store is not None:
            try:
                generation = self.registry_store.state_generation(
                    self.manifest.engine_id
                )
            except Exception:  # noqa: BLE001 - enrichment, not evidence
                generation = None
        return {
            "engine": self.manifest.engine_id,
            "engineVersion": self.manifest.version,
            "modelVersion": self._active.version,
            "instanceId": self.instance_id,
            "registryGeneration": generation,
        }

    def _profile_parts(self) -> dict[str, Any]:
        """Host-side evidence attached to every profile bundle: the phase
        waterfall at capture time and the sampler's folded stacks."""
        return {
            "waterfall": self.waterfall.snapshot(),
            "stacks": self.sampler.snapshot(),
        }

    def _capture_profile(self, ms: int | None, trigger: str) -> str:
        """Blocking capture body (trace sleep + bundle file writes): runs
        on an executor thread, never on the event loop."""
        return self.profiler.capture(
            ms=ms, trigger=trigger, parts=self._profile_parts()
        )

    # ---------------------------------------------------------------- routes
    async def handle_queries(self, request: web.Request) -> web.Response:
        """Trace + metrics envelope around the query path: accept or mint
        the request's trace id (echoed in the response), record the
        ingress span, and count/observe every status — including the
        shed/deadline 503s the resilience layer used to decide silently."""
        trace_id = request.headers.get(TRACE_HEADER) or mint_trace_id()
        token = set_trace_id(trace_id)
        status = 500
        t0 = time.perf_counter()
        # drain accounting: the SIGTERM drain path waits for this count to
        # reach zero before the process exits, so a supervised restart
        # answers everything it already accepted
        self._inflight_requests += 1
        # per-request waterfall channel: the inner handler and the batcher
        # fill it with phase timestamps; the ingress span carries the
        # handler-side phases as tags
        phases: dict[str, float] = {"t_start": t0}
        try:
            with self.tracer.span(
                "http.query", kind="ingress", endpoint="/queries.json"
            ) as sp:
                resp = await self._handle_queries_inner(request, phases)
                status = resp.status
                sp.tags["status"] = status
                if phases.get("t_done") is not None:
                    phases["respond_s"] = time.perf_counter() - phases["t_done"]
                sp.tags.update(
                    phase_tags_ms(
                        ingress_parse=phases.get("parse_s"),
                        respond=phases.get("respond_s"),
                    )
                )
        finally:
            reset_trace_id(token)
            self._inflight_requests -= 1
            # ONE end timestamp anchors both the e2e histogram and the
            # respond phase, so the waterfall tiles the same wall clock the
            # latency histogram reports (the reconciliation contract)
            t_end = time.perf_counter()
            self._m_requests.inc(endpoint="/queries.json", status=str(status))
            self._m_latency.observe(t_end - t0, endpoint="/queries.json")
            t_done = phases.get("t_done")
            if t_done is not None:
                self.waterfall.observe(PHASE_RESPOND, t_end - t_done, trace_id)
        resp.headers[TRACE_HEADER] = trace_id
        return resp

    async def _handle_queries_inner(
        self, request: web.Request, phases: dict[str, float] | None = None
    ) -> web.Response:
        phases = {} if phases is None else phases
        if self.config.accesskey:
            supplied = request.query.get("accessKey") or request.headers.get(
                "Authorization", ""
            ).removeprefix("Bearer ").strip()
            if supplied != self.config.accesskey:
                return web.json_response({"message": "Invalid accessKey."}, status=401)
        t0 = time.perf_counter()
        if (
            self.config.max_payload_bytes
            and request.content_length is not None
            and request.content_length > self.config.max_payload_bytes
        ):
            return web.json_response(
                {
                    "message": (
                        f"query payload too large "
                        f"({request.content_length} > "
                        f"{self.config.max_payload_bytes} bytes)"
                    )
                },
                status=413,
            )
        try:
            payload = await request.json()
        except Exception as exc:
            return web.json_response({"message": str(exc)}, status=400)
        # ingress parse complete (auth + size check + JSON decode) — the
        # first waterfall phase. The same timestamp anchors the cache
        # phase so the two tile exactly.
        t_parse_end = time.perf_counter()
        parse_s = t_parse_end - phases.get("t_start", t0)
        phases["parse_s"] = parse_s
        self.waterfall.observe(PHASE_INGRESS_PARSE, parse_s, current_trace_id())
        # ---- version-keyed result cache, consulted BEFORE admission ----
        # (and before the breaker check: a wedged device must not block
        # answers the cache already holds). A hit's waterfall is
        # parse -> cache -> respond; a miss pays the lookup in the cache
        # phase and carries its canonical key so the batcher can insert
        # the answer under the version that actually served it.
        cache = self._result_cache
        cache_key: bytes | None = None
        t_anchor = t_parse_end
        if cache is not None:
            entry = None
            version = self._cache_lookup_version()
            if version is not None:
                try:
                    cache_key = _canonical_query_bytes(payload)
                except (TypeError, ValueError):
                    cache_key = None
                if cache_key is not None:
                    entry = cache.get(version, cache_key)
            t_cache_end = time.perf_counter()
            cache_s = t_cache_end - t_parse_end
            phases["cache_s"] = cache_s
            self.waterfall.observe(PHASE_CACHE, cache_s, current_trace_id())
            t_anchor = t_cache_end
            if entry is not None:
                self._m_cache_hits.inc()
                phases["t_done"] = t_cache_end
                text = entry.text
                if text is None:
                    # serialize once per entry; every later hit's respond
                    # phase is a prebuilt-string write
                    text = entry.text = _fast_dumps(entry.body)
                elapsed = time.perf_counter() - t0
                self.request_count += 1
                self.last_serving_sec = elapsed
                self.avg_serving_sec += (
                    elapsed - self.avg_serving_sec
                ) / self.request_count
                if self.config.feedback:
                    self._spawn_bg(self._send_feedback(payload, entry.body))
                return web.Response(
                    text=text, content_type="application/json"
                )
            if cache_key is not None:
                self._m_cache_misses.inc()
        try:
            # a wedged device has tripped the dispatch breaker: shed at the
            # door with a Retry-After instead of queueing doomed work
            self.dispatch_breaker.allow()
        except CircuitOpenError as exc:
            self._m_breaker_rejected.inc()
            return self._unavailable(
                "serving temporarily unavailable (dispatch circuit open)",
                exc.retry_after_s,
            )
        deadline = Deadline.after(self.config.request_timeout_s)
        try:
            # the batcher runs decode -> supplement -> predict_batch -> serve
            # on its worker thread, so the event loop never blocks on device
            # or storage work and concurrent requests coalesce into one
            # batched device call; the deadline rides along and bounds every
            # stage (queue wait, dispatch, result fetch — the breaker
            # admission above is accounted into queue_wait via the anchor)
            body = await self._batcher.submit(
                payload,
                deadline,
                phases=phases,
                t_submit=t_anchor,
                cache_key=cache_key,
            )
        except LoadShedError as exc:
            # this request died before any dispatch could record against the
            # breaker: free its half-open probe slot (no-op when closed/open)
            # or an unresolved probe would wedge the circuit half-open
            self.dispatch_breaker.release_probe()
            return self._unavailable(str(exc), exc.retry_after_s)
        except DeadlineExceeded as exc:
            self.dispatch_breaker.release_probe()
            self._m_deadline.inc()
            logger.warning("query deadline exceeded: %s", exc)
            return self._unavailable(str(exc), self.config.shed_retry_after_s)
        except ShuttingDownError as exc:
            self.dispatch_breaker.release_probe()
            return self._unavailable(str(exc), self.config.shed_retry_after_s)
        except Exception as exc:
            logger.exception("query failed")
            if self.config.log_url:
                import traceback

                tb = "".join(traceback.format_exception(exc))
                msg = f"Query:\n{payload}\n\nStack Trace:\n{tb}\n\n"
                self._spawn_bg(self._remote_log(msg))
            return web.json_response({"message": str(exc)}, status=400)
        elapsed = time.perf_counter() - t0
        self.request_count += 1
        self.last_serving_sec = elapsed
        self.avg_serving_sec += (elapsed - self.avg_serving_sec) / self.request_count
        if self.config.feedback:
            self._spawn_bg(self._send_feedback(payload, body))
        # the respond phase (results distributed -> future resumed ->
        # response serialized) is observed by the envelope in
        # handle_queries, anchored on the same end timestamp as the e2e
        # latency histogram; the precompiled compact encoder keeps it off
        # the sort_keys canonical path
        return web.json_response(body, dumps=_fast_dumps)

    def _dispatch_query_batch(self, items: list[_QItem], batch_no: int = 0):
        """One micro-batch's dispatch phase as a ``pio:dispatch`` span;
        ``batch_no`` is the batcher's running number of the batch."""
        with annotate("pio:dispatch", batch=batch_no, n=len(items)):
            return self._route_and_dispatch(items)

    def _route_and_dispatch(self, items: list[_QItem]):
        """Dispatch-phase of one micro-batch (runs on the dispatch thread):
        decode and supplement each query, then *dispatch* every algorithm's
        device work via ``predict_batch_dispatch`` without blocking on
        results. Returns a finalize callable (run on a fetch thread) that
        blocks on the device, calls its ``device_answered`` argument (the
        batcher's slot going back), then serves and encodes — so the
        dispatcher can start batch n+1 while batch n's results are in flight
        and batch n+2 while they are being served.

        ``items`` is the batcher's queued-item list itself (payload +
        ingress trace id read in place — zero per-batch re-packing); the
        trace id is re-installed around the per-query stages
        (decode/supplement here, serve in finalize) so spans those stages
        record — a serving component fetching user features from storage,
        say — join the request's trace across the thread hop.

        Rollout routing happens here: ONE read each of ``_active`` /
        ``_candidate`` / ``_plan`` means an in-flight batch is immune to
        /reload, promote, and rollback and always sees a consistent
        (algorithms, serving, models, version) quadruple per lane. During
        a canary, each query's sticky key routes it to stable or candidate
        *before* supplement (the lanes own separate serving components);
        candidate-lane failures never surface to users — they feed the
        candidate breaker (whose trip forces instant rollback) and the
        query is re-answered on the stable lane.

        Per-query failures are isolated: the failing slot gets its
        exception, batch mates answer normally. Finalize returns one
        ``(encoded result body or exception, model version)`` pair per
        payload; the version rides into the per-query batch span."""
        stable: Lane = self._active
        cand: Lane | None = self._candidate
        plan = self._plan
        gen = self._rollout_gen
        canary = (
            cand is not None and plan.mode == MODE_CANARY and plan.fraction > 0
        )
        shadow = cand is not None and plan.mode == MODE_SHADOW
        # bandit accounting is snapshotted with the lanes: answered queries
        # of THIS batch are pulls of THIS rollout's arms (the version check
        # inside record_impression drops any race with promote/rollback)
        bandit = (
            self.bandit
            if canary and self.bandit is not None and self.bandit.active
            else None
        )
        payloads = [it.payload for it in items]
        trace_ids = [it.trace_id for it in items]
        n = len(payloads)
        outs: list[Any] = [None] * n
        versions: list[str] = [stable.version] * n
        queries: list[Any] = [None] * n
        supplemented: list[Any] = [None] * n
        stable_idx: list[int] = []
        cand_idx: list[int] = []
        inst = self._rollout_instruments
        with annotate("pio:dispatch.decode"):
            for i, payload in enumerate(payloads):
                token = set_trace_id(trace_ids[i])
                try:
                    try:
                        q = self.engine.decode_query(payload)
                        queries[i] = q
                    except Exception as exc:
                        # client error (bad payload) — no lane touched it, so
                        # no per-version accounting
                        outs[i] = exc
                        continue
                    lane = stable
                    if canary and (
                        choose_lane(
                            plan,
                            routing_key(payload, self.config.sticky_key_field),
                        )
                        == LANE_CANDIDATE
                    ):
                        # a failing candidate supplement degrades this query to
                        # the stable answer, not to an error; the failure is
                        # paired with a request so the error-RATE gate compares
                        # like with like
                        try:
                            supplemented[i] = cand.serving.supplement(q)
                            lane = cand
                        except Exception:
                            logger.exception("candidate supplement failed")
                            if gen == self._rollout_gen:
                                inst.requests.inc(
                                    version=cand.version, lane=LANE_CANDIDATE
                                )
                            self._record_candidate_failure(cand.version, gen)
                    if lane is stable:
                        try:
                            supplemented[i] = stable.serving.supplement(q)
                        except Exception as exc:
                            # symmetric accounting: a stable supplement failure
                            # is a stable-lane error, not silence — otherwise a
                            # flaky shared dependency reads as candidate-only
                            # and rolls back a candidate no worse than stable
                            inst.requests.inc(
                                version=stable.version, lane=LANE_STABLE
                            )
                            inst.errors.inc(
                                version=stable.version, lane=LANE_STABLE
                            )
                            outs[i] = exc
                            continue
                        stable_idx.append(i)
                    else:
                        versions[i] = cand.version
                        cand_idx.append(i)
                finally:
                    reset_trace_id(token)
        dispatched: list[tuple[Lane, str, list[int], list[Any], list[Any]]] = []
        for lane, lane_name, idxs in (
            (stable, LANE_STABLE, stable_idx),
            (cand, LANE_CANDIDATE, cand_idx),
        ):
            if lane is None or not idxs:
                continue
            sup = [supplemented[i] for i in idxs]
            finalizers: list[Any] = []
            for algo, model in zip(lane.algorithms, lane.models):
                fin = None
                try:
                    # staging, upload and the kernel's launch
                    with annotate("pio:dispatch.enqueue", n=len(sup)):
                        fin = algo.predict_batch_dispatch(model, sup)
                except Exception:
                    logger.exception(
                        "predict_batch_dispatch failed; deferring to fetch"
                    )
                finalizers.append(fin)
            dispatched.append((lane, lane_name, idxs, sup, finalizers))

        # finalize publishes its measured sub-phases here: the fetch-thread
        # wall decomposes into device compute (blocked on device results),
        # serve (per-query serve + encode), and a transport/hop residual
        # the batcher derives (see _finish)
        timings: dict[str, float] = {"device_s": 0.0, "serve_s": 0.0}

        def finalize(device_answered=None) -> list[tuple[Any, str]]:
            sniffed: list[tuple[Any, Any]] = []
            inst = self._rollout_instruments
            fetched: list[list[list[Any]]] = []
            for lane, _, _, sup, finalizers in dispatched:
                t0 = time.perf_counter()
                fetched.append(self._lane_predictions(lane, sup, finalizers))
                lane_predict_s = time.perf_counter() - t0
                timings["device_s"] += lane_predict_s
                inst.predict_seconds.observe(
                    lane_predict_s, version=lane.version
                )
            # every lane's packed results are on the host: the device has
            # answered this batch, and the batcher's slot goes back before
            # the per-query serve and encode below
            if device_answered is not None:
                device_answered()
            for (lane, lane_name, idxs, _, _), preds_per_algo in zip(
                dispatched, fetched
            ):
                with annotate("pio:serve"):
                    for row, i in enumerate(idxs):
                        token = set_trace_id(trace_ids[i])
                        t_serve = time.perf_counter()
                        # candidate accounting is generation-scoped end to end:
                        # a stale batch must not add errorless requests to the
                        # denominator of the NEW candidate's error-rate gate
                        # (its errors are already dropped by the gen guard)
                        if lane_name != LANE_CANDIDATE or gen == self._rollout_gen:
                            inst.requests.inc(version=lane.version, lane=lane_name)
                        try:
                            outs[i] = self._serve_one(
                                lane,
                                queries[i],
                                [preds[row] for preds in preds_per_algo],
                                sniffed,
                            )
                            if lane_name == LANE_CANDIDATE and gen == self._rollout_gen:
                                # same generation guard as the failure paths: a
                                # stale batch's successes must not reset the
                                # consecutive-failure count a failing successor
                                # candidate is accumulating
                                self.candidate_breaker.record_success()
                            if bandit is not None:
                                # an answered query is a pull the moment it is
                                # served; the trace id becomes matchable for
                                # feedback credit
                                bandit.record_impression(
                                    trace_ids[i],
                                    ARM_CANDIDATE
                                    if lane_name == LANE_CANDIDATE
                                    else ARM_STABLE,
                                    lane.version,
                                )
                        except Exception as exc:
                            if lane_name == LANE_CANDIDATE:
                                self._record_candidate_failure(lane.version, gen)
                                outs[i], versions[i] = self._stable_retry(
                                    stable, queries[i], sniffed
                                )
                                if bandit is not None and not isinstance(
                                    outs[i], BaseException
                                ):
                                    # re-answered on stable: that's a stable pull
                                    bandit.record_impression(
                                        trace_ids[i], ARM_STABLE, stable.version
                                    )
                            else:
                                inst.errors.inc(
                                    version=lane.version, lane=lane_name
                                )
                                outs[i] = exc
                        finally:
                            timings["serve_s"] += time.perf_counter() - t_serve
                            reset_trace_id(token)
            if shadow:
                pairs = [
                    (queries[i], outs[i])
                    for i in stable_idx
                    if not isinstance(outs[i], BaseException)
                ]
                if pairs:
                    self._submit_shadow(cand, pairs, gen)
            if sniffed and self.plugin_context.output_sniffers:
                # observers are fire-and-forget on their own thread: a slow
                # or throwing sniffer must neither delay the batch's
                # responses nor overwrite a successful result
                self._sniffer_pool.submit(self._notify_sniffers, sniffed)
            return list(zip(outs, versions))

        finalize.timings = timings
        return finalize

    def _lane_predictions(
        self, lane: Lane, sup: list[Any], finalizers: list[Any]
    ) -> list[list[Any]]:
        """One lane's per-algorithm predictions with the batch -> per-query
        fallback: one poisonous query can't fail its batch mates."""
        preds_per_algo: list[list[Any]] = []
        for fin, (algo, model) in zip(
            finalizers, zip(lane.algorithms, lane.models)
        ):
            try:
                if fin is not None:
                    preds = list(fin())
                else:
                    preds = list(algo.predict_batch(model, sup))
                if len(preds) != len(sup):
                    raise RuntimeError(
                        f"predict_batch returned {len(preds)} results "
                        f"for {len(sup)} queries"
                    )
            except Exception:
                logger.exception(
                    "batched predict failed; falling back to per-query"
                )
                preds = []
                for s in sup:
                    try:
                        preds.append(algo.predict(model, s))
                    except Exception as exc:
                        logger.exception("query predict failed")
                        preds.append(exc)
            preds_per_algo.append(preds)
        return preds_per_algo

    def _serve_one(
        self, lane: Lane, query: Any, plist: list[Any], sniffed: list
    ) -> Any:
        """serve + output-blockers + encode for one query on one lane;
        raises the first per-query prediction failure."""
        for p in plist:
            if isinstance(p, BaseException):
                raise p
        result = lane.serving.serve(query, plist)
        result = self.plugin_context.apply_output_blockers(
            self.manifest.variant, query, result
        )
        sniffed.append((query, result))
        return Engine.encode_result(result)

    def _record_candidate_failure(self, version: str, gen: int | None = None) -> None:
        """Count one candidate failure against the breaker — unless the
        caller's rollout generation is stale (the work belongs to an
        already promoted/rolled-back candidate and must not trip the
        breaker of the current one)."""
        if gen is not None and gen != self._rollout_gen:
            return
        self._rollout_instruments.errors.inc(
            version=version, lane=LANE_CANDIDATE
        )
        self.candidate_breaker.record_failure()

    def _stable_retry(
        self, stable: Lane, query: Any, sniffed: list
    ) -> tuple[Any, str]:
        """Re-answer a candidate-lane query on the stable lane (single
        query path) so canary traffic never surfaces candidate errors."""
        inst = self._rollout_instruments
        inst.requests.inc(version=stable.version, lane=LANE_STABLE)
        try:
            s = stable.serving.supplement(query)
            plist = [
                algo.predict(model, s)
                for algo, model in zip(stable.algorithms, stable.models)
            ]
            return self._serve_one(stable, query, plist, sniffed), stable.version
        except Exception as exc:
            logger.exception("stable retry after candidate failure failed")
            inst.errors.inc(version=stable.version, lane=LANE_STABLE)
            return exc, stable.version

    def _submit_shadow(
        self, cand: Lane, pairs: list[tuple[Any, Any]], gen: int
    ) -> None:
        """Queue one batch for shadow scoring, bounded: a candidate slower
        than live traffic drops samples (counted) instead of growing the
        single-worker queue — and the memory it pins — without limit."""
        with self._shadow_lock:
            if self._shadow_pending >= self.config.shadow_max_backlog:
                self._rollout_instruments.shadow_dropped.inc(
                    len(pairs), version=cand.version
                )
                return
            self._shadow_pending += 1
        self._shadow_pool.submit(self._shadow_score, cand, pairs, gen)

    def _shadow_score(
        self, cand: Lane, pairs: list[tuple[Any, Any]], gen: int
    ) -> None:
        """Score the candidate on already-answered stable traffic (runs on
        the shadow thread, fully off the serving path): the candidate's
        answer is discarded, only the divergence/error record remains. A
        crashing candidate trips its breaker from here exactly as it would
        from the canary lane. Work queued for a rollout that has since
        ended (generation mismatch) is skipped wholesale — it must not
        feed the next candidate's breaker or counters."""
        inst = self._rollout_instruments
        discard: list = []
        try:
            for query, stable_body in pairs:
                if gen != self._rollout_gen:
                    return
                try:
                    t0 = time.perf_counter()
                    s = cand.serving.supplement(query)
                    plist = [
                        algo.predict(model, s)
                        for algo, model in zip(cand.algorithms, cand.models)
                    ]
                    body = self._serve_one(cand, query, plist, discard)
                    scored_s = time.perf_counter() - t0
                    if gen != self._rollout_gen:
                        return  # rollout ended while this query was scoring
                    # the latency gate needs candidate samples in shadow
                    # mode too, or a 10x-slower candidate would sail
                    # through on error/divergence alone (per-query single
                    # path here vs the canary's batched path — a rough but
                    # usable comparison basis)
                    inst.predict_seconds.observe(scored_s, version=cand.version)
                    inst.shadow_scored.inc(version=cand.version)
                    if _canonical_json(body) != _canonical_json(stable_body):
                        inst.divergence.inc(version=cand.version)
                    self.candidate_breaker.record_success()
                except Exception:
                    logger.exception("shadow scoring failed")
                    if gen != self._rollout_gen:
                        return
                    inst.shadow_scored.inc(version=cand.version)
                    inst.errors.inc(version=cand.version, lane=LANE_SHADOW)
                    self.candidate_breaker.record_failure()
        finally:
            with self._shadow_lock:
                self._shadow_pending -= 1

    def _notify_sniffers(self, sniffed: list) -> None:
        for query, result in sniffed:
            try:
                self.plugin_context.notify_output_sniffers(
                    self.manifest.variant, query, result
                )
            except Exception:
                logger.exception("output sniffer failed")

    @staticmethod
    def _unavailable(message: str, retry_after_s: float) -> web.Response:
        """503 with a Retry-After hint — the contract load balancers and
        well-behaved clients need to back off instead of hammering."""
        return web.json_response(
            {"message": message},
            status=503,
            headers={"Retry-After": str(max(1, round(retry_after_s)))},
        )

    def _spawn_bg(self, coro) -> None:
        task = asyncio.ensure_future(coro)
        self._bg_tasks.add(task)
        task.add_done_callback(self._bg_tasks.discard)

    def _http(self):
        """The shared background-HTTP session, created lazily on the running
        loop with a total timeout (config.http_timeout_s) and closed by
        ``stop()``: a stalled collector now costs one bounded task, not an
        ever-growing pile of hung ones."""
        import aiohttp

        if self._http_session is None or self._http_session.closed:
            self._http_session = aiohttp.ClientSession(
                timeout=aiohttp.ClientTimeout(total=self.config.http_timeout_s)
            )
        return self._http_session

    async def _remote_log(self, message: str) -> None:
        """Ship a serving error to the remote collector: POST body is
        ``log_prefix`` + JSON of {engineInstance, message}
        (ref ``CreateServer.remoteLog``, CreateServer.scala:423-434)."""
        body = self.config.log_prefix + json.dumps(
            {"engineInstance": self.instance_id, "message": message}
        )
        try:
            async with self._http().post(self.config.log_url, data=body):
                pass  # response body unused; context exit releases the conn
        except Exception:
            logger.error("Unable to send remote log")

    async def _send_feedback(self, query: Any, prediction: Any) -> None:
        """POST a `predict` event back to the event server
        (ref CreateServer.scala:500-570)."""
        url = self.config.event_server_url
        key = self.config.feedback_access_key
        if not url or not key:
            return
        event = {
            "event": "predict",
            "entityType": "pio_pr",
            "entityId": self.manifest.engine_id,
            "properties": {"query": query, "prediction": prediction},
        }
        try:
            async with self._http().post(
                f"{url}/events.json", params={"accessKey": key}, json=event
            ):
                pass
        except Exception:
            logger.exception("feedback POST failed")

    async def handle_status(self, request: web.Request) -> web.Response:
        return web.json_response(
            {
                "status": "alive",
                "engineId": self.manifest.engine_id,
                "engineVersion": self.manifest.version,
                "engineVariant": self.manifest.variant,
                "engineFactory": self.manifest.engine_factory,
                "engineInstanceId": self.instance_id,
                "modelVersion": self._active.version,
                "device": self.serving_devices,
                "rollout": {
                    "mode": self._plan.mode,
                    "fraction": self._plan.fraction,
                    "candidate": (
                        self._candidate.version
                        if self._candidate is not None
                        else None
                    ),
                },
                "bandit": (
                    self.bandit.snapshot() if self.bandit is not None else None
                ),
                "startTime": self.start_time.isoformat(),
                "requestCount": self.request_count,
                "avgServingSec": self.avg_serving_sec,
                "lastServingSec": self.last_serving_sec,
                "resultCache": (
                    self._result_cache.stats()
                    if self._result_cache is not None
                    else None
                ),
                "latency": self._latency_summary_ms(),
                "batching": {
                    "batches": self._batcher.batches_dispatched,
                    "queries": self._batcher.queries_dispatched,
                    "avgBatchSize": (
                        self._batcher.queries_dispatched
                        / max(1, self._batcher.batches_dispatched)
                    ),
                },
                "resilience": self._resilience_snapshot(),
            }
        )

    def _latency_summary_ms(self) -> dict[str, Any]:
        """Legacy status-page latency block, derived from the SAME obs
        histogram /metrics exports (one source of truth; keys kept from
        the pre-registry LatencyHistogram). Counts every /queries.json
        answer including resilience 503s — the distribution an operator
        staring at `/` should see under load."""
        s = self._m_latency.summary(endpoint="/queries.json")
        if s["count"] == 0:
            return {"count": 0}
        return {
            "count": s["count"],
            "mean_ms": 1000.0 * s["mean"],
            "p50_ms": 1000.0 * s["p50"],
            "p95_ms": 1000.0 * s["p95"],
            "p99_ms": 1000.0 * s["p99"],
            "max_ms": 1000.0 * s["max"],
        }

    def _resilience_snapshot(self) -> dict[str, Any]:
        b = self._batcher
        return {
            "queueDepth": b.queue_depth,
            "queueHighWater": b.high_water,
            "watchdogTrips": b.watchdog_trips,
            "loadShedCount": b.shed_count,
            "breakers": {"dispatch": self.dispatch_breaker.snapshot()},
        }

    async def handle_healthz(self, request: web.Request) -> web.Response:
        """Readiness (distinct from the `/` liveness/status page): a load
        balancer drains this replica while the dispatch circuit is open or
        the admission queue is at high water, instead of sending traffic
        that would be shed."""
        snap = self._resilience_snapshot()
        shedding = (
            snap["queueHighWater"] > 0
            and snap["queueDepth"] >= snap["queueHighWater"]
        )
        ready = (
            not self._draining
            and not self._batcher._closed
            and not shedding
            and snap["breakers"]["dispatch"]["state"] != OPEN
        )
        return web.json_response(
            {"ready": ready, "draining": self._draining, **snap},
            status=200 if ready else 503,
        )

    async def handle_reload_get(self, request: web.Request) -> web.Response:
        """Deprecated GET spelling of /reload, kept for compat with old
        deploy scripts: a state-mutating GET is cacheable/prefetchable by
        intermediaries, which is how surprise reloads happen. Docs and
        tools all use POST."""
        logger.warning(
            "GET /reload is deprecated (state-mutating GET); use POST /reload"
        )
        return await self.handle_reload(request)

    async def handle_reload(self, request: web.Request) -> web.Response:
        """Swap in the latest COMPLETED instance (ref MasterActor reload).

        Serialized: two concurrent /reloads used to interleave their
        ``engine_params`` / ``_active`` / ``instance_id`` assignments and
        could leave the server announcing instance A while serving B's
        models. Under the lock, everything is loaded and warmed first and
        the three fields commit together only after that succeeds."""
        async with self._reload_lock:
            loop = asyncio.get_running_loop()
            latest = await loop.run_in_executor(
                None,
                lambda: self.storage.get_meta_data_engine_instances()
                .get_latest_completed(
                    self.manifest.engine_id,
                    self.manifest.version,
                    self.manifest.variant,
                ),
            )
            if latest is None:
                return web.json_response(
                    {"message": "no completed engine instance found"}, status=404
                )
            try:
                engine_params = self._engine_params_of(latest)
                models = await loop.run_in_executor(
                    None,
                    lambda: load_models_for_instance(
                        self.engine, engine_params, latest.id, storage=self.storage
                    ),
                )
                _, _, algorithms, serving = self.engine.make_components(
                    engine_params
                )
                # warm the NEW components before they take traffic; a
                # warmup failure fails this reload and the old lane stays
                await loop.run_in_executor(
                    None, self._warmup_components, algorithms, models
                )
                # blocking registry manifest scan stays off the event loop
                new_version = await loop.run_in_executor(
                    None, self._version_for_instance, latest.id
                )
            except Exception as exc:
                logger.exception("reload failed")
                return web.json_response({"message": str(exc)}, status=500)
            # commit: one consistent swap, nothing mutated on any failure path
            self.engine_params = engine_params
            with self._rollout_mutex:
                retired = self._active.version
                self._active = Lane(  # atomic swap
                    algorithms,
                    serving,
                    models,
                    new_version,
                    latest.id,
                    engine_params,
                )
                self.instance_id = latest.id
                cand = self._candidate
                if cand is not None:
                    # an active bake was comparing against the version that
                    # just got replaced: rebase the baseline on the new
                    # stable so the gates judge the candidate against what
                    # actually serves (the retired version's counters would
                    # freeze and collapse the error-rate allowance)
                    self.rollout_controller.begin(
                        new_version, cand.version, self._plan.mode
                    )
            # the registry-swap invalidation hook: the version that just
            # stopped serving must not answer another query from cache
            self._cache_flush(retired, f"reload -> {new_version}")
        logger.info("reloaded engine instance %s", latest.id)
        return web.json_response(
            {"message": "Reload successful", "instanceId": latest.id}
        )

    def _engine_params_of(self, instance: EngineInstance) -> EngineParams:
        return _engine_params_of_instance(self.engine, instance)

    # ------------------------------------------------- result cache plumbing
    def _cache_lookup_version(self) -> str | None:
        """The version whose cache lane may answer right now: the stable
        version when no rollout is active, None (= bypass) while one is.
        Canary users must exercise the candidate for the bake gates to
        mean anything, shadow mode needs dispatched stable answers to
        sample — and because candidate answers are never cached, a canary
        answer can never be served from a stale lane."""
        if self._candidate is not None or self._plan is not PLAN_OFF:
            return None
        return self._active.version

    def _cache_store(self, version: str, key: bytes, body: Any) -> None:
        """Insert one answered body (called by the batcher's finish path).
        Guarded at store time: only the CURRENT stable version's answers
        are cacheable — a swap or stage between dispatch and store
        orphans the write instead of caching across the boundary."""
        cache = self._result_cache
        if cache is None:
            return
        if self._candidate is not None or version != self._active.version:
            return
        cache.put(version, key, body)

    def _cache_flush(self, version: str | None, why: str) -> None:
        """Invalidate the affected lane's entries on a rollout transition
        (stage/promote/rollback) or reload. ``version=None`` clears all."""
        cache = self._result_cache
        if cache is None:
            return
        n = cache.clear() if version is None else cache.flush_version(version)
        if n:
            logger.info("result cache: flushed %d entries (%s)", n, why)

    def _collect_buckets(self) -> None:
        """Registry collector: mirror ops/topk's bucket tallies and the bytes
        of its resident tables (a process that never imported it has launched
        no bucket and holds no table: explicit zeros)."""
        topk = sys.modules.get("predictionio_tpu.ops.topk")
        real, bucket, batches = topk.bucket_counts() if topk else (0, 0, {})
        self._m_serve_rows.set_total(real, kind="real")
        self._m_serve_rows.set_total(bucket, kind="bucket")
        for size, count in batches.items():
            self._m_serve_batches.set_total(count, bucket=str(size))
        tables = topk.table_bytes() if topk else {"item": 0, "user": 0}
        for table, nbytes in tables.items():
            self._m_serve_table_bytes.set(nbytes, table=table)

    def _collect_cache(self) -> None:
        """Scrape-time mirror of the cache's monotonic stats into the
        pio_cache_* counters (hits are also inc'd inline on the hot path;
        set_total clamps monotonic so the two sources can't fight)."""
        stats = self._result_cache.stats()
        self._m_cache_hits.set_total(stats["hits"])
        self._m_cache_misses.set_total(stats["misses"])
        self._m_cache_evictions.set_total(stats["evictions"])
        self._m_cache_invalidations.set_total(stats["invalidations"])

    @property
    def cache_hit_ratio(self) -> float:
        cache = self._result_cache
        return cache.hit_ratio if cache is not None else 0.0

    # ------------------------------------------------- progressive rollout
    def _version_for_instance(self, instance_id: str) -> str:
        """Registry version whose lineage points at this engine instance;
        the instance id itself when the registry doesn't know it."""
        if self.registry_store is not None:
            for m in self.registry_store.list_versions(self.manifest.engine_id):
                if m.instance_id == instance_id:
                    return m.version
        return instance_id

    def _on_candidate_transition(self, name: str, old: str, new: str) -> None:
        """Candidate breaker trip = the fast rollback path: no bake-window
        wait, the candidate lane is gone before the next batch forms.
        Fires on a dispatch/shadow thread; the rollback swap is mutex-
        guarded and pure attribute writes, so that is safe."""
        if new == OPEN:
            self._rollback_candidate("breaker-trip")
            # profile-on-alert: attach the host stacks (and optionally a
            # short device trace) that show WHAT the serving threads were
            # doing when the candidate died — off the dispatch thread, the
            # rollback must not wait for bundle file writes
            self._profile_on_alert(
                "breaker-trip", {"breaker": name, "from": old, "to": new}
            )

    def _profile_on_alert(self, trigger: str, context: dict[str, Any]) -> None:
        """Rate-limited background profile capture for alert paths; never
        blocks or raises into the caller (the alert path is already a
        failure path)."""
        if not self.config.profile_on_alert:
            return
        parts = self._profile_parts()
        texts = {"stacks_folded": self.sampler.folded()}
        threading.Thread(
            target=self.profiler.capture_alert,
            args=(trigger,),
            kwargs={"context": context, "parts": parts, "texts": texts},
            name="pio-profile-alert",
            daemon=True,
        ).start()

    def _check_slo_alerts(self) -> None:
        """SLO alert *transitions* capture a profile bundle (level
        triggers would re-fire every heartbeat of a long burn; the
        per-kind rate limiter bounds it anyway, but the transition is the
        incident). Rides the rollout heartbeat."""
        try:
            reports = self.slo.evaluate()
        except Exception:  # noqa: BLE001 - a broken SLO eval must not loop-kill
            return
        for rpt in reports:
            slo_name = rpt.get("name", "?")
            was = self._slo_alerting.get(slo_name, False)
            now_alerting = bool(rpt.get("alerting"))
            self._slo_alerting[slo_name] = now_alerting
            if now_alerting and not was:
                self._profile_on_alert(
                    "slo-alert",
                    {
                        "slo": slo_name,
                        "objective": rpt.get("objective"),
                        "compliance": rpt.get("compliance"),
                    },
                )

    def _bandit_tailer(self) -> RewardTailer:
        """Build the reward tail for one rollout: feedback events of the
        configured app, matched to served impressions by the trace id
        echoed into their properties. The cursor seeds at the current
        sequence head — historical events never retro-credit an arm."""
        from predictionio_tpu.data.store.event_store import resolve_app

        app_name = self.config.bandit_app_name
        if not app_name:
            raise ValueError(
                "bandit policy configured without bandit_app_name (the app "
                "whose events carry rewards)"
            )
        app_id, channel_id = resolve_app(
            self.storage, app_name, self.config.bandit_channel_name
        )
        return RewardTailer(
            self.storage.get_l_events(),
            app_id,
            channel_id,
            event_names=tuple(self.config.bandit_reward_events),
            trace_property=self.config.bandit_trace_property,
            reward_property=self.config.bandit_reward_property,
        )

    def _bandit_apply_fraction(self, fraction: float) -> None:
        """Move the live canary fraction to the policy's choice. The salt
        (candidate version) is untouched, so the sticky buckets stay
        fleet-consistent and a fraction change only flips the users whose
        bucket the boundary crossed."""
        with self._rollout_mutex:
            plan = self._plan
            if self._candidate is None or plan.mode != MODE_CANARY:
                return
            if abs(plan.fraction - fraction) < 1e-9:
                return
            self._plan = RolloutPlan(MODE_CANARY, fraction, plan.salt)
            plan = self._plan
        self._rollout_instruments.set_plan(plan)

    def stage_candidate_lane(
        self,
        lane: Lane,
        mode: str = MODE_CANARY,
        fraction: float = 0.1,
        persist: bool = True,
    ) -> None:
        """Install a candidate lane and begin the bake. The sticky salt is
        the candidate version, so every replica in a fleet canaries the
        same user population and a later rollout resamples a fresh one."""
        if mode not in (MODE_CANARY, MODE_SHADOW):
            raise ValueError(f"rollout mode must be canary|shadow, got {mode!r}")
        if lane.version == self._active.version:
            # canarying stable against itself would also desync server and
            # registry state (the store rejects it, and that rejection must
            # not be swallowed as bookkeeping noise)
            raise ValueError(f"{lane.version} is already the stable version")
        fraction = max(0.0, min(1.0, float(fraction)))
        with self._rollout_mutex:
            self._rollout_gen += 1  # orphan any in-flight work of the old bake
            self.candidate_breaker.reset()
            self._candidate = lane
            self._plan = RolloutPlan(
                mode, fraction if mode == MODE_CANARY else 0.0, lane.version
            )
            stable_version = self._active.version
            self.rollout_controller.begin(stable_version, lane.version, mode)
        if self.bandit is not None and mode == MODE_CANARY:
            # engage the two-arm bandit on this rollout; a persisted
            # posterior for the same version pair resumes. Failure (no
            # reward app resolvable, storage down) degrades to the plain
            # bake gate — never blocks the stage itself.
            try:
                self.bandit.begin(
                    stable_version, lane.version, self._bandit_tailer()
                )
            except Exception:
                logger.exception(
                    "bandit engage failed; plain bake gate governs this "
                    "rollout"
                )
        # a RE-staged candidate must not inherit entries from any earlier
        # life of its version (e.g. a prior bake followed by rollback);
        # lookups are bypassed for the whole bake anyway — this flush
        # guarantees the lane starts empty
        self._cache_flush(lane.version, f"stage {lane.version}")
        self._rollout_instruments.set_plan(self._plan)
        if persist and self.registry_store is not None:
            try:
                self.registry_store.stage_candidate(
                    self.manifest.engine_id,
                    lane.version,
                    mode=mode,
                    fraction=fraction,
                )
            except Exception:
                logger.exception("registry stage bookkeeping failed")
        logger.info(
            "staged candidate %s (%s, fraction %.3f)", lane.version, mode, fraction
        )

    def _promote_candidate(self, persist: bool = True) -> str | None:
        """Candidate becomes stable (atomic Lane swap). Returns the
        promoted version, or None when no candidate is staged.
        ``persist=False`` skips the registry write — the fleet-sync path
        uses it when the registry ALREADY records the promote (another
        replica's bake gate or the CLI did it first)."""
        with self._rollout_mutex:
            cand = self._candidate
            if cand is None:
                return None
            self._rollout_gen += 1
            retired = self._active.version
            self._active = cand
            if cand.instance_id:
                self.instance_id = cand.instance_id
            if cand.engine_params is not None:
                self.engine_params = cand.engine_params
            self._candidate = None
            self._plan = PLAN_OFF
            self.rollout_controller.end()
        # the retired stable's lane is the affected one: its entries stop
        # being addressable (lookups key on the NEW stable) — flush them
        # so nothing lingers in memory either
        self._cache_flush(retired, f"promote {cand.version}")
        self._rollout_instruments.set_plan(PLAN_OFF)
        self._rollout_instruments.promotions.inc()
        if self.bandit is not None and self.bandit.active:
            self.bandit.end("promote")
        if persist and self.registry_store is not None:
            try:
                self.registry_store.promote(self.manifest.engine_id, cand.version)
            except Exception:
                logger.exception("registry promote bookkeeping failed")
        logger.info("promoted candidate %s to stable", cand.version)
        return cand.version

    def _rollback_candidate(
        self, reason: str, detail: str = "", persist: bool = True
    ) -> str | None:
        """Drop the candidate lane; stable keeps serving untouched.
        ``reason`` is a short label (breaker-trip/manual/error-rate/
        latency/divergence/fleet-sync — bounded metric cardinality),
        ``detail`` the human sentence for logs and registry history.
        ``persist=False``: registry already reflects the rollback (the
        fleet-sync path reacting to another process's unstage)."""
        with self._rollout_mutex:
            cand = self._candidate
            if cand is None:
                return None
            self._rollout_gen += 1
            self._candidate = None
            self._plan = PLAN_OFF
            self.rollout_controller.end()
        # the candidate lane is the affected one (stable entries stay
        # valid — stable never changed); candidate answers are never
        # cached, so this is belt-and-braces against any future path that
        # would put them there
        self._cache_flush(cand.version, f"rollback {cand.version} ({reason})")
        self._rollout_instruments.set_plan(PLAN_OFF)
        self._rollout_instruments.rollbacks.inc(reason=reason)
        if self.bandit is not None and self.bandit.active:
            self.bandit.end(
                "retire" if reason == "bandit-retire" else "rollback"
            )
        if persist and self.registry_store is not None:
            try:
                # unstage, never rollback: the store's rollback falls back
                # to reverting the stable pin when no candidate is recorded
                # (e.g. the stage write was swallowed), which would desync
                # the registry from what this server actually serves
                self.registry_store.unstage(
                    self.manifest.engine_id,
                    reason=(f"{reason}: {detail}" if detail else reason),
                )
            except Exception:
                logger.exception("registry rollback bookkeeping failed")
        logger.warning(
            "candidate %s rolled back (%s) %s", cand.version, reason, detail
        )
        return cand.version

    def _load_lane_from_registry(self, version: str) -> Lane:
        """Registry artifact -> servable Lane: verified blob, deserialize,
        prepare_deploy, fresh components, warmup. Blocking — run in an
        executor. Engine params come from the lineage manifest's engine
        instance when the metadata store still has it."""
        store = self.registry_store
        if store is None:
            raise RuntimeError("no model registry configured (registry_dir)")
        manifest = store.get_manifest(self.manifest.engine_id, version)
        if manifest is None:
            raise ValueError(f"unknown model version {version!r}")
        blob = store.load_blob(self.manifest.engine_id, version)
        persisted = model_io.deserialize_models(blob)
        engine_params = self.engine_params
        if manifest.instance_id:
            instance = self.storage.get_meta_data_engine_instances().get(
                manifest.instance_id
            )
            if instance is not None:
                engine_params = self._engine_params_of(instance)
        ctx = WorkflowContext(mode="serving", _storage=self.storage)
        models = self.engine.prepare_deploy(ctx, engine_params, persisted)
        # pin the version's ANN index (if the manifest carries one) onto
        # the model object BEFORE warmup compiles the serving programs
        ann_lifecycle.attach_from_registry(
            store, self.manifest.engine_id, version, models
        )
        _, _, algorithms, serving = self.engine.make_components(engine_params)
        self._warmup_components(algorithms, models)
        return Lane(
            algorithms, serving, models, version, manifest.instance_id, engine_params
        )

    async def _rollout_loop(self) -> None:
        """Controller heartbeat: evaluate the bake gates on a cadence and
        apply the verdict. Promotion takes the reload lock so it can never
        interleave with a /reload commit."""
        while True:
            await asyncio.sleep(self.config.bake_check_interval_s)
            try:
                # profile-on-alert rides the same heartbeat: SLO alert
                # transitions capture host stacks (the eval is counter
                # math; the capture itself runs on its own thread)
                self._check_slo_alerts()
                await self._rollout_tick()
            except asyncio.CancelledError:
                raise
            except Exception:
                logger.exception("rollout controller tick failed")

    async def _rollout_tick(self) -> None:
        if self._candidate is None:
            return
        verdict, reason = self.rollout_controller.evaluate()
        loop = asyncio.get_running_loop()
        # the bake gate's health veto outranks everything, bandit or not: a
        # reward-winning arm that 5xxes or blows the latency ratio still
        # rolls back through the same path
        if verdict == VERDICT_ROLLBACK:
            # "error-rate gate: ..." -> label "error-rate", detail = full text
            await loop.run_in_executor(
                None, self._rollback_candidate, reason.split(" ")[0], reason
            )
            return
        bandit = self.bandit
        if bandit is None or not bandit.active:
            # plain PR-4 bake gate: time + health decide
            if verdict == VERDICT_PROMOTE:
                async with self._reload_lock:
                    version = await loop.run_in_executor(
                        None, self._promote_candidate
                    )
                if version:
                    logger.info("auto-promoted %s: %s", version, reason)
            return
        # bandit engaged: the bake gate doubles as reward accounting. The
        # tick drains feedback from the event store (blocking reads:
        # executor), credits the posteriors, and the policy re-chooses the
        # live traffic fraction. The REWARD posterior owns promote/retire;
        # the controller's promote verdict acts as the health+window
        # precondition (both evidence floors must clear).
        decision = await loop.run_in_executor(None, bandit.tick)
        if decision is None:
            return  # rollout flipped underneath the tick
        if decision.verdict == DECIDE_PROMOTE and verdict == VERDICT_PROMOTE:
            async with self._reload_lock:
                version = await loop.run_in_executor(
                    None, self._promote_candidate
                )
            if version:
                logger.info("bandit promoted %s: %s", version, decision.reason)
        elif decision.verdict == DECIDE_RETIRE:
            await loop.run_in_executor(
                None,
                self._rollback_candidate,
                "bandit-retire",
                decision.reason,
            )
        else:
            self._bandit_apply_fraction(decision.fraction)

    # ------------------------------------------- fleet registry coordination
    async def _registry_sync_loop(self) -> None:
        """Fleet heartbeat (docs/fleet.md): poll the registry's cheap
        ``state_generation()`` and reconcile local lanes whenever another
        process moved it — a promote/rollback/stage issued through ANY
        replica, the gateway, or the CLI propagates to every worker, and
        each per-process result cache flushes on the transition."""
        while True:
            await asyncio.sleep(self.config.registry_sync_interval_s)
            try:
                await self._registry_sync_tick()
            except asyncio.CancelledError:
                raise
            except Exception:
                logger.exception("registry sync tick failed")

    async def _registry_sync_tick(self) -> None:
        store = self.registry_store
        if store is None:
            return
        loop = asyncio.get_running_loop()
        # generation probe is ONE small-file read — the cadence can be
        # aggressive without scanning manifests every tick
        gen = await loop.run_in_executor(
            None, store.state_generation, self.manifest.engine_id
        )
        if gen == self._seen_state_gen:
            return
        # the reload lock serializes against HTTP-driven stage/promote/
        # rollback and /reload, so reconciliation never interleaves with a
        # locally-initiated transition half-way through its own commit
        async with self._reload_lock:
            state = await loop.run_in_executor(
                None, store.get_state, self.manifest.engine_id
            )
            if await self._reconcile_registry_state(state):
                self._seen_state_gen = state.generation
            # else: a lane failed to load (transient I/O, artifact not yet
            # visible) — leave the seen generation behind so the NEXT tick
            # retries instead of never adopting this transition

    async def _reconcile_registry_state(self, state) -> bool:
        """Make local serving lanes match the registry's rollout state.
        Local transitions (which wrote that state themselves) reconcile to
        a no-op; remote ones are adopted without re-persisting. Returns
        False when a referenced version could not be loaded — the caller
        must retry the same generation on its next tick."""
        loop = asyncio.get_running_loop()
        # 1) the stable pin moved
        if state.stable and state.stable != self._active.version:
            cand = self._candidate
            if cand is not None and cand.version == state.stable:
                # another replica's bake gate promoted the candidate we
                # are baking: same lane objects, just swap locally
                await loop.run_in_executor(None, self._promote_candidate, False)
                logger.info("fleet-sync: adopted promote of %s", state.stable)
            else:
                try:
                    lane = await loop.run_in_executor(
                        None, self._load_lane_from_registry, state.stable
                    )
                except Exception:
                    logger.exception(
                        "fleet-sync: pinned stable %s unloadable; still "
                        "serving %s",
                        state.stable,
                        self._active.version,
                    )
                    return False
                self._adopt_stable(lane)
        # 2) the candidate changed
        cand = self._candidate
        if state.candidate and state.candidate != self._active.version:
            plan_changed = cand is not None and (
                self._plan.mode != state.mode
                or (
                    state.mode == MODE_CANARY
                    and abs(self._plan.fraction - state.fraction) > 1e-9
                )
            )
            if cand is not None and cand.version == state.candidate and not plan_changed:
                return True  # already baking exactly this rollout
            if cand is not None and cand.version == state.candidate:
                lane = cand  # plan change only: reuse the loaded lane
            else:
                try:
                    lane = await loop.run_in_executor(
                        None, self._load_lane_from_registry, state.candidate
                    )
                except Exception:
                    logger.exception(
                        "fleet-sync: staged candidate %s unloadable",
                        state.candidate,
                    )
                    return False
            await loop.run_in_executor(
                None,
                lambda: self.stage_candidate_lane(
                    lane,
                    mode=state.mode,
                    fraction=state.fraction,
                    persist=False,
                ),
            )
            logger.info(
                "fleet-sync: adopted staged candidate %s (%s)",
                state.candidate,
                state.mode,
            )
        elif not state.candidate and cand is not None:
            # unstaged/rolled back elsewhere (possibly by a peer's breaker
            # trip): drop the local lane too, without re-persisting
            await loop.run_in_executor(
                None,
                lambda: self._rollback_candidate(
                    "fleet-sync",
                    "registry candidate cleared by another process",
                    persist=False,
                ),
            )
        return True

    def _adopt_stable(self, lane: Lane) -> None:
        """Swap in a stable version pinned by another process — /reload's
        commit semantics (atomic Lane swap, retired lane's cache entries
        flushed) without the metadata-store resolution. A local bake in
        flight is rebased on the new stable, exactly like /reload."""
        with self._rollout_mutex:
            self._rollout_gen += 1
            retired = self._active.version
            self._active = lane
            if lane.instance_id:
                self.instance_id = lane.instance_id
            if lane.engine_params is not None:
                self.engine_params = lane.engine_params
            cand = self._candidate
            if cand is not None:
                self.rollout_controller.begin(
                    lane.version, cand.version, self._plan.mode
                )
        self._cache_flush(retired, f"fleet-sync stable -> {lane.version}")
        logger.info(
            "fleet-sync: adopted stable %s (was %s)", lane.version, retired
        )

    def _models_snapshot(self) -> dict[str, Any]:
        stable = self._active
        cand = self._candidate
        plan = self._plan
        inst = self._rollout_instruments

        def lane_json(lane: Lane) -> dict[str, Any]:
            return {
                "version": lane.version,
                "instanceId": lane.instance_id,
                "counters": inst.lane_counts(lane.version),
                "p95PredictMs": round(inst.p95_seconds(lane.version) * 1e3, 3),
            }

        out: dict[str, Any] = {
            "stable": lane_json(stable),
            "candidate": lane_json(cand) if cand is not None else None,
            "mode": plan.mode,
            "fraction": plan.fraction,
            "stickyKeyField": self.config.sticky_key_field,
            "candidateBreaker": self.candidate_breaker.snapshot(),
            "controller": self.rollout_controller.snapshot(),
        }
        if self.registry_store is not None:
            state = self.registry_store.get_state(self.manifest.engine_id)
            out["registry"] = {
                "dir": self.registry_store.base_dir,
                # the fleet-coordination change detector, surfaced so
                # dashboards and peers can watch for cross-process moves
                # without reading the whole state
                "stateGeneration": state.generation,
                "state": state.to_json_dict(),
                "versions": [
                    m.summary_row()
                    for m in self.registry_store.list_versions(
                        self.manifest.engine_id
                    )
                ],
            }
        return out

    async def handle_models(self, request: web.Request) -> web.Response:
        """What serves, what bakes, what the controller thinks — the JSON
        behind `pio models show --url` and the dashboard's rollout panel.
        The snapshot scans registry manifests on disk: executor, not event
        loop — a dashboard polling /models on a slow volume must never
        stall /queries.json ingress."""
        snapshot = await asyncio.get_running_loop().run_in_executor(
            None, self._models_snapshot
        )
        return web.json_response(snapshot)

    async def handle_models_candidate(self, request: web.Request) -> web.Response:
        """Stage a registry version as the rollout candidate."""
        try:
            body = await request.json()
            version = str(body["version"])
            mode = body.get("mode", MODE_CANARY)
            fraction = float(body.get("fraction", 0.1))
        except Exception:
            return web.json_response(
                {"message": "body must be JSON with a 'version' key"}, status=400
            )
        async with self._reload_lock:
            try:
                loop = asyncio.get_running_loop()
                lane = await loop.run_in_executor(
                    None, self._load_lane_from_registry, version
                )
                # staging persists registry state (fsync'd write): executor
                await loop.run_in_executor(
                    None,
                    lambda: self.stage_candidate_lane(
                        lane, mode=mode, fraction=fraction
                    ),
                )
            except (ValueError, RuntimeError) as exc:
                return web.json_response({"message": str(exc)}, status=400)
            except Exception as exc:
                logger.exception("staging candidate failed")
                return web.json_response({"message": str(exc)}, status=500)
        return web.json_response(
            {
                "message": "Candidate staged",
                "version": version,
                "mode": mode,
                "fraction": fraction,
            }
        )

    async def handle_models_promote(self, request: web.Request) -> web.Response:
        """Promote the staged candidate. An explicit ``{"version": ...}``
        in the body is a guard, not a selector: it must name the staged
        candidate, or nothing happens (409) — silently promoting whatever
        is staged when the operator asked for a specific version is how
        the wrong model ships."""
        requested = None
        if request.can_read_body:
            try:
                requested = (await request.json()).get("version")
            except Exception:
                pass
        async with self._reload_lock:
            if requested is not None:
                cand = self._candidate
                if cand is None or cand.version != requested:
                    staged = cand.version if cand is not None else "none"
                    return web.json_response(
                        {
                            "message": (
                                f"version {requested} is not the staged "
                                f"candidate (staged: {staged})"
                            )
                        },
                        status=409,
                    )
            version = await asyncio.get_running_loop().run_in_executor(
                None, self._promote_candidate
            )
        if version is None:
            return web.json_response(
                {"message": "no candidate staged"}, status=404
            )
        return web.json_response(
            {
                "message": "Promoted",
                "version": version,
                "instanceId": self.instance_id,
            }
        )

    async def handle_models_rollback(self, request: web.Request) -> web.Response:
        version = await asyncio.get_running_loop().run_in_executor(
            None, self._rollback_candidate, "manual"
        )
        if version is None:
            return web.json_response(
                {"message": "no candidate staged"}, status=404
            )
        return web.json_response({"message": "Rolled back", "version": version})

    async def handle_metrics(self, request: web.Request) -> web.Response:
        """Prometheus text exposition: request latency histogram, phase
        waterfall, queue depth, shed/deadline/watchdog counters, breaker
        state, jit recompile count — everything `pio top` and a Prometheus
        scrape need. OpenMetrics negotiation (Accept header or
        ``?exemplars=1``) adds per-bucket trace-id exemplars."""
        return metrics_response(self.metrics, request)

    async def handle_slo(self, request: web.Request) -> web.Response:
        """Burn-rate report for the declared objectives plus the phase
        waterfall summary — the JSON behind the `pio top` SLO line."""
        body = self.slo.report()
        body["phases"] = self.waterfall.snapshot()
        return web.json_response(body)

    async def handle_traces_recent(self, request: web.Request) -> web.Response:
        return traces_response(self.tracer, request)

    async def handle_profile_capture(self, request: web.Request) -> web.Response:
        """On-demand device capture: ``POST /profile/capture?ms=``. The
        duration is clamped to the configured rails; a capture already in
        flight answers 409 (single-flight — jax keeps one global trace
        session per process). The blocking body (trace sleep + bundle
        writes) runs on an executor, never on the event loop."""
        raw_ms = request.query.get("ms")
        try:
            ms = int(raw_ms) if raw_ms is not None else None
        except ValueError:
            return web.json_response(
                {"message": "ms must be an integer"}, status=400
            )
        loop = asyncio.get_running_loop()
        try:
            path = await loop.run_in_executor(
                None, self._capture_profile, ms, "manual"
            )
        except ProfileBusyError:
            return web.json_response(
                {"message": "a profile capture is already in flight"},
                status=409,
            )
        except Exception as exc:  # noqa: BLE001 - surface, don't 500-blank
            logger.exception("profile capture failed")
            return web.json_response(
                {"message": f"capture failed: {exc}"}, status=500
            )
        return web.json_response(
            {
                "bundle": os.path.basename(path),
                "path": path,
                "durationMs": self.profiler.clamp_ms(ms),
                "modelVersion": self.model_version,
            }
        )

    async def handle_profile_stacks(self, request: web.Request) -> web.Response:
        """The always-on sampler's folded stacks: flamegraph-ready folded
        text by default (``stack count`` lines, pipe into flamegraph.pl),
        the structured snapshot + hotspot table with ``?format=json``
        (what ``pio top --hotspots`` consumes)."""
        if request.query.get("format") == "json":
            body = self.sampler.snapshot()
            body["hotspots"] = self.sampler.hotspots()
            return web.json_response(body)
        return web.Response(
            text=self.sampler.folded(), content_type="text/plain"
        )

    async def handle_stop(self, request: web.Request) -> web.Response:
        self._stop_event.set()
        return web.json_response({"message": "Stopping."})

    async def handle_plugins(self, request: web.Request) -> web.Response:
        return web.json_response(self.plugin_context.to_json_dict())

    # ------------------------------------------------------------------- app
    def make_app(self) -> web.Application:
        app = web.Application()
        app.add_routes(
            [
                web.get("/", self.handle_status),
                web.get("/healthz", self.handle_healthz),
                web.get("/metrics", self.handle_metrics),
                web.get("/slo", self.handle_slo),
                web.get("/traces/recent", self.handle_traces_recent),
                web.post("/queries.json", self.handle_queries),
                # POST is the contract (CreateServer.scala:618-626); the GET
                # spelling still works but logs a deprecation warning
                web.post("/reload", self.handle_reload),
                web.get("/reload", self.handle_reload_get),
                # model registry / progressive rollout surface
                web.get("/models", self.handle_models),
                web.post("/models/candidate", self.handle_models_candidate),
                web.post("/models/promote", self.handle_models_promote),
                web.post("/models/rollback", self.handle_models_rollback),
                web.post("/stop", self.handle_stop),
                web.get("/stop", self.handle_stop),
                web.get("/plugins.json", self.handle_plugins),
                # profiling plane (docs/observability.md §Profiling plane)
                web.post("/profile/capture", self.handle_profile_capture),
                web.get("/profile/stacks", self.handle_profile_stacks),
            ]
        )

        async def _start_rollout_loop(app: web.Application) -> None:
            if self.config.sampler_period_s > 0:
                self.sampler.start()
            self._rollout_task = asyncio.ensure_future(self._rollout_loop())
            if (
                self.registry_store is not None
                and self.config.registry_sync_interval_s > 0
            ):
                self._registry_sync_task = asyncio.ensure_future(
                    self._registry_sync_loop()
                )

        async def _close_batcher(app: web.Application) -> None:
            self.sampler.stop()
            tasks = [self._rollout_task, self._registry_sync_task]
            self._rollout_task = None
            self._registry_sync_task = None
            for task in tasks:
                if task is not None:
                    task.cancel()
            live = [t for t in tasks if t is not None]
            if live:
                await asyncio.gather(*live, return_exceptions=True)
            # cancel the collect loop while its event loop is still alive
            # (otherwise the pending task leaks a "loop is closed" warning)
            self._batcher.close()
            await self._batcher.wait_closed()
            await self._close_background()

        app.on_startup.append(_start_rollout_loop)
        app.on_cleanup.append(_close_batcher)
        return app

    async def _close_background(self) -> None:
        """Cancel fire-and-forget tasks and close the shared HTTP session —
        the 'zero hung asyncio tasks after shutdown' half of the resilience
        contract."""
        for task in list(self._bg_tasks):
            task.cancel()
        if self._bg_tasks:
            await asyncio.gather(*self._bg_tasks, return_exceptions=True)
        self._bg_tasks.clear()
        if self._http_session is not None and not self._http_session.closed:
            await self._http_session.close()
        self._http_session = None

    @property
    def algorithms(self) -> list[Any]:
        return self._active.algorithms

    @property
    def serving(self) -> Any:
        return self._active.serving

    @property
    def models(self) -> list[Any]:
        return self._active.models

    @property
    def model_version(self) -> str:
        return self._active.version

    def _warmup(self) -> None:
        """Pre-compile serving programs (pow2 batch buckets etc.) so the
        first traffic burst after deploy/reload pays no XLA compiles."""
        lane = self._active
        self._warmup_components(lane.algorithms, lane.models)

    def _collect_ann_indexes(self) -> None:
        candidate = self._candidate
        self.ann_instruments.sync_indexes(
            ann_lifecycle.pinned_indexes(
                [self._active.models]
                + ([candidate.models] if candidate is not None else [])
            )
        )

    def _warmup_components(self, algorithms: list[Any], models: list[Any]) -> None:
        # late-bind any registry-attached ANN index to this server's
        # pio_ann_* instruments (the lane loader runs before the metrics
        # registry is in scope). The ANN search buckets warm inside each
        # engine's warmup_serving below — each engine compiles exactly
        # the kernel variant its dispatch actually runs (exclusion /
        # composed-tower), not the generic one
        ann_lifecycle.bind_instruments(models, self.ann_instruments)
        # an algorithm that keeps instruments of its own declares them in
        # this server's registry before it takes traffic
        for algo in algorithms:
            algo.register_metrics(self.metrics)
        # a batch is closed at the least of the operator's limit and the
        # algorithms' own (`BaseAlgorithm.batch_limit`; the lane warmed last
        # decides: a reload brings new models, seldom another algorithm)
        limits = [n for algo in algorithms if (n := algo.batch_limit()) is not None]
        self._batcher.max_batch = max(1, min([self.config.max_batch_size, *limits]))
        # a warmup failure is not swallowed: a program the device's
        # compiler refuses would otherwise be paid for, or thrown, on the
        # first request. At startup it fails the start; /reload and the
        # registry lane loaders catch it, keep the old lane and report.
        for algo, model in zip(algorithms, models):
            algo.warmup_serving(model, self.config.max_batch_size)
        # where the resident serving arrays sit, read from the arrays
        # themselves (GET / reports it)
        self.serving_devices = xray.live_devices()
        # baseline the compile watcher AFTER warmup: the compiles warmup
        # just paid for are intentional; only compiles past this point are
        # serving-time recompiles worth alarming on
        try:
            self.compile_watcher.sample()
        except Exception:
            logger.exception("compile watcher baseline failed (continuing)")

    async def start(self) -> None:
        await asyncio.get_running_loop().run_in_executor(None, self._warmup)
        self.gc_watcher.install()  # removed by stop()
        retries = max(1, self.config.bind_retries)
        last_error: Exception | None = None
        for attempt in range(retries):
            # fresh runner+site per attempt: a TCPSite cannot be re-started
            # after a failed bind (it stays registered with the runner).
            # access_log=None: per-request access-line formatting is host
            # glue on the respond phase; request accounting is owned by
            # the metrics registry + waterfall instead
            self._runner = web.AppRunner(self.make_app(), access_log=None)
            await self._runner.setup()
            site = web.TCPSite(
                self._runner,
                self.config.ip,
                self.config.port,
                ssl_context=self.config.ssl_context(),
            )
            try:
                await site.start()
                break
            except OSError as exc:  # bind retry (ref MasterActor x3)
                last_error = exc
                await self._runner.cleanup()
                self._runner = None
                logger.warning(
                    "bind %s:%d failed (attempt %d/%d): %s",
                    self.config.ip,
                    self.config.port,
                    attempt + 1,
                    retries,
                    exc,
                )
                if attempt + 1 < retries:
                    await asyncio.sleep(1.0)
        else:
            raise last_error  # type: ignore[misc]
        logger.info(
            "engine server on %s:%d, device %s",
            self.config.ip,
            self.config.port,
            json.dumps(self.serving_devices),
        )

    async def drain(self) -> None:
        """Graceful drain (the SIGTERM path): stop accepting, let the
        micro-batcher flush and every in-flight request answer, then
        return — so a supervised restart or rolling redeploy is 5xx-free
        even without a gateway in front.

        Order matters: (1) mark draining so /healthz goes unready and a
        load balancer routes around us; (2) close the listener — NEW
        connections are refused at the TCP level (the client/gateway
        retries elsewhere), which is not a 5xx; (3) wait out the
        admission queue + dispatch pipeline + handler tail, bounded by
        ``drain_grace_s``. The batcher keeps running the whole time, so
        queued queries dispatch and answer normally. Idempotent."""
        if self._draining:
            return
        self._draining = True
        logger.info("drain: listener closing, answering in-flight requests")
        if self._runner is not None:
            for site in list(self._runner.sites):
                try:
                    await site.stop()
                except Exception:
                    logger.exception("drain: site stop failed (continuing)")
        deadline = time.perf_counter() + max(0.0, self.config.drain_grace_s)
        b = self._batcher
        while time.perf_counter() < deadline:
            if (
                self._inflight_requests == 0
                and b.queue_depth == 0
                and not b._finish_tasks
            ):
                break
            await asyncio.sleep(0.02)
        leftover = self._inflight_requests
        if leftover:
            logger.warning(
                "drain grace (%.1fs) expired with %d requests in flight",
                self.config.drain_grace_s,
                leftover,
            )
        else:
            logger.info("drain complete: zero requests in flight")

    def begin_drain(self) -> None:
        """Signal-handler entry (``loop.add_signal_handler`` callbacks
        must not block): drain, then release ``run_until_stopped``. The
        task is held on its own attribute — ``stop()``'s background-task
        sweep only runs after the drain has already set the stop event,
        so the drain can never be cancelled by the shutdown it causes."""

        async def _go() -> None:
            await self.drain()
            self._stop_event.set()

        self._drain_task = asyncio.ensure_future(_go())

    async def stop(self) -> None:
        self.gc_watcher.remove()
        self._batcher.close()
        await self._batcher.wait_closed()
        self._sniffer_pool.shutdown(wait=False, cancel_futures=True)
        self._shadow_pool.shutdown(wait=False, cancel_futures=True)
        await self._close_background()
        if self._runner is not None:
            await self._runner.cleanup()
            self._runner = None

    async def run_until_stopped(self) -> None:
        await self.start()
        await self._stop_event.wait()
        await self.stop()


def _engine_params_of_instance(engine: Engine, instance: EngineInstance) -> EngineParams:
    variant = {
        "datasource": {"params": json.loads(instance.data_source_params or "{}")},
        "preparator": {"params": json.loads(instance.preparator_params or "{}")},
        "algorithms": json.loads(instance.algorithms_params or "[]"),
        "serving": {"params": json.loads(instance.serving_params or "{}")},
    }
    return engine.engine_params_from_variant(variant)


def create_query_server(
    engine_dir: str,
    variant_path: str | None = None,
    storage: Storage | None = None,
    config: ServerConfig | None = None,
    instance_id: str | None = None,
) -> QueryServer:
    """Build a server for the engine dir. With a registry configured, the
    registry's pinned *stable* version is the source of truth for what
    serves (docs/DECISIONS.md — the instances table is the training
    ledger); without one, or when the registry can't be read, the latest
    COMPLETED instance is resolved exactly as the reference did
    (ref commands/Engine.deploy :207-242)."""
    storage = storage or Storage.instance()
    config = config or ServerConfig()
    manifest, engine = load_engine(engine_dir, variant_path)
    store = ArtifactStore(config.registry_dir) if config.registry_dir else None
    instances = storage.get_meta_data_engine_instances()
    if store is not None and not instance_id:
        state = store.get_state(manifest.engine_id)
        if state.stable:
            try:
                return _query_server_from_registry(
                    engine, manifest, store, state.stable, storage, config
                )
            except Exception:
                logger.exception(
                    "registry stable %s unusable; falling back to the "
                    "latest COMPLETED instance",
                    state.stable,
                )
    if instance_id:
        instance = instances.get(instance_id)
        if instance is None:
            raise RuntimeError(f"engine instance {instance_id} not found")
    else:
        instance = instances.get_latest_completed(
            manifest.engine_id, manifest.version, manifest.variant
        )
        if instance is None:
            raise RuntimeError(
                f"no COMPLETED engine instance for {manifest.engine_id} "
                f"{manifest.version} {manifest.variant}; run train first"
            )
    engine_params = engine.engine_params_from_variant(manifest.variant_json)
    ctx = WorkflowContext(mode="serving", _storage=storage)
    models = load_models_for_instance(
        engine, engine_params, instance.id, ctx=ctx, storage=storage
    )
    return QueryServer(
        engine=engine,
        engine_params=engine_params,
        models=models,
        manifest=manifest,
        instance_id=instance.id,
        storage=storage,
        config=config,
        registry_store=store,
    )


def _query_server_from_registry(
    engine: Engine,
    manifest: EngineManifest,
    store: ArtifactStore,
    version: str,
    storage: Storage,
    config: ServerConfig,
) -> QueryServer:
    """Deploy the registry's stable version: verified blob -> deserialize
    -> prepare_deploy, params from the lineage manifest's instance."""
    reg_manifest = store.get_manifest(manifest.engine_id, version)
    if reg_manifest is None:
        raise RuntimeError(f"registry stable {version} has no manifest")
    blob = store.load_blob(manifest.engine_id, version)
    persisted = model_io.deserialize_models(blob)
    engine_params = None
    if reg_manifest.instance_id:
        instance = storage.get_meta_data_engine_instances().get(
            reg_manifest.instance_id
        )
        if instance is not None:
            engine_params = _engine_params_of_instance(engine, instance)
    if engine_params is None:
        engine_params = engine.engine_params_from_variant(manifest.variant_json)
    ctx = WorkflowContext(mode="serving", _storage=storage)
    models = engine.prepare_deploy(ctx, engine_params, persisted)
    ann_lifecycle.attach_from_registry(store, manifest.engine_id, version, models)
    logger.info(
        "deploying registry stable %s (instance %s)",
        version,
        reg_manifest.instance_id or "?",
    )
    return QueryServer(
        engine=engine,
        engine_params=engine_params,
        models=models,
        manifest=manifest,
        instance_id=reg_manifest.instance_id or version,
        storage=storage,
        config=config,
        registry_store=store,
        model_version=version,
    )


def _maybe_install_uvloop() -> bool:
    """Swap in uvloop when available (PIO_UVLOOP=0 opts out): the query
    hot path is event-loop-bound once device work is micro-batched, and
    uvloop's C event loop shaves the per-request asyncio overhead. A
    missing uvloop is silently fine — it is optional by contract (the
    container image must not need it)."""
    import os

    if os.environ.get("PIO_UVLOOP", "1").lower() in ("0", "false", "no"):
        return False
    try:
        import uvloop  # type: ignore[import-not-found]
    except ImportError:
        return False
    uvloop.install()
    logger.info("uvloop installed for the query server event loop")
    return True


def run_query_server(
    engine_dir: str,
    variant_path: str | None = None,
    config: ServerConfig | None = None,
) -> None:
    _maybe_install_uvloop()
    server = create_query_server(engine_dir, variant_path, config=config)

    async def main():
        import signal

        # SIGTERM = graceful drain, not teardown-with-requests-in-flight:
        # the listener closes, the micro-batcher flushes, in-flight
        # queries answer, THEN the process exits — what a supervisor's
        # rolling restart (fleet/supervisor.py) relies on for zero 5xx
        try:
            asyncio.get_running_loop().add_signal_handler(
                signal.SIGTERM, server.begin_drain
            )
        except (NotImplementedError, RuntimeError):
            pass  # loop without signal support: default SIGTERM applies
        await server.run_until_stopped()

    asyncio.run(main())
